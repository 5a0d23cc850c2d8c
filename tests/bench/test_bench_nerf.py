"""The nerf cell of BENCHMARK.json: its configuration is the Table I file
the nerf fixture cells run, and what nerf adds to the field (the
direction encode, the density MLP, the colour MLP) is named in the
compiled program by scopes that the phase join keeps in their phases and
that change nothing else."""
import contextlib
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import (FIXTURES, assert_sound, harness,  # noqa: E402
                         rehearse, small_cell)
from bench import program_trace as pt  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

CELL = "nerf_hash.tiles"
# nerf's sub-scopes and the phase each lies in
SUB_SCOPES = {"sh_encode": "encode", "density_mlp": "mlp",
              "color_mlp": "mlp"}


def test_the_cell_runs_the_fixture_configuration():
    """One configuration: the file BENCHMARK.json names is the Table I
    fixture that the tests' nerf cells run (`test_bench_harness`,
    `test_bench_work` check it against Table I), with nvr's traffic."""
    cell = harness.load_cell(CELL)
    fixture = json.loads((FIXTURES / "nerf_hash.json").read_text())
    assert cell.config == fixture
    assert cell.traffic == harness.load_cell("nvr_hash.tiles").traffic
    assert cell.chips == 1 and cell.config["reduced"] == []


def test_the_cell_rehearses_under_its_own_limits():
    """At the tiny size the program reads 1.2e-7 here, well under the
    chip's limit (the fixture cells keep the tests' own limit for the
    larger sizes, where the CPU reads up to 2.5e-6)."""
    cell = small_cell(harness.load_cell(CELL))
    assert cell.limits == harness.load_cell(CELL).limits
    assert_sound(rehearse(cell), cell)


def _compiled() -> str:
    """The optimized HLO of the cell's field at a tiny size on the CPU."""
    import jax
    import jax.numpy as jnp
    from repro.common.param import unbox
    from repro.core import fields
    cfg = harness.field_config(small_cell(harness.load_cell(CELL)).config)
    params = unbox(fields.init_field(jax.random.PRNGKey(0), cfg))[0]
    pts = jax.random.uniform(jax.random.PRNGKey(1), (64, 3))
    dirs = jax.random.normal(jax.random.PRNGKey(2), (64, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    fn = jax.jit(lambda p, x, d: fields.apply_field(p, cfg, x, d))
    return fn.lower(params, pts, dirs).compile().as_text()


def _sub_scope(op_name: str):
    for scope in SUB_SCOPES:
        if f"/{scope}/" in op_name:
            return scope
    return None


def test_sub_scopes_lie_in_their_phase():
    text = _compiled()
    phases = tr.phase_map([text])
    subs = pt.scope_map([text], _sub_scope)
    assert set(subs.values()) == set(SUB_SCOPES) | {"other"}
    for key, scope in subs.items():
        if scope != "other":
            assert phases[key] == SUB_SCOPES[scope], (key, scope)
    for scope, phase in SUB_SCOPES.items():
        assert tr.phase_of_op_name(
            f"jit(tile)/{phase}/{scope}/dot_general") == phase


def _stripped(text: str) -> str:
    """Optimized HLO without its module name, metadata and the source
    tables that metadata points into."""
    out, skip = [], False
    for line in text.splitlines()[1:]:
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and line.startswith("%"):
            skip = False
        if not skip:
            out.append(re.sub(r",?\s*metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def test_sub_scopes_leave_the_program_unchanged(monkeypatch):
    import jax
    from repro.core import fields

    def phases_only(name):
        return (contextlib.nullcontext() if name in SUB_SCOPES
                else jax.named_scope(name))

    scoped = _compiled()
    monkeypatch.setattr(fields, "annotate", phases_only)
    plain = _compiled()
    assert "/sh_encode/" in scoped and "/sh_encode/" not in plain
    assert _stripped(scoped) == _stripped(plain)
