"""What the program writes into a profile: its spans on the host plane and
one named scope per encode level in the HLO (``bench/program_trace.py``).

The fixture ``train_cpu_trace.json`` / ``train_cpu_hlo.txt`` is a CPU
profile of two train steps of a 4-level nvr field (level 0 dense, 1-3
hashed) with the tracer in profile mode, cut as ``read_xplane`` would cut
a chip's (each XLA thread of the CPU stands for one device), and the
chunk program's HLO cut to each instruction's name, call and op_name.
``python tests/bench/test_bench_program_trace.py`` records it again.
"""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_trace as pt  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench import work  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
GRID = {"dim": 3, "n_levels": 4, "n_features": 2, "log2_table_size": 8,
        "base_resolution": 4, "growth": 1.5, "kind": "hash"}


def _fixture():
    trace = json.loads((FIXTURES / "train_cpu_trace.json").read_text())
    hlo = (FIXTURES / "train_cpu_hlo.txt").read_text()
    return trace, hlo


# ------------------------------------------------------------ op names
def test_level_of_op_name():
    assert pt.level_of_op_name(
        "jit(fn)/encode/lvl07_hash/jit(_take)/gather") == "lvl07_hash/forward"
    assert pt.level_of_op_name(
        "jit(chunk)/while/body/closed_call/jvp(encode)/lvl00_dense/mul"
    ) == "lvl00_dense/forward"
    assert pt.level_of_op_name(
        "jit(chunk)/while/body/transpose(jvp(encode))/lvl01_hash/"
        "jit(_take)/scatter-add") == "lvl01_hash/transpose"
    assert pt.level_of_op_name(
        "jit(fn)/transpose(jvp(lvl02_dense))/add_any") == "lvl02_dense/transpose"
    assert pt.level_of_op_name("jit(fn)/jvp(lvl02_dense)/mul") == (
        "lvl02_dense/forward")
    assert pt.level_of_op_name("jit(fn)/encode/concatenate") is None
    assert pt.level_of_op_name("jit(fn)/xlvl02_dense/mul") is None
    assert pt.level_of_op_name("jit(fn)/lvl07_hash") is None   # a primitive
    # the phase join leaves every level's op in encode
    assert tr.phase_of_op_name(
        "jit(fn)/encode/lvl07_hash/jit(_take)/gather") == "encode"


def test_scope_map_with_phases_is_the_phase_map():
    hlo = (FIXTURES / "tile_hlo.txt").read_text()
    assert pt.scope_map([hlo], tr.phase_of_op_name) == tr.phase_map([hlo])


# ------------------------------------------------------ the CPU fixture
def test_level_seconds_sum_to_the_encode_phase():
    trace, hlo = _fixture()
    pmap, lmap = tr.phase_map([hlo]), pt.level_map([hlo])
    level_s = pt.level_seconds(trace, lmap)
    assert {k.split("/")[0] for k in level_s} == {
        "lvl00_dense", "lvl01_hash", "lvl02_hash", "lvl03_hash"}
    # every op of a level scope is an encode op; the encode's remainder
    # is what lies outside every level (the features' concatenation)
    assert all(pmap[k] == "encode" for k, v in lmap.items() if v != "other")
    rest = {k: v for k, v in pmap.items()
            if v == "encode" and lmap[k] == "other"}
    w0, w1 = tr.window_of(trace)
    lookup = tr.phase_lookup(rest)
    rest_s = 0.0
    for dev, ops in trace["devices"].items():
        clipped = [(max(s, w0), min(s + d, w1), n, m) for n, m, s, d in ops
                   if min(s + d, w1) > max(s, w0)]
        own = tr._self_times([(s, e) for s, e, _, _ in clipped])
        rest_s += sum(o for (_, _, n, m), o in zip(clipped, own)
                      if lookup(m, n) == "encode") * 1e-9
    rest_s /= len(trace["devices"])
    r = tr.reduce_trace(trace, pmap)
    assert sum(level_s.values()) + rest_s == pytest.approx(
        r["phase_s"]["encode"], rel=1e-9)
    assert sum(level_s.values()) > 0.5 * r["phase_s"]["encode"]


def test_forward_and_transpose_split():
    trace, hlo = _fixture()
    lmap = pt.level_map([hlo])
    passes = {}
    for key in lmap.values():
        if key != "other":
            scope, p = key.split("/")
            passes.setdefault(scope, set()).add(p)
    assert all(p == {"forward", "transpose"} for p in passes.values())
    # the table gradient's scatter is in the transpose, the gathers in
    # the forward pass
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and m.group(1).endswith("/scatter-add"):
            assert pt.level_of_op_name(m.group(1)).endswith("/transpose")
        if m and m.group(1).endswith("/gather") and "lvl" in m.group(1):
            assert "transpose(" not in m.group(1)
            assert pt.level_of_op_name(m.group(1)).endswith("/forward")
    level_s = pt.level_seconds(trace, lmap)
    assert sum(v for k, v in level_s.items() if k.endswith("/forward")) > 0
    assert sum(v for k, v in level_s.items()
               if k.endswith("/transpose")) > 0


def test_program_spans_of_the_fixture():
    trace, _ = _fixture()
    spans = trace["program_spans"]
    names = {s[0] for s in spans}
    assert names == {"train.chunk", "train.dispatch", "train.sync",
                     "train.host"}
    chunks = [s for s in spans if s[0] == "train.chunk"]
    # the window opens inside step 1's host work and closes inside step
    # 3's, so of the chunks only step 2's lies whole in the profile
    assert [c[3]["start"] for c in chunks] == ["2"]
    assert all(c[3]["n_steps"] == "1" for c in chunks)
    # step 2's dispatch, sync and host lie inside its chunk, in order;
    # step 3's dispatch and sync follow it
    (_, c0, cd, _), = chunks
    inside = [(n, s) for n, s, d, _ in spans
              if n != "train.chunk" and c0 <= s and s + d <= c0 + cd]
    assert [n for n, _ in sorted(inside, key=lambda x: x[1])] == [
        "train.dispatch", "train.sync", "train.host"]
    after = [n for n, s, _, _ in spans if s >= c0 + cd]
    assert sorted(after) == ["train.dispatch", "train.sync"]


def test_idle_by_span_of_the_fixture_adds_up_to_the_idle_time():
    trace, hlo = _fixture()
    r = tr.reduce_trace(trace, tr.phase_map([hlo]))
    idle = pt.idle_by_span(trace, trace["program_spans"])
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert set(idle) <= {"train.chunk", "train.dispatch", "train.sync",
                         "train.host", "none"}
    assert idle["train.sync"] > 0


# --------------------------------------------------------- hand-made
def test_idle_by_span_takes_the_innermost_span_of_each_part():
    # window 0..100; the device is busy 10-20 and 60-90, so it idles
    # 0-10, 20-60 and 90-100; a chunk 5-95 holds dispatch 5-15, sync
    # 15-50 and host 50-95
    trace = {"devices": {"d": [["op", "m", 10, 10], ["op", "m", 60, 30]]},
             "spans": [["bench.window", 0, 100]]}
    spans = [["train.chunk", 5, 90, {}], ["train.dispatch", 5, 10, {}],
             ["train.sync", 15, 35, {}], ["train.host", 50, 45, {}]]
    idle = pt.idle_by_span(trace, spans)
    assert idle == pytest.approx({
        "none": 10e-9,                  # 0-5 and 95-100
        "train.dispatch": 5e-9,         # 5-10
        "train.sync": 30e-9,            # 20-50
        "train.host": 15e-9,            # 50-60 and 90-95
    })


def test_span_seconds_are_clipped_to_the_window():
    trace = {"devices": {}, "spans": [["bench.window", 100, 100]]}
    spans = [["train.host", 50, 100, {}], ["train.host", 190, 20, {}],
             ["train.sync", 120, 10, {}]]
    assert pt.span_seconds(spans, trace, "train.host") == pytest.approx(
        60e-9)


# ---------------------------------------------------------------- work
@pytest.mark.parametrize("grid", [
    GRID,
    json.loads((ROOT / "bench/configs/nvr_hash.json").read_text())["grid"],
    json.loads((ROOT / "bench/configs/gia_hash.json").read_text())["grid"],
], ids=["small", "nvr_hash", "gia_hash"])
def test_encode_work_sums_to_the_whole_grid(grid):
    n = 4096 * 32
    levels = range(grid["n_levels"])
    f, b = pt.encode_work(grid, n, levels)
    assert f == pytest.approx(work.encode_flops(grid, n), rel=1e-12)
    assert b == pytest.approx(work.encode_bytes(grid, n), rel=1e-12)
    fb, bb = pt.encode_work(grid, n, levels, backward=True)
    assert f + fb == pytest.approx(work.encode_flops(grid, n, True),
                                   rel=1e-12)
    assert b + bb == pytest.approx(work.encode_bytes(grid, n, True),
                                   rel=1e-12)
    hashed = [l for l in levels if work.level_is_hashed(grid, l)]
    dense = [l for l in levels if not work.level_is_hashed(grid, l)]
    parts = [pt.encode_work(grid, n, ls) for ls in (hashed, dense)]
    assert sum(p[0] for p in parts) == pytest.approx(f, rel=1e-12)
    assert sum(p[1] for p in parts) == pytest.approx(b, rel=1e-12)


# ------------------------------------------------------------ readings
def _cell(name):
    from bench import harness
    return harness.load_cell(name)


def test_readings_of_the_fixture():
    from bench import peaks
    trace, hlo = _fixture()
    cell = _cell("nvr_hash.train")
    cell.config = {**cell.config, "grid": GRID}
    level_s = pt.level_seconds(trace, pt.level_map([hlo]))
    counts = {"steps": 2, "rays_per_step": 16, "n_samples": 4}
    got = pt.readings(cell, counts, peaks.peaks_for("TPU v5 lite"), 1,
                      level_s, trace["program_spans"], trace)
    assert set(got) == {"encode_transpose_roofline.train",
                        "host_ms_per_step.train"}
    assert 0 < got["encode_transpose_roofline.train"] < 100
    host = (pt.span_seconds(trace["program_spans"], trace, "train.dispatch")
            + pt.span_seconds(trace["program_spans"], trace, "train.host"))
    assert got["host_ms_per_step.train"] == pytest.approx(host / 2 * 1e3)


def test_readings_are_absent_without_scopes_or_spans():
    """A program without level scopes or profiled spans (the chip trace
    of the tile fixture) gives none of the readings."""
    from bench import peaks
    trace = json.loads((FIXTURES / "tile_trace.json").read_text())
    hlo = (FIXTURES / "tile_hlo.txt").read_text()
    level_s = pt.level_seconds(trace, pt.level_map([hlo]))
    assert level_s == {}
    p = peaks.peaks_for("TPU v5 lite")
    serve = {"held_requests": 1, "tile_pixels": 4096, "n_samples": 32}
    assert pt.readings(_cell("nvr_hash.tiles"), serve, p, 1, level_s, [],
                       trace) == {}
    assert pt.readings(_cell("gia_hash.pan"), serve, p, 1, level_s, [],
                       trace) == {}
    train = {"steps": 2, "rays_per_step": 8192, "n_samples": 32}
    assert pt.readings(_cell("nvr_hash.train"), train, p, 1, level_s, [],
                       trace) == {}
    r = tr.reduce_trace(trace, tr.phase_map([hlo]))
    assert pt.idle_by_span(trace, []) == pytest.approx(
        {"none": r["window_s"] - r["busy_s"]}, rel=1e-9)


# -------------------------------------------------------------- record
def _cut_hlo(text: str) -> str:
    """Each instruction's name, call and op_name, where it has either;
    nothing else."""
    out = []
    for line in text.splitlines():
        if line.startswith("HloModule"):
            out.append(line.split(",")[0])
        elif " = " not in line:
            if tr._COMPUTATION.match(line) or line.strip() == "}":
                out.append(line)
        else:
            head = line.split(" = ", 1)[0]
            calls = tr._CALLS.search(line)
            op = tr._OP_NAME.search(line)
            if not (calls or op):
                continue
            out.append(head + " = op()"
                       + (f", calls=%{calls.group(1)}" if calls else "")
                       + (f', metadata={{op_name="{op.group(1)}"}}'
                          if op else ""))
    return "\n".join(out) + "\n"


def record(out_dir: Path = FIXTURES):
    """Record the CPU fixture again (two steps after two of warm-up)."""
    import glob
    import tempfile

    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import train as train_mod
    from repro.core.encoding import GridConfig
    from repro.core.fields import FieldConfig
    from repro.core.mlp import MLPConfig
    from repro.obs.trace import TRACER

    g = GridConfig(**GRID)
    cfg = FieldConfig(app="nvr", grid=g, name="fixture",
                      mlp=MLPConfig(in_dim=g.out_dim, hidden_dim=16,
                                    n_hidden=2, out_dim=4))
    tmp = tempfile.mkdtemp()
    held = {}
    engines = []

    def on_metrics(i, row, st):
        if i == 1:
            jax.profiler.start_trace(tmp)
            held["window"] = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            held["window"].__enter__()
            TRACER.enable(buffer=False, profile=True)
        if i == 3:
            held["window"].__exit__(None, None, None)
            TRACER.disable()
            jax.profiler.stop_trace()

    train_mod.train_field(cfg, steps=4, batch_size=16, seed=0,
                          chunk_steps=1, on_metrics=on_metrics,
                          on_engine=engines.append, n_samples=4,
                          gt_samples=4)
    hlo = engines[0].compiled_chunk(1).as_text()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" in stats:
                    devices.setdefault(f"{plane.name}/{line.name}", []).append(
                        [str(stats["hlo_op"]), str(stats["hlo_module"]),
                         float(ev.start_ns), float(ev.duration_ns)])
                elif ev.name == tr.WINDOW_SPAN:
                    spans.append([ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)])
    trace = {"devices": devices, "spans": spans,
             "program_spans": pt.read_program_spans(path)}
    (out_dir / "train_cpu_trace.json").write_text(json.dumps(trace) + "\n")
    (out_dir / "train_cpu_hlo.txt").write_text(_cut_hlo(hlo))


if __name__ == "__main__":
    record()
