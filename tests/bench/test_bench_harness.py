"""The harness's contract without a chip: BENCHMARK.json's shape, each
cell's files found by name, and the refusal of a run without a TPU."""
import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in WORKLOADS
            assert harness._reports(e2e[m["moves"]], w)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(WORKLOADS) // 2)


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_files_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == name)
    assert cell.config["reduced"] == []
    assert callable(harness.driver(cell.traffic).run)
    assert callable(harness.reference(cell.config).render)
    assert set(cell.limits) and all(v > 0 for v in cell.limits.values())
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]))


def test_no_cell_or_metric_named_in_the_code():
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["per_layer"]])
    for path in (ROOT / "bench").rglob("*.py"):
        text = path.read_text()
        for n in names:
            assert n not in text, f"{n} named in {path}"


def test_no_app_module_named_outside_the_lookup():
    """The drivers take every weight and reference function from
    ``harness.reference(config)``; only the reference's shared modules are
    imported by name."""
    shared = {"field", "scene"}
    named = re.compile(r"bench\.reference\.(\w+)"
                       r"|from bench\.reference import ([\w, ]+)")
    for path in (ROOT / "bench").rglob("*.py"):
        for m in named.finditer(path.read_text()):
            modules = {n.split()[0] for n in
                       (m.group(1) or m.group(2)).split(",")}
            assert modules <= shared, f"{m.group(0)} in {path}"


def _file_config(name):
    path = (ROOT / "tests" / "bench" / "fixtures" / f"{name}.json"
            if name == "nerf_hash"
            else ROOT / "bench" / "configs" / f"{name}.json")
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", ["nvr_hash", "gia_hash", "nerf_hash"])
def test_field_config_is_table_one(name):
    """The FieldConfig built from a configuration file at Table I widths
    is the program's own Table I row."""
    import dataclasses
    from repro.core import fields
    config = _file_config(name)
    want = fields.make_field_config(config["app"], config["grid"]["kind"])
    got = harness.field_config(config)
    assert got == dataclasses.replace(want, name=got.name)


@pytest.mark.parametrize("name", ["nvr_hash", "gia_hash"])
def test_field_config_of_one_mlp_is_unchanged(name):
    """A file without a density MLP gives the FieldConfig it gave before
    fields of two MLPs were read: one MLP on the grid's L * F."""
    from repro.core.encoding import GridConfig
    from repro.core.fields import FieldConfig
    from repro.core.mlp import MLPConfig
    config = _file_config(name)
    g = GridConfig(**config["grid"])
    assert harness.field_config(config) == FieldConfig(
        app=config["app"], grid=g,
        mlp=MLPConfig(in_dim=g.out_dim, **config["mlp"]),
        name=config["name"])


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_check_devices_counts_chips():
    with pytest.raises(harness.NoAccelerator):
        harness.check_devices(1, require_tpu=True)
    with pytest.raises(harness.NoAccelerator):
        harness.check_devices(10 ** 6, require_tpu=False)
    assert len(harness.check_devices(1, require_tpu=False)) == 1


@pytest.mark.parametrize("driver", ["serve", "train"])
def test_a_missing_program_hook_is_named(driver, monkeypatch):
    """A traced run finds the compiled program through the engines'
    private parts; where a refactor takes one away, the run says which."""
    mod = importlib.import_module(f"bench.drivers.{driver}")
    if driver == "serve":
        with pytest.raises(RuntimeError, match="_buckets, _get_fn"):
            mod._hlo_texts(object(), 64, None)
    else:
        from repro.train import loop
        monkeypatch.delattr(loop.TrainEngine, mod.CHUNK_HOOK)
        with pytest.raises(RuntimeError, match=mod.CHUNK_HOOK):
            mod._chunk_builder(loop)


def test_window_stats_keep_the_longest_gap():
    import gc
    w = harness.WindowStats()
    w.open(10.0)
    for t in (10.5, 11.0, 13.0, 13.2):
        w.done(t)
    gc.collect()
    w.close()
    assert w.gap == (2.0, 3.0)
    assert w.gc[0] >= 1 and w.gc[1] >= 0
    assert w.usage["cpu_s"] >= 0
    assert "longest gap 2.000 s" in w.describe()
