"""The entry points' contracts that need no chip: where the compile
cache goes, that the chip smoke refuses a CPU and runs its phases at a
small size, and that the benchmark harness exits non-zero on a failure."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]
SMALL = dict(log2_table_size=10, n_levels=4, tile_pixels=256, n_requests=4,
             frame=32, app_frame=16, train_batch=256, train_steps=4,
             chunk_steps=2, kernel_rows=1024)


# ----------------------------------------------------------- compile cache
@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_honours_env(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_enable_compile_cache_sets_only_the_fixed_dir(monkeypatch,
                                                      cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


# -------------------------------------------------------------- chip smoke
def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def _phase_rows(stdout: str):
    return [json.loads(line) for line in stdout.splitlines()
            if line.startswith("{")]


def test_chip_smoke_one_chip_phases_small(capsys):
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    sizes = chip_smoke.Sizes(**SMALL)
    chip_smoke.run_one_chip(sizes, chip_smoke.CompileClock(),
                            require_compiled=False)
    rows = _phase_rows(capsys.readouterr().out)
    assert [r["phase"] for r in rows] == [
        "kernels", "train", "serve", "parity", "apps", "apps", "apps"]
    assert [r["app"] for r in rows[4:]] == ["nerf", "nsdf", "gia"]
    assert rows[1]["loss_last"] < rows[1]["loss_first"]
    assert rows[3]["max_abs_diff"] <= chip_smoke.PARITY_ATOL


def test_chip_smoke_four_device_phases_small():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import chip_smoke
        sizes = chip_smoke.Sizes(**{SMALL!r})
        chip_smoke.run_four_chips(sizes, chip_smoke.CompileClock())
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    rows = {row["phase"]: row for row in _phase_rows(r.stdout)}
    assert rows["serve_sharded"]["output_devices"] == [4]
    assert rows["serve_1dev"]["output_devices"] == [1]
    assert rows["shard_parity"]["max_abs_diff"] <= 1e-5
    assert rows["dp_parity"]["max_rel_diff"] <= 1e-4


# ---------------------------------------------------------- bench harness
def test_bench_run_exits_nonzero_when_a_module_raises(tmp_path, monkeypatch,
                                                      capsys):
    (tmp_path / "bench_ok.py").write_text(
        "def run(csv):\n    csv.add('ok/row', 1.0, '')\n")
    (tmp_path / "bench_boom.py").write_text(
        "def run(csv):\n    raise RuntimeError('boom')\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    from benchmarks import run
    monkeypatch.setattr(run, "MODULES", [("ok", "bench_ok"),
                                         ("boom", "bench_boom")])
    assert run.main(["--only", "ok"]) == 0
    assert run.main([]) == 1
    out = capsys.readouterr().out
    assert "ok/row" in out and "boom/ERROR" in out
