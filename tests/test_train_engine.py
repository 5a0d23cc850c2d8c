"""The shared training engine (train/loop.py, DESIGN.md §6).

Covers the engine's four contracts: (1) loss parity with the seed
per-step loop (``train_field_reference``) on every field app and both
kernel routes; (2) bitwise-identical kill-and-resume via grid-aligned
chunking; (3) compression's error-feedback invariant *through* the
engine state; (4) the lr schedule edges now wired into field training.
"""
import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import small_field_config
from repro.common.param import unbox
from repro.core import fields, train
from repro.train import compression, loop, optim

REPO = str(Path(__file__).resolve().parents[1])


# ------------------------------------------------------------ chunk plan
def test_chunk_plan_grid_aligned():
    # ends sit on the global grid regardless of start: a resumed run
    # replays the uninterrupted run's chunk sequence
    assert loop.chunk_plan(0, 40, 16) == [(0, 16), (16, 16), (32, 8)]
    assert loop.chunk_plan(16, 40, 16) == [(16, 16), (32, 8)]
    # mid-grid restart first realigns to the grid
    assert loop.chunk_plan(5, 40, 16) == [(5, 11), (16, 16), (32, 8)]
    assert loop.chunk_plan(39, 40, 16) == [(39, 1)]
    assert loop.chunk_plan(40, 40, 16) == []


# ------------------------------------------------- engine vs seed loop
def _loss_curve(history):
    return np.array([row["loss"] for row in history])


@pytest.mark.parametrize("app", ["gia", "nsdf", "nerf", "nvr"])
def test_engine_matches_reference_loss(app):
    cfg = small_field_config(app, "hash", log2_T=10, n_levels=2)
    kw = dict(steps=6, batch_size=128, seed=0, log_every=1)
    ray = dict(n_samples=4, gt_samples=8) if app in ("nerf", "nvr") else {}

    losses = []
    train.train_field(cfg, chunk_steps=4,
                      on_metrics=lambda i, row, st: losses.append(
                          row["loss"]),
                      **kw, **ray)
    _, ref_hist = train.train_field_reference(cfg, **kw, **ray)
    ref = np.array([l for _, l in ref_hist])
    assert len(losses) == len(ref) == 6
    np.testing.assert_allclose(np.array(losses), ref, rtol=0, atol=1e-5)


@pytest.mark.parametrize("app", ["gia", "nsdf", "nerf", "nvr"])
def test_engine_matches_reference_pallas(app):
    # interpret-mode Pallas is CPU-slow: tiny batch/steps, 2 samples/ray
    cfg = small_field_config(app, "hash", log2_T=10, n_levels=2)
    kw = dict(steps=3, batch_size=32, seed=0, log_every=1,
              use_pallas=True)
    ray = dict(n_samples=2, gt_samples=4) if app in ("nerf", "nvr") else {}
    losses = []
    train.train_field(cfg, chunk_steps=2,
                      on_metrics=lambda i, row, st: losses.append(
                          row["loss"]), **kw, **ray)
    _, ref_hist = train.train_field_reference(cfg, **kw, **ray)
    np.testing.assert_allclose(
        np.array(losses), np.array([l for _, l in ref_hist]),
        rtol=0, atol=1e-5)


def test_sorted_row_sum_trains_like_the_scatter_add(monkeypatch):
    """Three ``train_field`` steps on nvr/hash give the losses and the
    parameters of the same steps with the table gradient taken by
    ``jax.grad`` through plain ``jnp.take`` (XLA's scatter-add), to
    summation-order rounding: Adam divides each gradient by its own
    magnitude, so where a row's updates nearly cancel their order shows
    in that row's step, and the parameters are compared by the norm of
    their change over the steps."""
    from repro.core import encoding as enc
    # level 0 dense (17^3 of 2^14 rows), levels 1-3 hashed
    cfg = small_field_config("nvr", "hash", log2_T=14, n_levels=4)
    kw = dict(steps=3, batch_size=256, seed=0, log_every=1, chunk_steps=3,
              n_samples=8, gt_samples=16)

    def run():
        losses = []
        params, _ = train.train_field(
            cfg, on_metrics=lambda i, row, st: losses.append(row["loss"]),
            **kw)
        return np.array(losses), params

    losses, params = run()
    monkeypatch.setattr(
        enc, "gather_corners", lambda rows, shifts, table, idx: tuple(
            jnp.take(table, (i + s) % rows, axis=0)
            for i, ss in zip(idx, shifts) for s in ss))
    ref_losses, ref_params = run()
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-6)
    init, _ = unbox(fields.init_field(train._data_keys(0)[0], cfg))
    for a, b, p0 in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params),
                        jax.tree.leaves(init)):
        change = np.linalg.norm(np.asarray(b) - np.asarray(p0))
        assert change > 0
        assert np.linalg.norm(np.asarray(a) - np.asarray(b)) <= 1e-5 * change


def test_chunk_program_is_the_same_for_every_seed():
    """The data key is a leaf of the engine state, not a constant of the
    chunk: two seeds compile one program, which JAX's persistent compile
    cache then finds again."""
    cfg = small_field_config("nvr", "hash", log2_T=8, n_levels=2)
    texts = []
    for seed in (1, 2):
        engines = []
        train.train_field(cfg, steps=1, batch_size=32, seed=seed,
                          chunk_steps=1, n_samples=4, gt_samples=8,
                          on_engine=engines.append)
        texts.append(engines[0].compiled_chunk(1).as_text())
    assert texts[0] == texts[1]


def test_engine_metrics_include_psnr_and_lr():
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    rows = []
    train.train_field(cfg, steps=2, batch_size=64, chunk_steps=2,
                      on_metrics=lambda i, row, st: rows.append(row))
    for row in rows:
        assert {"loss", "psnr", "lr", "step", "dt"} <= set(row)
        assert row["psnr"] == pytest.approx(
            -10.0 * np.log10(max(row["loss"], 1e-12)), rel=1e-5)


# ------------------------------------------------------- kill & resume
def test_kill_and_resume_bitwise(tmp_path):
    """Interrupted-at-k + resumed run == uninterrupted run, bitwise."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    kw = dict(steps=16, batch_size=128, seed=0, chunk_steps=4,
              ckpt_every=8)

    full_losses = []
    p_full, _ = train.train_field(
        cfg, on_metrics=lambda i, row, st: full_losses.append(
            (i, row["loss"])), **kw)

    # "killed" run: same config but stopped at step 8 (half the run)
    ckpt = str(tmp_path / "ckpt")
    part_losses = []
    train.train_field(cfg, **{**kw, "steps": 8}, ckpt_dir=ckpt,
                      on_metrics=lambda i, row, st: part_losses.append(
                          (i, row["loss"])))
    # resume: identical invocation with the full horizon
    p_res, _ = train.train_field(
        cfg, **kw, ckpt_dir=ckpt,
        on_metrics=lambda i, row, st: part_losses.append(
            (i, row["loss"])))

    # the resumed run continued at step 8 (elastic contract: the step
    # counter continues across restarts) and the stitched trajectory is
    # bitwise identical to the uninterrupted one
    assert [i for i, _ in part_losses] == list(range(16))
    assert part_losses == full_losses          # float equality: bitwise
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ schedules
def test_lr_schedule_edges():
    base = 1e-2
    # warmup: first optimizer step (step=1) is scaled, ramp hits 1 at
    # the warmup horizon
    cfg = optim.AdamConfig(lr=base, lr_warmup_steps=10)
    assert float(optim.lr_schedule(cfg, 0)) == pytest.approx(0.1 * base)
    assert float(optim.lr_schedule(cfg, 4)) == pytest.approx(0.5 * base)
    assert float(optim.lr_schedule(cfg, 9)) == pytest.approx(base)
    assert float(optim.lr_schedule(cfg, 100)) == pytest.approx(base)
    # cosine decay reaches 0 at the horizon and clamps beyond it
    cfg = optim.AdamConfig(lr=base, lr_decay_steps=100)
    assert float(optim.lr_schedule(cfg, 0)) == pytest.approx(base)
    assert float(optim.lr_schedule(cfg, 50)) == pytest.approx(0.5 * base)
    assert float(optim.lr_schedule(cfg, 100)) == pytest.approx(0.0, abs=1e-12)
    assert float(optim.lr_schedule(cfg, 10**6)) == pytest.approx(0.0, abs=1e-12)
    # both 0: constant
    cfg = optim.AdamConfig(lr=base)
    for s in (0, 1, 10**6):
        assert float(optim.lr_schedule(cfg, s)) == pytest.approx(base)


def test_warmup_wired_into_field_training():
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    lrs = []
    train.train_field(cfg, steps=4, batch_size=64, chunk_steps=4,
                      opt_cfg=optim.AdamConfig(lr=1e-2, lr_warmup_steps=4),
                      on_metrics=lambda i, row, st: lrs.append(row["lr"]))
    np.testing.assert_allclose(
        lrs, [1e-2 * f for f in (0.5, 0.75, 1.0, 1.0)], rtol=1e-5)


# ---------------------------------------------------------- compression
def test_engine_efb_invariant():
    """state['efb'] carries exactly the mass top-k dropped: after one
    engine step, kept + efb_new == grad + efb_old (efb_old = 0)."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    k_init, k_data = train._data_keys(0)
    params, _ = unbox(fields.init_field(k_init, cfg))
    batch = train.make_batch(cfg, jax.random.fold_in(k_data, 0), 128)
    opt_cfg = optim.AdamConfig(lr=1e-2)
    frac = 0.05

    step_fn = loop.make_scanned_step(
        lambda p, b: train.field_loss(p, cfg, b), opt_cfg,
        compression="topk", compression_topk=frac)
    state = loop.init_train_state(params, compression="topk")
    state1, _ = step_fn(state, jnp.int32(0), batch)

    g = jax.grad(train.field_loss)(params, cfg, batch)["grid"]
    kept, efb = compression.compress_topk(g, jnp.zeros_like(g), frac)
    np.testing.assert_allclose(state1["efb"]["grid"], efb, atol=1e-7)
    np.testing.assert_allclose(kept + efb, g, atol=1e-7)


def test_topk_compression_converges():
    """Top-k on the naturally-sparse table gradient is near-lossless:
    within a few percent of the uncompressed loss at 200 steps."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    kw = dict(steps=200, batch_size=256, seed=0, log_every=200)

    def final_loss(**extra):
        losses = []
        train.train_field(cfg, on_metrics=lambda i, row, st:
                          losses.append(row["loss"]), **kw, **extra)
        return float(np.mean(losses[-10:]))     # averaged: step noise

    plain = final_loss()
    topk = final_loss(compression="topk", compression_topk=0.05)
    assert abs(topk - plain) / plain < 0.01


# ------------------------------------------------------------ grad accum
def test_grad_accum_matches_single_pass():
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=2)
    k_init, k_data = train._data_keys(0)
    params, _ = unbox(fields.init_field(k_init, cfg))
    batch = train.make_batch(cfg, jax.random.fold_in(k_data, 0), 128)
    opt_cfg = optim.AdamConfig(lr=1e-2)
    loss_fn = lambda p, b: train.field_loss(p, cfg, b)

    s1, m1 = loop.make_scanned_step(loss_fn, opt_cfg)(
        loop.init_train_state(params), jnp.int32(0), batch)
    s2, m2 = loop.make_scanned_step(loss_fn, opt_cfg, grad_accum=2)(
        loop.init_train_state(params), jnp.int32(0), batch)
    # MSE over the full batch == mean of the two half-batch MSEs, so the
    # accumulated grads/loss match the single pass to float tolerance
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(a, b, atol=1e-6)


# ------------------------------------------------- data-parallel shard_map
@pytest.mark.slow
def test_data_parallel_grads_match_single_device():
    out = subprocess.run(
        [sys.executable, "-c",
         "import os\n"
         "os.environ['XLA_FLAGS'] = "
         "'--xla_force_host_platform_device_count=8'\n"
         "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            sys.path.insert(0, 'tests')
            from conftest import small_field_config
            from repro.common.param import unbox
            from repro.common import partitioning
            from repro.core import fields, train
            from repro.launch.mesh import make_mesh
            from repro.train import loop

            cfg = small_field_config('gia', 'hash', log2_T=10, n_levels=2)
            k_init, k_data = train._data_keys(0)
            params, _ = unbox(fields.init_field(k_init, cfg))
            batch = train.make_batch(
                cfg, jax.random.fold_in(k_data, 0), 256)
            loss_fn = lambda p, b: train.field_loss(p, cfg, b)

            mesh = make_mesh((8,), ('data',))
            sharded = loop.data_parallel_grad_fn(
                loss_fn, mesh, partitioning.DEFAULT_RULES)
            l1, g1 = jax.value_and_grad(loss_fn)(params, batch)
            l2, g2 = sharded(params, batch)
            np.testing.assert_allclose(float(l1), float(l2), atol=1e-6)
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
                np.testing.assert_allclose(a, b, atol=1e-5)
            print('OK')
        """)],
        capture_output=True, text=True, cwd=REPO, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "OK" in out.stdout


# -------------------------------------------------------- compiled program
def test_compiled_chunk_is_the_chunk_program_the_engine_runs(monkeypatch):
    """The public route to a chunk's compiled program, on the engine that
    ``train_field`` hands out, gives the HLO of the route the benchmark's
    train driver takes (``bench/drivers/train.py``: the chunk the engine
    built, lowered for the state's shapes at the first step)."""
    monkeypatch.syspath_prepend(REPO)
    from bench.drivers import train as train_driver

    cfg = small_field_config("nvr", "hash", log2_T=8, n_levels=2)
    engines, chunks, shapes = [], [], {}

    def on_engine(engine):
        with pytest.raises(ValueError):
            engine.compiled_chunk(1)
        engines.append(engine)

    def on_metrics(i, row, st):
        if i == 0:
            shapes["state"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)

    build = train_driver._chunk_builder(loop)

    def keep_chunk(self, n):
        fn = build(self, n)
        chunks.append(fn)
        return fn

    setattr(loop.TrainEngine, train_driver.CHUNK_HOOK, keep_chunk)
    try:
        train.train_field(cfg, steps=2, batch_size=32, chunk_steps=1,
                          on_metrics=on_metrics, on_engine=on_engine,
                          n_samples=4, gt_samples=4)
    finally:
        setattr(loop.TrainEngine, train_driver.CHUNK_HOOK, build)
    driver = chunks[0].lower(shapes["state"], jnp.int32(0)).compile()
    assert engines[0].compiled_chunk(1).as_text() == driver.as_text()
