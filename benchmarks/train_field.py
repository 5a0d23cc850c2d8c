"""Training-route benchmark: the field train step through the XLA path vs
the Pallas NFP kernel route (forward = fused encode+MLP kernel, backward =
the custom-VJP scatter-add table transpose).

The paper's apps are trained then served; with the kernels' custom VJPs
the SAME use_pallas flag now covers both. Also reports the touched-rows
fraction of the hash-table gradient — the sparsity that motivates the
compressed gradient all-reduce in train/compression.py — and the kernel's
VMEM plan (level-group size + resident table bytes) at each scale.

``run_scan_compare`` measures the training *engine* (train/loop.py):
steps/s of the seed per-step loop (one host dispatch + host-keyed batch
per step) vs the engine's jitted scanned chunks with on-device batch
synthesis, same RNG contract — so it also reports the loss parity
between the two routes (DESIGN.md §6 promises ≤1e-5 in f32).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Csv, small_field, time_fn
from repro.common.param import unbox
from repro.core import fields, train
from repro.data import scenes
from repro.kernels.common import pick_level_group, table_block_bytes
from repro.train import loop, optim


def run_scan_compare(csv: Csv, app: str = "gia", batch: int = 8192,
                     log2_T: int = 14, steps: int = 48,
                     chunk_steps: int = 16, n_levels: int = None,
                     mlp: tuple = None, n_samples: int = None,
                     gt_samples: int = 64, tag: str = ""):
    """Seed per-step loop vs scanned engine, XLA route, same RNG.

    Two regimes matter (and ``run`` reports both): with the default
    16-level grid the *step compute* dominates and the engine's win is
    just the removed per-step overhead; with a ray app whose eager
    ground-truth synthesis dominates the step (the host-side batch
    bottleneck the training engine exists to remove), folding synthesis
    into the compiled scan is the whole game."""
    import dataclasses as dc
    cfg = small_field(app, "hash", log2_T=log2_T)
    if n_levels is not None:
        cfg = cfg.with_grid(dc.replace(cfg.grid, n_levels=n_levels))
    if mlp is not None:
        cfg = dc.replace(cfg, mlp=dc.replace(
            cfg.mlp, hidden_dim=mlp[0], n_hidden=mlp[1]))
    k_init, k_data = train._data_keys(0)
    params0, _ = unbox(fields.init_field(k_init, cfg))
    opt_cfg = optim.AdamConfig(lr=1e-2)
    cam = (scenes.default_camera() if app in ("nerf", "nvr") else None)

    def synth(s):
        return train.make_batch(cfg, jax.random.fold_in(k_data, s), batch,
                                cam, gt_samples=gt_samples)

    # --- seed per-step loop: jitted step, eager host-dispatched batch
    step_fn = train.make_field_train_step(cfg, opt_cfg,
                                          n_samples=n_samples)

    def run_perstep(capture=None):
        params, opt = params0, optim.adam_init(params0)
        for i in range(steps):
            params, opt, m = step_fn(params, opt, synth(i))
            if capture is not None:
                capture.append(float(m["loss"]))
        jax.block_until_ready(m["loss"])  # repro: allow[host-sync] timing boundary
        return m

    run_perstep()                                    # compile
    t0 = time.perf_counter()
    run_perstep()
    t_ref = time.perf_counter() - t0

    # --- engine: one dispatch per chunk, batches synthesized in-scan
    sstep = loop.make_scanned_step(
        lambda p, b: train.field_loss(p, cfg, b, n_samples=n_samples),
        opt_cfg)
    engine = loop.TrainEngine(
        loop.EngineConfig(steps=steps, chunk_steps=chunk_steps),
        sstep, device_batch_fn=lambda s, state: synth(s))

    def fresh_state():
        # chunks donate their input buffers; give each run its own copy
        return loop.init_train_state(
            jax.tree.map(lambda x: x.copy(), params0))

    engine.run(fresh_state())                        # compile
    t0 = time.perf_counter()
    _, hist = engine.run(fresh_state())
    t_eng = time.perf_counter() - t0

    # --- loss parity across the full horizon (untimed re-runs)
    ref_losses = []
    run_perstep(capture=ref_losses)
    _, hist = engine.run(fresh_state())
    parity = max(abs(r["loss"] - l) for r, l in zip(hist, ref_losses))

    sps_ref, sps_eng = steps / t_ref, steps / t_eng
    csv.add(f"train/{app}{tag}/perstep_loop", t_ref / steps,
            f"steps_per_s={sps_ref:.1f}_batch={batch}")
    csv.add(f"train/{app}{tag}/scanned_engine", t_eng / steps,
            f"steps_per_s={sps_eng:.1f}_speedup={sps_eng / sps_ref:.2f}x"
            f"_loss_parity={parity:.2e}")
    return sps_eng / sps_ref, parity


def run(csv: Csv, batch: int = 8192, log2_T: int = 14):
    for app in ("gia", "nsdf"):
        cfg = small_field(app, "hash", log2_T=log2_T)
        params, _ = unbox(fields.init_field(jax.random.PRNGKey(0), cfg))
        opt_state = optim.adam_init(params)
        b = train.make_batch(cfg, jax.random.PRNGKey(1), batch)

        for use_pallas in (False, True):
            step = train.make_field_train_step(cfg, use_pallas=use_pallas)
            # interpret-mode Pallas is CPU-slow; shrink its batch so the
            # benchmark stays runnable — the structural claim is the VJP
            # route itself, not CPU wall time
            bb = (b if not use_pallas else
                  {k: v[:1024] for k, v in b.items()})
            t = time_fn(step, params, opt_state, bb)
            label = "pallas" if use_pallas else "xla"
            csv.add(f"train/{app}/{label}_step", t,
                    f"batch={len(next(iter(bb.values())))}")

        stats = train.sparse_table_stats(cfg, params, b)
        csv.add(f"train/{app}/grad_sparsity", 0.0,
                f"touched_rows_frac={stats['touched_rows_frac']:.4f}")
        g = pick_level_group(cfg.grid, jax.numpy.float32)
        csv.add(f"train/{app}/vmem_plan", 0.0,
                f"level_group={g}_table_block_bytes="
                f"{table_block_bytes(cfg.grid, g, jax.numpy.float32)}")

    # compute-bound regime: default grid, step compute dominates — the
    # engine's margin is only the removed per-step dispatch/synthesis
    run_scan_compare(csv, "gia", batch=batch, log2_T=log2_T)
    # synthesis-bound regime: ray supervision where the seed loop's
    # eager ground-truth compositing dominates — in-scan synthesis is
    # the acceptance row (>= 2x steps/s at batch 8192, XLA route)
    run_scan_compare(csv, "nvr", batch=batch, log2_T=10, n_levels=2,
                     mlp=(32, 2), n_samples=2, gt_samples=128,
                     tag="_raysynth")
