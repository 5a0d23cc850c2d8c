"""Training for neural fields (the paper's apps are trained, then served).

Loss is MSE against the analytic ground-truth scene (data/scenes.py).
The hashgrid table gradient is *sparse* (only touched rows receive
gradient); ``sparse_table_stats`` measures the touched fraction — the
quantity that motivates the sparse/compressed gradient all-reduce in
train/compression.py for multi-host field training.

``train_field`` is a thin adapter over the shared training engine
(``train/loop.py``, DESIGN.md §6): batches are synthesized *on device*
inside the scanned chunk (batch key = ``fold_in(data_key, step)``, the
data key a leaf of the engine state, so the compiled chunk is the same
for every seed and JAX's compile cache finds it), the
``(params, opt)`` buffers are donated per chunk, and checkpointing,
gradient compression, and data-parallel sharding ride the same engine
the LM launcher uses. ``train_field_reference`` keeps the seed per-step
loop as the parity oracle (tests + benchmarks assert the engine
reproduces its loss history).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.param import unbox
from repro.core import fields, render
from repro.core.fields import FieldConfig
from repro.data import scenes
from repro.train import loop, optim


def field_loss(params, cfg: FieldConfig, batch: Dict, fused: bool = True,
               use_pallas: bool = False,
               n_samples: Optional[int] = None) -> jnp.ndarray:
    """use_pallas routes encode+MLP through the NFP Pallas kernels — fully
    differentiable via their custom VJPs (scatter-add table transpose), so
    the same flag serves both render AND train benchmarks. ``n_samples``
    overrides the ray apps' per-step compositing depth (default 32)."""
    if cfg.app in ("gia", "nsdf"):
        pred = fields.apply_field(params, cfg, batch["points"], fused=fused,
                                  use_pallas=use_pallas)
        return jnp.mean((pred - batch["target"]) ** 2)
    # nerf / nvr: render rays and compare pixels
    def fapply(p, d):
        return fields.apply_field(params, cfg, p, d, fused=fused,
                                  use_pallas=use_pallas)
    pred = render.render_rays(fapply, batch["origins"], batch["dirs"],
                              n_samples=n_samples or 32, rng=None)
    return jnp.mean((pred - batch["target"]) ** 2)


def make_field_train_step(cfg: FieldConfig, opt_cfg: Optional[optim.AdamConfig]
                          = None, fused: bool = True,
                          use_pallas: bool = False,
                          n_samples: Optional[int] = None) -> Callable:
    opt_cfg = opt_cfg or optim.AdamConfig(lr=1e-2)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(field_loss)(
            params, cfg, batch, fused=fused, use_pallas=use_pallas,
            n_samples=n_samples)
        params, opt_state, metrics = optim.adam_update(
            grads, opt_state, params, opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return step


def make_batch(cfg: FieldConfig, rng, batch_size: int,
               cam: Optional[render.Camera] = None,
               gt_samples: int = 64) -> Dict:
    """Synthesize one training batch; fully jittable (traced rng ok), so
    the engine can fold it into the scanned chunk. For the ray apps pass
    a concrete ``cam`` built *outside* any trace (Camera construction
    stages its intrinsics under jit)."""
    if cfg.app == "gia":
        xy, target = scenes.gia_batch(rng, batch_size)
        return {"points": xy, "target": target}
    if cfg.app == "nsdf":
        p, target = scenes.nsdf_batch(rng, batch_size)
        return {"points": p, "target": target}
    cam = cam or scenes.default_camera()
    origins, dirs, target = scenes.nerf_ray_batch(rng, cam, batch_size,
                                                  gt_samples=gt_samples)
    return {"origins": origins, "dirs": dirs, "target": target}


def _data_keys(seed: int):
    """The engine RNG contract (DESIGN.md §6): one init key, one data
    key; the step-``i`` batch key is ``fold_in(data_key, i)`` — a pure
    function of the global step, identical across restarts and across
    the scanned/per-step routes."""
    k_init, k_data = jax.random.split(jax.random.PRNGKey(seed))
    return k_init, k_data


def train_field(cfg: FieldConfig, steps: int = 200, batch_size: int = 2048,
                seed: int = 0, fused: bool = True, use_pallas: bool = False,
                log_every: int = 50,
                opt_cfg: Optional[optim.AdamConfig] = None,
                callback: Optional[Callable] = None, *,
                chunk_steps: int = 16, grad_accum: int = 1,
                ckpt_dir=None, ckpt_every: int = 50,
                compression: Optional[str] = None,
                compression_topk: float = 0.05,
                mesh=None, rules=None,
                on_metrics: Optional[Callable] = None,
                on_engine: Optional[Callable] = None,
                n_samples: Optional[int] = None, gt_samples: int = 64,
                occupancy_res: Optional[int] = None,
                occupancy_every: int = 1,
                occupancy_threshold: float = 0.01,
                occupancy_decay: float = 0.95):
    """End-to-end field training against the analytic scene, on the
    shared engine.

    Seed-compatible surface: returns ``(params, history)`` with history
    entries ``(step, loss)`` at ``log_every`` boundaries and the final
    step; ``callback(step, loss, params)`` fires at the same points
    (params are the enclosing chunk-end params). New engine knobs:
    checkpoint/resume (``ckpt_dir``), gradient accumulation, top-k/int8
    compression of the hash-table gradient, and data-parallel
    ``shard_map`` over the ``field_batch`` mesh axes. ``on_metrics``
    receives every step's full metrics row (loss, psnr, lr, dt);
    ``on_engine`` receives the ``TrainEngine`` before it runs (for its
    ``compiled_chunk``).

    Passing ``occupancy_res`` (nerf/nvr only) maintains an occupancy
    grid (DESIGN.md §7) off the engine's ``on_chunk_end`` hook: built
    fresh at the first chunk end, EMA-refreshed every
    ``occupancy_every`` chunk ends after that, and attached to the
    returned params as the ``'occupancy'`` leaf — ready for
    ``RenderSettings(occupancy=True)`` serving. The grid lives outside
    the scanned/donated training state (no optimizer moments for it).
    """
    from repro.core import occupancy as occ_mod

    if occupancy_res is not None and cfg.app not in ("nerf", "nvr"):
        raise ValueError("occupancy_res is only meaningful for the ray "
                         f"apps (nerf/nvr), not app={cfg.app!r}")
    k_init, k_data = _data_keys(seed)
    params, _spec = unbox(fields.init_field(k_init, cfg))
    state = loop.init_train_state(params, compression=compression)
    state["data_key"] = k_data
    opt_cfg = opt_cfg or optim.AdamConfig(lr=1e-2)
    cam = scenes.default_camera() if cfg.app in ("nerf", "nvr") else None

    occ_box = {"occ": None, "chunks": 0}

    def _refresh_occupancy(end, st):
        occ_box["chunks"] += 1
        if occ_box["occ"] is None:
            occ_box["occ"] = occ_mod.build_occupancy(
                st["params"], cfg, res=occupancy_res,
                threshold=occupancy_threshold, fused=fused,
                use_pallas=use_pallas)
        elif occ_box["chunks"] % occupancy_every == 0:
            occ_box["occ"] = occ_mod.update_occupancy(
                occ_box["occ"], st["params"], cfg,
                decay=occupancy_decay, threshold=occupancy_threshold,
                fused=fused, use_pallas=use_pallas)

    step_fn = loop.make_scanned_step(
        lambda p, b: field_loss(p, cfg, b, fused=fused,
                                use_pallas=use_pallas,
                                n_samples=n_samples),
        opt_cfg, grad_accum=grad_accum, compression=compression,
        compression_topk=compression_topk, mesh=mesh, rules=rules)
    engine = loop.TrainEngine(
        loop.EngineConfig(steps=steps, chunk_steps=chunk_steps,
                          ckpt_dir=ckpt_dir, ckpt_every=ckpt_every),
        step_fn,
        device_batch_fn=lambda step, st: make_batch(
            cfg, jax.random.fold_in(st["data_key"], step), batch_size, cam,
            gt_samples=gt_samples),
        on_chunk_end=(_refresh_occupancy if occupancy_res is not None
                      else None))

    history = []

    def _on_metrics(i, row, st):
        if i % log_every == 0 or i == steps - 1:
            history.append((i, row["loss"]))
            if callback:
                callback(i, row["loss"], st["params"])
        if on_metrics:
            on_metrics(i, row, st)

    if on_engine:
        on_engine(engine)
    state, _ = engine.run(state, on_metrics=_on_metrics)
    out_params = state["params"]
    if occ_box["occ"] is not None:
        out_params = occ_mod.attach(out_params, occ_box["occ"])
    return out_params, history


def train_field_reference(cfg: FieldConfig, steps: int = 200,
                          batch_size: int = 2048, seed: int = 0,
                          fused: bool = True, use_pallas: bool = False,
                          log_every: int = 50,
                          opt_cfg: Optional[optim.AdamConfig] = None,
                          n_samples: Optional[int] = None,
                          gt_samples: int = 64):
    """The seed per-step Python loop, kept as the engine's parity oracle
    (and the benchmark baseline): one host dispatch per step, host-side
    batch key, no checkpointing. Same RNG contract as the engine, so the
    loss histories must agree (tests/test_train_engine.py, f32 1e-5)."""
    k_init, k_data = _data_keys(seed)
    params, _spec = unbox(fields.init_field(k_init, cfg))
    opt_state = optim.adam_init(params)
    step_fn = make_field_train_step(cfg, opt_cfg, fused=fused,
                                    use_pallas=use_pallas,
                                    n_samples=n_samples)
    cam = scenes.default_camera() if cfg.app in ("nerf", "nvr") else None
    history = []
    for i in range(steps):
        batch = make_batch(cfg, jax.random.fold_in(k_data, i),
                           batch_size, cam, gt_samples=gt_samples)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % log_every == 0 or i == steps - 1:
            history.append((i, float(metrics["loss"])))
    return params, history


def sparse_table_stats(cfg: FieldConfig, params, batch,
                       use_pallas: bool = False) -> Dict[str, float]:
    """Fraction of hash-table rows touched by one batch's gradient."""
    grads = jax.grad(field_loss)(params, cfg, batch, use_pallas=use_pallas)
    g = grads["grid"]                       # (L, T, F)
    touched = jnp.any(g != 0.0, axis=-1)    # (L, T)
    return {
        "touched_rows_frac": float(jnp.mean(touched)),
        "table_rows": int(g.shape[0] * g.shape[1]),
    }


def psnr(mse: float) -> float:
    """Host-side PSNR of an MSE (rendering comparisons). The training
    engine reports PSNR per step in its metrics dict; this helper is for
    losses/MSEs computed outside the engine."""
    return -10.0 * math.log10(max(mse, 1e-12))
