"""Smoke run of the main path on a TPU, at Table I width.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # the multi-chip paths of a four-chip host

One chip, in one process:

  kernels   the Pallas kernels that compile for the TPU
            (``composite_pallas``, ``fused_mlp_pallas``) run once, compiled
            (never interpreted), against their XLA references;
  train     nvr/hash trains a few steps through ``core.train.train_field``
            (the TrainEngine);
  serve     the trained scene is served through the ``RenderEngine``:
            warmup, then tile requests, with no compile in the served window;
  parity    every served tile matches ``pipeline.render_frame`` of the same
            scene and camera;
  apps      nerf/hash, nsdf/hash and gia/hash (a 2 GiB f32 table) each serve
            one tile from freshly initialised params, checked the same way.

Four chips: data-parallel ``train_field(mesh=...)`` steps against the
one-device losses, then the trained scene served pixel-parallel over the
4-device mesh against the same requests on one device.

Each phase prints one JSON line. Times and rates in them are smoke
readings, not benchmark results. The last line is
``{"ok": true, "device": {...}}``. Without a TPU, or outside a checkout of
the repository, the script exits non-zero and prints no such line; any
failed check raises.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
APP = "nvr"
OTHER_APPS = ("nerf", "nsdf", "gia")
# engine vs render_frame run the same f32 tile math in two programs
PARITY_ATOL = 1e-5
# the kernels' matmuls may take bf16 passes on the MXU, as the XLA
# route's default-precision matmuls do; relative to the output's scale
KERNEL_RTOL = 1e-2
# data-parallel losses differ from one device only in reduction order
DP_LOSS_RTOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the smoke cuts. The defaults are the chip run: Table I
    widths, a 4096-pixel tile, a few steps of a 4096-ray batch."""
    log2_table_size: int | None = None     # None: the Table I value
    n_levels: int | None = None            # None: the Table I value
    tile_pixels: int = 4096
    n_requests: int = 8
    frame: int = 128                       # served frames are frame^2 px
    app_frame: int = 64                    # one tile per other-app frame
    train_batch: int = 4096
    train_steps: int = 16
    chunk_steps: int = 8
    kernel_rows: int = 65536               # fused-MLP rows

    def field_config(self, app: str):
        from repro.configs import registry
        cfg = registry.field_config(app, "hash")
        grid = cfg.grid
        if self.log2_table_size is not None:
            grid = dataclasses.replace(grid,
                                       log2_table_size=self.log2_table_size)
        if self.n_levels is not None:
            grid = dataclasses.replace(grid, n_levels=self.n_levels)
        return cfg.with_grid(grid) if grid != cfg.grid else cfg


class CompileClock:
    """Counts the executables JAX builds (compiled or loaded from the
    persistent cache) and the seconds spent tracing, lowering and
    compiling, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.n = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.s += secs
            if event.endswith("backend_compile_duration"):
                self.n += 1

    def mark(self):
        return self.n, self.s

    def since(self, mark):
        return self.n - mark[0], self.s - mark[1]


def device_label() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def emit(phase: str, clock: CompileClock, mark, **fields):
    n, s = clock.since(mark)
    row = {"phase": phase, "device": device_label(), "compiles": n,
           "compile_s": round(s, 3), **fields,
           "peak_hbm_bytes": peak_bytes()}
    print(json.dumps(row), flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise AssertionError(msg)


def max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def kernel_is_compiled(fn, *args, **kwargs) -> bool:
    """The compiled program holds a Mosaic kernel (not the interpreter)."""
    return "tpu_custom_call" in fn.lower(*args, **kwargs).compile().as_text()


# ------------------------------------------------------------------ phases
def phase_kernels(sizes: Sizes, clock: CompileClock,
                  require_compiled: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.param import unbox
    from repro.core import render
    from repro.core.mlp import apply_mlp, init_mlp
    from repro.kernels.common import default_interpret
    from repro.kernels.fused_mlp import ops as mlp_ops
    from repro.kernels.ray_march import ops as rm_ops

    mark = clock.mark()
    interpret = default_interpret()
    if require_compiled:
        check(interpret is False, "Pallas would run interpreted")
    k = jax.random.split(jax.random.PRNGKey(SEED), 4)
    r, s = sizes.tile_pixels, 32
    rgb = jax.random.uniform(k[0], (r, s, 3))
    sigma = jax.random.uniform(k[1], (r, s)) * 8
    dts = jnp.full((r, s), 0.07)
    if require_compiled:
        check(kernel_is_compiled(rm_ops.composite, rgb, sigma, dts),
              "composite: no tpu_custom_call in the compiled program")
    pix, opac = rm_ops.composite(rgb, sigma, dts)
    ref_pix, ref_opac = jax.jit(render.composite)(rgb, sigma, dts)
    d_comp = max(max_abs(pix, ref_pix), max_abs(opac, ref_opac))
    check(d_comp <= PARITY_ATOL,
          f"composite_pallas vs render.composite: {d_comp}")

    mcfg = sizes.field_config(APP).mlp
    params, _ = unbox(init_mlp(k[2], mcfg))
    x = jax.random.uniform(k[3], (sizes.kernel_rows, mcfg.in_dim))
    if require_compiled:
        check(kernel_is_compiled(mlp_ops.mlp, params, x, cfg=mcfg),
              "fused_mlp: no tpu_custom_call in the compiled program")
    out = mlp_ops.mlp(params, x, cfg=mcfg)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(apply_mlp, static_argnums=2)(params, x, mcfg)
    scale = float(np.max(np.abs(np.asarray(ref))))
    d_mlp = max_abs(out, ref)
    check(np.isfinite(d_mlp) and d_mlp <= KERNEL_RTOL * scale,
          f"fused_mlp_pallas vs apply_mlp: {d_mlp} (scale {scale})")
    emit("kernels", clock, mark, interpret=interpret,
         composite_max_abs_diff=d_comp, mlp_max_abs_diff=d_mlp,
         mlp_out_scale=scale)


def train(sizes: Sizes, clock: CompileClock, mesh=None, phase="train"):
    """nvr/hash through ``train_field``; returns (params, losses)."""
    import numpy as np
    from repro.core.train import train_field

    mark = clock.mark()
    cfg = sizes.field_config(APP)
    rows = []
    params, _ = train_field(
        cfg, steps=sizes.train_steps, batch_size=sizes.train_batch,
        seed=SEED, chunk_steps=sizes.chunk_steps, mesh=mesh,
        on_metrics=lambda i, row, st: rows.append(row))
    losses = [r["loss"] for r in rows]
    check(len(losses) == sizes.train_steps, f"{len(losses)} loss rows")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    steady = [r["dt"] for r in rows[sizes.chunk_steps:]] or [rows[-1]["dt"]]
    emit(phase, clock, mark, config=cfg.name,
         log2_table_size=cfg.grid.log2_table_size, steps=len(losses),
         batch_rays=sizes.train_batch, loss_first=losses[0],
         loss_last=losses[-1],
         smoke_step_ms=float(np.median(steady)) * 1e3,
         mesh=None if mesh is None else dict(mesh.shape))
    return params, losses


def serve_requests(engine, sizes: Sizes, clock: CompileClock, scene: str,
                   cams):
    """Warm up, then serve ``n_requests`` random tiles. Returns the
    requests, their outputs, the tickets, and the served window's
    compile count."""
    import numpy as np
    from repro.serve import RenderRequest

    engine.warmup()
    rng = np.random.default_rng(SEED)
    mark = clock.mark()
    reqs, tickets = [], []
    for i in range(sizes.n_requests):
        cam = cams[i % len(cams)]
        ids = rng.integers(0, sizes.frame * sizes.frame,
                           sizes.tile_pixels).astype(np.int32)
        reqs.append((cam, ids))
        tickets.append(engine.submit(RenderRequest(scene, cam, ids)))
    engine.flush()
    outs = [t.result() for t in tickets]
    window_compiles, _ = clock.since(mark)
    stats = engine.stats()
    check(stats["n_traces_total"] == len(stats["buckets"]),
          f"{stats['n_traces_total']} traces for "
          f"{len(stats['buckets'])} buckets")
    check(window_compiles == 0,
          f"{window_compiles} compiles inside the served window")
    for o in outs:
        check(o.shape == (sizes.tile_pixels, 3), f"tile shape {o.shape}")
        check(bool(np.all(np.isfinite(o))), "non-finite pixels")
    return reqs, outs, tickets, stats


def serve_and_check(params, sizes: Sizes, clock: CompileClock):
    """The trained nvr scene through the RenderEngine, then parity."""
    import numpy as np
    from repro.core import pipeline
    from repro.data import scenes
    from repro.serve import RenderEngine

    cfg = sizes.field_config(APP)
    settings = pipeline.RenderSettings(tile_pixels=sizes.tile_pixels)
    mark = clock.mark()
    engine = RenderEngine(settings)
    engine.add_scene("scene0", cfg, params)
    cams = [scenes.orbit_camera(sizes.frame, sizes.frame, a)
            for a in (0.0, 2.0)]
    reqs, outs, _, stats = serve_requests(engine, sizes, clock, "scene0",
                                          cams)
    emit("serve", clock, mark, config=cfg.name,
         log2_table_size=cfg.grid.log2_table_size,
         n_requests=stats["n_requests"], tile_pixels=sizes.tile_pixels,
         served_window_compiles=0, warmup_s=stats["warmup_s"],
         smoke_p50_ms=stats["p50_ms"],
         smoke_mpix_per_s=stats["mpix_per_s"])

    mark = clock.mark()
    refs = {id(c): np.asarray(pipeline.render_frame(params, cfg, c, settings)
                              ).reshape(-1, 3) for c in cams}
    diff = max(max_abs(o, refs[id(cam)][ids])
               for (cam, ids), o in zip(reqs, outs))
    check(diff <= PARITY_ATOL, f"engine vs render_frame: {diff}")
    emit("parity", clock, mark, n_requests=len(outs), max_abs_diff=diff,
         atol=PARITY_ATOL)


def phase_other_apps(sizes: Sizes, clock: CompileClock):
    import jax
    import numpy as np
    from repro.common.param import unbox
    from repro.core import fields, pipeline
    from repro.data import scenes
    from repro.serve import RenderEngine, RenderRequest

    settings = pipeline.RenderSettings(tile_pixels=sizes.tile_pixels)
    cam = scenes.orbit_camera(sizes.app_frame, sizes.app_frame, 1.0)
    ids = np.arange(min(sizes.tile_pixels, sizes.app_frame ** 2),
                    dtype=np.int32)
    for i, app in enumerate(OTHER_APPS):
        mark = clock.mark()
        cfg = sizes.field_config(app)
        params, _ = unbox(fields.init_field(
            jax.random.PRNGKey(SEED + 1 + i), cfg))
        engine = RenderEngine(settings)
        engine.add_scene(app, cfg, params)
        engine.warmup()
        out = engine.submit(RenderRequest(app, cam, ids)).result()
        stats = engine.stats()
        check(stats["n_traces_total"] == len(stats["buckets"]),
              f"{app}: {stats['n_traces_total']} traces")
        check(out.shape == (ids.size, 3)
              and bool(np.all(np.isfinite(out))), f"{app}: bad tile")
        ref = pipeline.render_frame(params, cfg, cam, settings)
        diff = max_abs(out, np.asarray(ref).reshape(-1, 3)[ids])
        check(diff <= PARITY_ATOL, f"{app} engine vs render_frame: {diff}")
        emit("apps", clock, mark, app=app, config=cfg.name,
             log2_table_size=cfg.grid.log2_table_size,
             table_bytes=int(params["grid"].nbytes), n_requests=1,
             max_abs_diff=diff, smoke_p50_ms=stats["p50_ms"])
        del engine, params


# ------------------------------------------------------------- four chips
def run_four_chips(sizes: Sizes, clock: CompileClock, n_devices: int = 4):
    import jax
    import numpy as np
    from repro.core import pipeline
    from repro.data import scenes
    from repro.launch.mesh import make_local_mesh
    from repro.serve import RenderEngine

    check(len(jax.devices()) >= n_devices,
          f"{len(jax.devices())} devices, need {n_devices}")
    mesh = make_local_mesh(n_devices)
    check(mesh.devices.size == n_devices, f"mesh {dict(mesh.shape)}")

    _, one = train(sizes, clock, phase="train_1dev")
    params, dp = train(sizes, clock, mesh=mesh, phase="train_dp")
    diff = float(np.max(np.abs(np.asarray(dp) - np.asarray(one))
                        / np.abs(np.asarray(one))))
    check(diff <= DP_LOSS_RTOL, f"data-parallel losses: rel diff {diff}")
    mark = clock.mark()
    emit("dp_parity", clock, mark, steps=len(dp), max_rel_diff=diff,
         rtol=DP_LOSS_RTOL)

    cfg = sizes.field_config(APP)
    settings = pipeline.RenderSettings(tile_pixels=sizes.tile_pixels)
    cams = [scenes.orbit_camera(sizes.frame, sizes.frame, a)
            for a in (0.0, 2.0)]
    results = {}
    # the data-parallel params are replicated over the mesh: the one-device
    # engine gets a copy on device 0, so that it computes there alone
    for name, m, p in (
            ("serve_1dev", None, jax.device_put(params, jax.devices()[0])),
            ("serve_sharded", mesh, params)):
        mark = clock.mark()
        engine = RenderEngine(settings, mesh=m)
        engine.add_scene("scene0", cfg, p)
        reqs, outs, tickets, stats = serve_requests(
            engine, sizes, clock, "scene0", cams)
        spans = {len(t.output_sharding.device_set) for t in tickets}
        results[name] = outs
        emit(name, clock, mark, n_requests=stats["n_requests"],
             output_devices=sorted(spans), smoke_p50_ms=stats["p50_ms"],
             smoke_mpix_per_s=stats["mpix_per_s"])
        want = 1 if m is None else n_devices
        check(spans == {want}, f"{name} output spans {spans} devices")
    mark = clock.mark()
    diff = max(max_abs(a, b) for a, b in zip(results["serve_1dev"],
                                               results["serve_sharded"]))
    check(diff <= PARITY_ATOL, f"sharded vs one device: {diff}")
    emit("shard_parity", clock, mark, n_requests=len(results["serve_1dev"]),
         max_abs_diff=diff, atol=PARITY_ATOL)


def run_one_chip(sizes: Sizes, clock: CompileClock,
                 require_compiled: bool = True):
    phase_kernels(sizes, clock, require_compiled)
    params, _ = train(sizes, clock)
    serve_and_check(params, sizes, clock)
    del params
    phase_other_apps(sizes, clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: only the multi-chip paths and their "
                         "one-device comparisons")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(json.dumps({"phase": "setup", "device": device_label(),
                      "compile_cache": cache}), flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(Sizes(), clock)
    else:
        run_one_chip(Sizes(), clock)
    print(json.dumps({"phase": "done", "wall_s":
                      round(time.perf_counter() - t0, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": device_label()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
