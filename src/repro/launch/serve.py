"""Serving launcher — two modes, matching the paper's kind:

  * ``--mode render``: the NGPC use case — batched pixel-request serving
    against a trained neural field (tiles scheduled like Fig. 10).
  * ``--mode lm``: LM decode loop (prefill + token-by-token decode) for
    the assigned architectures.

  PYTHONPATH=src python -m repro.launch.serve --mode render --app gia
  PYTHONPATH=src python -m repro.launch.serve --mode render --app nvr \
      --log2-table-size 14        # a CPU-sized table; default is Table I
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch olmoe-1b-7b --reduced
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.obs import log as obs_log
from repro.obs.trace import TRACER

_LOG = obs_log.get_logger("serve")


def serve_render(app: str = "gia", encoding: str = "hash",
                 train_steps: int = 150, n_requests: int = 8,
                 tile_pixels: int = 4096, height: int = 128,
                 width: int = 128, use_pallas: bool = False, seed: int = 0,
                 n_scenes: int = 2, n_cameras: int = 3, shard: bool = False,
                 occupancy: bool = False,
                 sample_budget: int | None = None,
                 quant: str | None = None,
                 metrics_out: str | None = None,
                 log2_table_size: int | None = None):
    """Train ``n_scenes`` small fields, then serve a mixed request stream
    (scenes x viewpoints) through the RenderEngine — one compiled
    executable for the whole bucket, warmup excluded from latency stats.

    The field is the Table I configuration of ``app``/``encoding``;
    ``log2_table_size`` replaces its hash-table size (CPU runs use 14).
    A compile inside the served window raises.

    ``occupancy`` serves the ray apps occupancy-culled (DESIGN.md §7):
    training maintains the grid at chunk ends, the engine compacts to
    ``sample_budget`` samples per tile (default: the dense count).

    ``quant`` ('int8' | 'fp8_e4m3') serves the scenes post-training-
    quantized (DESIGN.md §10): tables are calibrated and encoded after
    training, the engine buckets them separately (cfg.quant + leaf
    dtypes), and both kernel routes dequantize on the fly."""
    import dataclasses
    from repro.core import pipeline
    from repro.core.train import train_field
    from repro.data import scenes
    from repro.quant import QuantSpec, quantize_field
    from repro.serve import RenderEngine, RenderRequest

    if n_scenes < 1 or n_cameras < 1:
        raise ValueError(f"need >=1 scene and >=1 camera "
                         f"(got {n_scenes}, {n_cameras})")
    if occupancy and app not in ("nerf", "nvr"):
        raise ValueError(f"--occupancy needs a ray-marched app (nerf/nvr),"
                         f" got {app!r}")
    cfg = registry.field_config(app, encoding)
    if log2_table_size is not None:
        # with_grid recomputes the dependent MLP dims (nerf's density MLP)
        cfg = cfg.with_grid(
            dataclasses.replace(cfg.grid, log2_table_size=log2_table_size))
    qspec = QuantSpec(table_qtype=quant) if quant else None
    if qspec is not None:
        cfg = cfg.with_quant(qspec)

    settings = pipeline.RenderSettings(tile_pixels=tile_pixels,
                                       use_pallas=use_pallas,
                                       occupancy=occupancy,
                                       sample_budget=sample_budget)
    mesh = make_local_mesh() if shard else None
    engine = RenderEngine(settings, mesh=mesh)
    for s in range(n_scenes):
        _LOG.info("train_scene", scene=s, config=cfg.name,
                  steps=train_steps)
        params, hist = train_field(
            cfg, steps=train_steps, batch_size=4096, seed=seed + s,
            occupancy_res=32 if occupancy else None)
        _LOG.info("scene_trained", scene=s,
                  loss_first=round(float(hist[0][1]), 4),
                  loss_last=round(float(hist[-1][1]), 4))
        if qspec is not None:
            params = quantize_field(params, qspec)
            _LOG.info("scene_quantized", scene=s, quant=qspec.tag)
        engine.add_scene(f"scene{s}", cfg, params)

    # viewpoints orbiting the scene — all served by the same executable
    cams = [scenes.orbit_camera(height, width, 2.0 * np.pi * c / n_cameras)
            for c in range(n_cameras)]

    t_warm = engine.warmup()
    _LOG.info("warmup", compile_s=round(t_warm, 2),
              note="excluded from stats")

    # mixed batched request stream: random (scene, camera, pixels) tuples
    rng = np.random.default_rng(seed)
    for r in range(n_requests):
        ids = rng.integers(0, height * width, tile_pixels).astype(np.int32)
        req = RenderRequest(scene=f"scene{r % n_scenes}",
                            camera=cams[r % n_cameras], pixel_ids=ids)
        engine.submit(req)
    engine.flush()

    stats = engine.stats()
    _LOG.info("served", n_requests=stats["n_requests"],
              n_scenes=n_scenes, n_cameras=n_cameras,
              p50_ms=round(stats["p50_ms"], 1),
              p99_ms=round(stats["p99_ms"], 1),
              mpix_per_s=round(stats["mpix_per_s"], 2),
              compiles=stats["n_traces_total"])
    if occupancy:
        _LOG.info("occupancy_culling",
                  live_sample_frac=round(stats["live_sample_frac"], 3),
                  samples_dropped=stats["samples_dropped"],
                  effective_mpix_per_s=round(
                      stats["effective_mpix_per_s"], 2))
    med_s = stats["p50_ms"] / 1e3
    _LOG.info("frame_budget_4k",
              ms_per_frame=round(3840 * 2160 / tile_pixels * med_s * 1e3))
    if stats["n_traces_total"] != len(stats["buckets"]):
        raise RuntimeError(
            f"{stats['n_traces_total']} traces for "
            f"{len(stats['buckets'])} buckets: a camera or scene leaked "
            "into the compiled graph")
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(engine.obs.to_json())
        _LOG.info("metrics_written", path=metrics_out)
    return stats


def serve_lm(arch: str, reduced: bool = True, batch: int = 2,
             prompt_len: int = 32, gen_len: int = 16, seed: int = 0):
    from repro.common.partitioning import DEFAULT_RULES
    from repro.parallel import api

    cfg = (registry.reduced_config(arch) if reduced
           else registry.get_config(arch))
    mesh = make_local_mesh()
    rules = DEFAULT_RULES.copy_with()
    capacity = prompt_len + gen_len

    prefill_fn, psh = api.make_prefill_step(
        cfg, mesh, rules, capacity=capacity, batch_size=batch,
        enc_len=prompt_len if cfg.is_encdec else 0,
        example_batch=None)
    decode_fn, dsh = api.make_decode_step(
        cfg, mesh, rules, capacity=capacity, batch_size=batch,
        enc_len=prompt_len if cfg.is_encdec else 0)

    params = api.init_params(cfg, seed=seed, mesh=mesh, rules=rules)
    cache = api.make_cache(cfg, batch, capacity,
                           enc_len=prompt_len if cfg.is_encdec else 0,
                           shardings=dsh["cache"])
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (batch, prompt_len)), jnp.int32)
    batch_in = {"tokens": toks}
    if cfg.is_encdec:
        batch_in["enc_embeddings"] = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            cfg.adtype)
    if cfg.frontend == "vision":
        batch_in = {"embeddings": jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            cfg.adtype)}

    with TRACER.span("lm.prefill", cat="serve", timed=True,
                     arch=arch) as prefill:
        logits, cache = prefill_fn(params, batch_in, cache)
        logits.block_until_ready()  # repro: allow[host-sync] prefill timing boundary
    t_prefill = prefill.seconds
    out_tokens = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    with TRACER.span("lm.decode", cat="serve", timed=True, arch=arch,
                     n_steps=gen_len) as decode:
        for i in range(gen_len):
            out_tokens.append(np.asarray(tok)[:, 0])
            logits, cache = decode_fn(params, cache, tok,
                                      jnp.int32(prompt_len + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        jax.block_until_ready(logits)  # repro: allow[host-sync] decode timing boundary
    t_decode = decode.seconds
    _LOG.info("lm_served", arch=arch, prompt_len=prompt_len,
              prefill_ms=round(t_prefill * 1e3),
              decode_steps=gen_len, decode_ms=round(t_decode * 1e3),
              tok_per_s=round(gen_len * batch / t_decode, 1))
    _LOG.info("lm_sample",
              tokens=[int(t) for t in np.stack(out_tokens, 1)[0][:12]])
    return t_prefill, t_decode


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="render", choices=["render", "lm"])
    ap.add_argument("--app", default="gia")
    ap.add_argument("--encoding", default="hash")
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tile-pixels", type=int, default=4096)
    ap.add_argument("--height", type=int, default=128)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--scenes", type=int, default=2)
    ap.add_argument("--cameras", type=int, default=3)
    ap.add_argument("--log2-table-size", type=int, default=None,
                    help="hash-table size of the served field (default: "
                         "the Table I value; CPU runs use 14)")
    ap.add_argument("--shard", action="store_true",
                    help="pixel-parallel shard_map over the local mesh")
    ap.add_argument("--occupancy", action="store_true",
                    help="occupancy-culled sampling (ray apps)")
    ap.add_argument("--sample-budget", type=int, default=None,
                    help="static field-eval budget per tile (default: "
                         "tile_pixels * n_samples, the dense count)")
    ap.add_argument("--quant", default=None,
                    choices=["int8", "fp8_e4m3"],
                    help="post-training table quantization (repro.quant):"
                         " serve scenes with int8/fp8 tables, dequantized"
                         " in-kernel on the Pallas route")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of the run here "
                         "(enables the span tracer)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics snapshot JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace_out:
        TRACER.enable()
    if args.mode == "render":
        serve_render(args.app, args.encoding, use_pallas=args.use_pallas,
                     train_steps=args.train_steps, n_requests=args.requests,
                     tile_pixels=args.tile_pixels, height=args.height,
                     width=args.width, n_scenes=args.scenes,
                     n_cameras=args.cameras, shard=args.shard,
                     occupancy=args.occupancy,
                     sample_budget=args.sample_budget,
                     quant=args.quant,
                     metrics_out=args.metrics_out,
                     log2_table_size=args.log2_table_size)
    else:
        serve_lm(args.arch, args.reduced)
    if args.trace_out:
        TRACER.export(args.trace_out)
        _LOG.info("trace_written", path=args.trace_out,
                  n_events=len(TRACER.events()))


if __name__ == "__main__":
    main()
