"""Tile serving through ``RenderEngine``: one client in a closed loop.

Traffic parameters (``bench/traffic/<name>.json``):

* ``tile_pixels``, ``n_samples``, ``max_inflight``: the engine's bucket
  and queue depth;
* ``image``: [height, width] of the frames requests are cut from;
* ``walk``: ``row_major`` streams consecutive tiles of whole frames, the
  orbit camera (``camera``: radius, height, focal per width) advancing by
  a seeded step drawn from ``angle_step`` after each frame;
  ``tour`` pans over ``block``-sized blocks of one fixed image along a
  tour of ``tour_blocks`` adjacent blocks (a random walk drawn once from
  ``tour_seed``), back and forth from a start and direction drawn from the
  run's seed: every seed requests the same blocks, in another order (the
  gather's cost depends on where in the image a block lies);
* ``table_range``: the scene's table features are the published init
  U(-1e-4, 1e-4) scaled to U(-range, range), a stand-in for a trained
  table whose every level moves the pixels;
* ``check_requests``: how many served requests, drawn from the seed, are
  compared with the reference after the window; the limit of the widest
  pixel gap is the cell's, in ``bench/limits/<cell>.json``.

The client sends the next request as soon as ``submit`` returns and takes
each result as soon as it is ready; a request's latency runs from the call
to ``submit`` until the client holds its pixels. Requests sent in the
window are all waited for; ``mpix_per_s`` counts the valid pixels held by
the window's end over its length, ``tile_p95_ms`` is the 95th percentile
of every request's latency.
"""
from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

from bench import harness
from bench.reference import field as ref_field

TABLE_INIT = 1e-4


# ------------------------------------------------------------ traffic
def cameras_and_ids(traffic: dict, seed: int):
    """Endless stream of (intrinsics (3,), c2w (4, 4), pixel ids)."""
    rng = np.random.default_rng(seed)
    h, w = traffic["image"]
    tp = traffic["tile_pixels"]
    if traffic["walk"] == "row_major":
        cam = traffic["camera"]
        intr = np.array([h, w, cam["focal_per_width"] * w], np.float32)
        angle = rng.uniform(0.0, 2 * math.pi)
        while True:
            eye = (cam["radius"] * math.cos(angle),
                   cam["radius"] * math.sin(angle), cam["height"])
            c2w = ref_field.look_at(eye)
            for start in range(0, h * w, tp):
                yield intr, c2w, np.arange(start, min(start + tp, h * w),
                                           dtype=np.int32)
            angle += rng.uniform(*cam["angle_step"])
    elif traffic["walk"] == "tour":
        bh, bw = traffic["block"]
        if bh * bw != tp:
            raise ValueError("a block must fill the tile")
        intr = np.array([h, w, w], np.float32)
        c2w = np.eye(4, dtype=np.float32)
        rows, cols = np.arange(bh), np.arange(bw)
        tour = block_tour(h // bh, w // bw, traffic["tour_blocks"],
                          traffic["tour_seed"])
        i, step = int(rng.integers(len(tour))), int(rng.choice((-1, 1)))
        while True:
            by, bx = tour[i]
            ids = ((by * bh + rows)[:, None] * w + bx * bw + cols[None, :])
            yield intr, c2w, ids.astype(np.int32).ravel()
            if not 0 <= i + step < len(tour):
                step = -step
            i += step
    else:
        raise ValueError(f"unknown walk {traffic['walk']!r}")


def block_tour(ny: int, nx: int, n: int, seed: int) -> list:
    """A random walk of ``n`` adjacent blocks of an ny x nx grid."""
    rng = np.random.default_rng(seed)
    by, bx = int(rng.integers(ny)), int(rng.integers(nx))
    tour = [(by, bx)]
    while len(tour) < n:
        dy, dx = ((1, 0), (-1, 0), (0, 1), (0, -1))[rng.integers(4)]
        by = by + dy if 0 <= by + dy < ny else by - dy
        bx = bx + dx if 0 <= bx + dx < nx else bx - dx
        tour.append((by, bx))
    return tour


def scaled_table(params: dict, table_range: float) -> dict:
    return {**params, "grid": params["grid"] * (table_range / TABLE_INIT)}


# ------------------------------------------------------------- program
def _program_params(ctx, cfg):
    """The scene's weights, made on the device in one call from the seed."""
    import jax
    from repro.common.param import unbox
    from repro.core import fields

    def make(key):
        return scaled_table(unbox(fields.init_field(key, cfg))[0],
                            ctx.traffic["table_range"])

    return jax.jit(make)(harness.base_key(ctx.seed))


# the engine's private parts that the trace's phase map needs, as the
# program names them today: it offers no public way to its compiled program
ENGINE_HOOKS = ("_buckets", "_get_fn", "_get_stacked")


def _hlo_texts(engine, tp: int, cam):
    """The compiled tile program's HLO, for the trace's phase map."""
    import jax.numpy as jnp
    missing = [h for h in ENGINE_HOOKS if not hasattr(engine, h)]
    if missing:
        raise RuntimeError(
            f"RenderEngine has no {', '.join(missing)}: the traced run "
            "cannot find the compiled tile program for its phase map")
    key = next(iter(engine._buckets))
    fn = engine._get_fn(key)
    args = (engine._get_stacked(key), jnp.int32(0), cam,
            jnp.zeros(tp, jnp.int32), jnp.ones(tp, bool))
    return [fn.lower(*args).compile().as_text()]


def _host_seconds(snapshot: dict):
    """(host seconds in submit and dispatch, submits) of a registry
    snapshot."""
    hists = snapshot["histograms"]
    host = sum(h["sum"] for n, h in hists.items()
               if n.startswith(("serve.submit_s.", "serve.dispatch_s.")))
    n = sum(h["count"] for n, h in hists.items()
            if n.startswith("serve.submit_s."))
    return host, n


def run(ctx) -> "harness.Outcome":
    import jax
    import jax.numpy as jnp
    from repro.core import pipeline, render
    from repro.serve import RenderEngine, RenderRequest

    t = ctx.traffic
    tp, n_samples = t["tile_pixels"], t["n_samples"]
    cfg = harness.field_config(ctx.config)
    params = _program_params(ctx, cfg)
    jax.block_until_ready(params)
    ctx.mark("weights")
    engine = RenderEngine(
        pipeline.RenderSettings(tile_pixels=tp, n_samples=n_samples),
        max_inflight=t["max_inflight"])
    engine.add_scene("scene", cfg, params)
    engine.warmup()
    ctx.mark("warmup")

    stream = cameras_and_ids(t, ctx.seed)
    cams = {}

    def camera(intr, c2w):
        k = c2w.tobytes()
        if k not in cams:
            cams.clear()
            cams[k] = render.Camera(intrinsics=jnp.asarray(intr),
                                    c2w=jnp.asarray(c2w))
        return cams[k]

    intr, c2w, ids = next(stream)
    first = (camera(intr, c2w), intr, c2w, ids)
    hlo = _hlo_texts(engine, tp, first[0]) if ctx.trace else []

    rng = np.random.default_rng(ctx.seed + 1)
    keep = []                       # reservoir of requests to check
    seen = 0
    latencies, failed, held_pixels, held = [], 0, 0, 0
    pending = collections.deque()

    def take(entry, t_end):
        nonlocal seen, failed, held_pixels, held
        t0, ticket, intr_, c2w_, ids_ = entry
        with ctx.span("collect"):
            out = ticket.result()
        t_held = time.perf_counter()
        latencies.append(t_held - t0)
        if out.shape != (ids_.size, 3) or not np.all(np.isfinite(out)):
            failed += 1
        if t_held <= t_end:
            held_pixels += ids_.size
            held += 1
            ctx.done(t_held)
        seen += 1
        slot = (seen - 1 if len(keep) < t["check_requests"]
                else int(rng.integers(seen)))
        if slot < t["check_requests"]:
            item = (intr_, c2w_, ids_, np.array(out))
            if slot < len(keep):
                keep[slot] = item
            else:
                keep.append(item)

    before = engine.obs.snapshot()
    t_open = ctx.open_window()
    t_end = t_open + ctx.seconds
    sent = 0
    while True:
        cam, intr, c2w, ids = first
        t0 = time.perf_counter()
        if t0 >= t_end:
            break
        with ctx.span("submit"):
            ticket = engine.submit(RenderRequest("scene", cam, ids))
        sent += 1
        pending.append((t0, ticket, intr, c2w, ids))
        while pending and pending[0][1].is_ready():
            take(pending.popleft(), t_end)
        with ctx.span("prep"):
            intr, c2w, ids = next(stream)
            first = (camera(intr, c2w), intr, c2w, ids)
    ctx.close_window(t_end)
    while pending:
        take(pending.popleft(), t_end)
    ctx.end_window_work()
    after = engine.obs.snapshot()
    host_b, n_b = _host_seconds(before)
    host_a, n_a = _host_seconds(after)
    reduced = ctx.read_trace(hlo) if ctx.trace else None
    peak = harness.memory_peak(ctx.devices)
    del engine, params, cams, first, pending
    gc.collect()

    gap, extra = check(ctx, keep)
    counts = {"requests": sent, "held_requests": held,
              "held_pixels": held_pixels,
              "host_s": host_a - host_b, "submits": n_a - n_b,
              "tile_pixels": tp, "n_samples": n_samples,
              "window_s": ctx.seconds, **extra}
    values = {"mpix_per_s": held_pixels / ctx.seconds / 1e6,
              "tile_p95_ms": float(np.percentile(latencies, 95)) * 1e3,
              "setup_s": t_open - ctx.t_start}
    return harness.Outcome(
        attempted=sent, failed=failed, values=values,
        checks={"pixel_gap": (gap, ctx.limits["pixel_gap"])},
        counts=counts, memory_peak_bytes=peak, reduced=reduced)


# ----------------------------------------------------------- reference
def check(ctx, keep):
    """Widest gap of a served pixel from the reference over the kept
    requests, and any extra readings. With ``ctx.control`` the control's
    pixels stand in for the served ones, and the program's gap is an extra
    reading."""
    import jax
    import jax.numpy as jnp

    t = ctx.traffic
    ref = harness.reference(ctx.config)
    cfg = ctx.config
    tp = t["tile_pixels"]

    @jax.jit
    def weights(key):
        return scaled_table(ref.init_weights(key, cfg), t["table_range"])

    w = weights(harness.base_key(ctx.seed))
    fns = {}

    def pixels(precision, intr, c2w, ids):
        k = (precision, tuple(float(v) for v in intr))
        if k not in fns:
            fns[k] = jax.jit(lambda w_, c, i, p=precision, s=k[1]:
                             ref.render(w_, cfg, s, c, i, t["n_samples"], p))
        padded = np.zeros(tp, np.int32)
        padded[:ids.size] = ids
        return np.asarray(fns[k](w, jnp.asarray(c2w), jnp.asarray(padded))
                          )[:ids.size]

    program = control = 0.0
    for intr, c2w, ids, out in keep:
        want = pixels("highest", intr, c2w, ids)
        program = max(program, float(np.max(np.abs(out - want))))
        if ctx.control:
            low = pixels("high", intr, c2w, ids)
            control = max(control, float(np.max(np.abs(low - want))))
    if ctx.control:
        return control, {"program.pixel_gap": program}
    return program, {}
