"""RenderEngine — batched multi-scene serving on one compiled executable.

The paper's NGPC serves frames by pipelining fixed-shape batches through
dedicated engines (Fig. 10); ICARUS argues the unit of scheduling is the
per-request batch, not the frame. This engine is that idea on TPU/XLA:

  * **Shape buckets.** Requests are grouped by
    ``(app, encoding, tile_pixels, n_samples, dtype)`` — everything that
    changes the *compiled graph*. Per bucket there is exactly one traced
    executable; scene id, camera, and pixel ids are traced *data*
    (DESIGN.md §3), so new viewpoints and new scenes never recompile.
  * **Megabatch pad + mask.** Every request is padded to the bucket's
    fixed ``tile_pixels`` shape; a boolean mask zeroes the padding lanes
    and the host slices the valid prefix off the result.
  * **Stacked scenes.** Per-scene field params are stacked along a leading
    scene axis and gathered per request by a traced ``scene_id`` — N
    scenes of one bucket share one executable (grid_sram residency: every
    chip holds every scene's tables).
  * **Double-buffered dispatch.** ``submit`` returns a :class:`Ticket`
    immediately (XLA async dispatch); the engine blocks only when more
    than ``max_inflight`` megabatches are outstanding — tile N+1 is
    enqueued while tile N is in flight, the Fig. 10 GPU/NGPC overlap.
  * **Optional pixel-parallel sharding.** With a mesh, the megabatch's
    pixel axis shard_maps over the 'field_batch' axes of the shared
    partitioning rules (repro.serve.sharding).
  * **Occupancy-culled sampling.** With ``settings.occupancy`` the ray
    apps march through the static-budget compaction (DESIGN.md §7):
    scenes carry an ``occupancy`` grid leaf (stacked like the tables),
    the bucket key grows ``(occupancy, sample_budget)`` (the budget
    changes the traced shapes), and ``stats()`` reports the live-sample
    fraction and dropped-sample count next to the effective Mpix/s.
  * **Observability (DESIGN.md §8).** Each request's four phases are
    live spans of the process tracer (``repro.obs.trace.TRACER``):
    ``serve.submit`` (request preparation), ``serve.dispatch`` (the
    jitted call's enqueue), ``serve.block`` (``Ticket.result`` waiting
    on the device) and ``serve.slice`` (the device-to-host copy and the
    valid-prefix slice), each with its ``bucket`` and the request's
    per-engine sequence number ``request``. The engine owns an
    ``repro.obs.metrics.Registry``: per-bucket phase histograms fed by
    the spans' own stamps, a ``serve.compiles`` counter fed by the
    trace-time side effect, and the submit→retire latency histogram
    that ``stats()``'s p50/p99 read (warmup excluded). Disabled (the
    default) a span is a pair of ``perf_counter`` stamps — **no added
    device syncs**.

Register all scenes, then ``warmup()`` (compiles each bucket once, outside
the latency statistics), then submit the mixed request stream.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline, render
from repro.core.fields import FieldConfig
from repro.core.pipeline import RenderSettings
from repro.obs import metrics as obs_metrics
from repro.obs.trace import TRACER, Stamps
from repro.quant.api import is_quantized_field
from repro.serve import sharding


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """Everything that selects a distinct compiled executable.

    ``(app, encoding, tile_pixels, n_samples, dtype)`` is the semantic
    bucket identity (DESIGN.md §3); ``cfg`` carries the full frozen
    FieldConfig so configs that differ below the app/encoding level
    (table size, level count, MLP dims) — which also change the traced
    graph — land in distinct buckets rather than colliding. ``dtype`` is
    the ordered tuple of param-leaf dtypes (mixed-precision scenes, e.g.
    bf16 tables + f32 MLPs, must not stack with all-f32 ones —
    ``jnp.stack`` would silently promote). ``occupancy``/``sample_budget``
    change the traced shapes (the compaction's static prefix, DESIGN.md
    §7), so different budgets must never collide on one executable."""
    app: str
    encoding: str
    tile_pixels: int
    n_samples: int
    dtype: str
    cfg: FieldConfig
    occupancy: bool = False
    sample_budget: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RenderRequest:
    """One pixel-batch request: scene + viewpoint + flat pixel ids.

    ``pixel_ids`` may hold at most the bucket's ``tile_pixels`` entries;
    larger workloads (full frames) are split into several requests
    (``RenderEngine.render_frame`` does this)."""
    scene: str
    camera: render.Camera
    pixel_ids: np.ndarray


class Ticket:
    """Handle for an in-flight request; ``result()`` blocks and returns
    the valid (n, 3) rgb rows.

    Recorded latency is submit→retire (standard serving semantics: it
    includes queueing behind earlier megabatches). The engine retires
    device-ready tickets eagerly on every subsequent ``submit`` so a
    ticket held by the caller does not keep accruing host time."""

    @property
    def output_sharding(self):
        """Sharding of the device output: a pixel-parallel engine's
        output spans every device of its mesh."""
        return self._out.sharding

    def is_ready(self) -> bool:
        try:
            return self._done or bool(self._out.is_ready())
        except AttributeError:        # non-jax output (sharded host array)
            return True

    def __init__(self, engine: "RenderEngine", out, n_valid: int,
                 t_submit: float, warmup: bool, aux=None, bucket_idx=0,
                 request: int = 0):
        self._engine = engine
        self._out = out
        self._n = n_valid
        self._t_submit = t_submit
        self._warmup = warmup
        self._aux = aux              # (k, 3) [live, total, dropped] rows
        self._bidx = bucket_idx
        self.request = request       # the engine's sequence number
        self._res: Optional[np.ndarray] = None
        self._done = False

    # repro: sync-boundary result() is THE designated submit/result sync point
    def result(self) -> np.ndarray:
        if not self._done:
            eng = self._engine
            with eng._span("block", self._bidx, self.request,
                           self._warmup) as block:
                jax.block_until_ready(self._out)
            self.latency_s = block.end - self._t_submit
            with eng._span("slice", self._bidx, self.request,
                           self._warmup) as sliced:
                res = np.asarray(self._out)[:self._n]
            if not self._warmup:
                eng._record(self.latency_s, self._n, block.end)
                eng._record_phase(self._bidx, "block", block)
                eng._record_phase(self._bidx, "slice", sliced)
                if self._aux is not None:
                    eng._record_aux(np.asarray(self._aux).sum(axis=0))
            self._res = res
            self._done = True
        return self._res


class _Bucket:
    def __init__(self, cfg: FieldConfig, key: BucketKey, idx: int):
        self.cfg = cfg
        self.key = key
        self.idx = idx                       # insertion index (metric label)
        self.order: List[str] = []           # scene names, stack order
        self.params: Dict[str, dict] = {}
        self.stacked = None                  # cached jnp.stack of params
        self.fn = None                       # cached jitted executable
        self.n_traces = 0                    # trace (compile) counter


class RenderEngine:
    """Shape-bucketed, multi-scene, async render server (DESIGN.md §3;
    observability contract in DESIGN.md §8).

    The one-trace-per-bucket and async-submit contracts are lint-checked
    (DESIGN.md §9): RJ202 verifies Camera treedef / BucketKey hash
    stability against this module, ``submit`` is a ``# repro: hot-path``
    scope where host syncs are errors, and ``Ticket.result`` is the
    designated ``# repro: sync-boundary``."""

    def __init__(self, settings: Optional[RenderSettings] = None,
                 mesh=None, rules=None, max_inflight: int = 2,
                 metrics_registry: Optional[obs_metrics.Registry] = None):
        self.settings = settings or RenderSettings()
        self.mesh = mesh
        self.rules = rules
        self.max_inflight = max(1, max_inflight)
        if mesh is not None:
            shards = sharding.pixel_shard_count(mesh, rules)
            if self.settings.tile_pixels % shards != 0:
                raise ValueError(
                    f"tile_pixels={self.settings.tile_pixels} not divisible"
                    f" by the mesh's {shards} pixel shards")
            sharding.check_sample_budget(self.settings, shards)
        # per-engine registry: engines in one process (tests, A/B serving)
        # must not mix latency histograms
        self.obs = metrics_registry or obs_metrics.Registry()
        self._lat_hist = self.obs.histogram("serve.latency_s")
        self._buckets: Dict[BucketKey, _Bucket] = {}
        self._scene_bucket: Dict[str, BucketKey] = {}
        self._inflight: collections.deque = collections.deque()
        self._requests = itertools.count()   # per-engine request ids
        self._pixels = 0
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._warmup_s = 0.0
        # culled-sampling aggregates (occupancy buckets only):
        # [live, total, dropped] sample counts over the serving window
        self._samples = np.zeros(3, np.float64)

    # ------------------------------------------------------------- scenes
    def add_scene(self, name: str, cfg: FieldConfig, params) -> BucketKey:
        """Register a trained scene. Scenes stack (= share one compiled
        executable) iff their FieldConfig and param dtypes match exactly;
        otherwise they transparently get their own bucket. Register every
        scene *before* ``warmup()``: growing a bucket's scene axis
        changes the stacked shape and forces a re-trace."""
        if name in self._scene_bucket:
            raise ValueError(f"scene {name!r} already registered")
        # ordered per-leaf dtypes (tree order is deterministic given cfg):
        # a bf16-table+f32-MLP scene must not collide with f32-table+bf16-MLP
        dtype = ",".join(str(l.dtype) for l in jax.tree.leaves(params))
        # quantized scenes (repro.quant): params and config must agree —
        # a quantized tree under a dense cfg (or vice versa) would compile
        # but silently mis-bucket or crash in the kernels at trace time
        q_params = is_quantized_field(params)
        if q_params and cfg.quant is None:
            raise ValueError(
                f"scene {name!r} has quantized params but cfg.quant is "
                "None — pair quantize_field(params, spec) with "
                "cfg.with_quant(spec)")
        if cfg.quant is not None and cfg.quant.table_qtype is not None \
                and "grid_scale" not in params:
            raise ValueError(
                f"scene {name!r}: cfg.quant declares table_qtype="
                f"{cfg.quant.table_qtype!r} but params have no "
                "'grid_scale' leaf — run repro.quant.quantize_field")
        if (self.settings.occupancy and cfg.app in ("nerf", "nvr")
                and "occupancy" not in params):
            raise ValueError(
                f"engine settings have occupancy=True but scene {name!r} "
                "has no 'occupancy' leaf — build one with "
                "core.occupancy.build_occupancy and attach()")
        key = BucketKey(app=cfg.app, encoding=cfg.grid.kind,
                        tile_pixels=self.settings.tile_pixels,
                        n_samples=self.settings.n_samples, dtype=dtype,
                        cfg=cfg, occupancy=self.settings.occupancy,
                        sample_budget=self.settings.sample_budget)
        bucket = self._buckets.get(key)
        if bucket is None:
            bucket = self._buckets[key] = _Bucket(cfg, key,
                                                  len(self._buckets))
        bucket.order.append(name)
        bucket.params[name] = params
        bucket.stacked = None                # re-stack lazily
        self._scene_bucket[name] = key
        return key

    def scenes(self) -> List[str]:
        return list(self._scene_bucket)

    # ----------------------------------------------------------- compile
    def _get_stacked(self, key: BucketKey):
        bucket = self._buckets[key]
        if bucket.stacked is None:
            bucket.stacked = pipeline.stack_scene_params(
                [bucket.params[n] for n in bucket.order])
        return bucket.stacked

    def _get_fn(self, key: BucketKey):
        bucket = self._buckets[key]
        if bucket.fn is None:
            with_aux = self.settings.occupancy
            mtile = pipeline.make_multi_scene_tile_fn(
                bucket.cfg, self.settings, with_aux=with_aux)
            compiles = self.obs.counter("serve.compiles")

            def fn(stacked, scene_id, cam, pixel_ids, mask):
                bucket.n_traces += 1     # python side effect: counts traces
                compiles.inc()
                out = mtile(stacked, scene_id, cam, pixel_ids)
                if with_aux:
                    rgb, aux = out
                    return jnp.where(mask[:, None], rgb, 0.0), aux
                return jnp.where(mask[:, None], out, 0.0)

            if self.mesh is not None:
                fn = sharding.shard_tile_fn(fn, self.mesh, self.rules,
                                            with_aux=with_aux)
            bucket.fn = jax.jit(fn)
        return bucket.fn

    def compiled_text(self) -> str:
        """Optimized HLO text of the first registered bucket's compiled
        tile program. Camera values are traced data, so any camera gives
        the same program."""
        key = next(iter(self._buckets))
        tp = self.settings.tile_pixels
        return self._get_fn(key).lower(
            self._get_stacked(key), jnp.int32(0), _warmup_camera(),
            jnp.zeros(tp, jnp.int32), jnp.ones(tp, bool)).compile().as_text()

    def warmup(self) -> float:
        """Compile every bucket once (dummy request) — excluded from the
        latency statistics, so p50/p99 measure serving, not XLA (the
        warmup-exclusion rule of ``obs.trace.time_fn``)."""
        with Stamps() as clock:
            cam = _warmup_camera()
            for key, bucket in self._buckets.items():
                req = RenderRequest(scene=bucket.order[0], camera=cam,
                                    pixel_ids=np.zeros(1, np.int32))
                self.submit(req, _warmup=True).result()
        self._warmup_s += clock.seconds
        return self._warmup_s

    # ------------------------------------------------------------- serve
    # repro: hot-path submit must stay async — device syncs live in result()
    def submit(self, req: RenderRequest, _warmup: bool = False) -> Ticket:
        key = self._scene_bucket.get(req.scene)
        if key is None:
            raise KeyError(f"unknown scene {req.scene!r}")
        bucket = self._buckets[key]
        tp = self.settings.tile_pixels
        request = next(self._requests)
        with self._span("submit", bucket.idx, request, _warmup,
                        scene=req.scene) as prep:
            # repro: allow[host-sync] request ids arrive as host numpy, never traced
            ids = np.asarray(req.pixel_ids, np.int32).ravel()
            n = ids.shape[0]
            if n > tp:
                raise ValueError(f"request has {n} pixels > tile_pixels="
                                 f"{tp}; split it (see render_frame)")
            padded = np.zeros(tp, np.int32)
            padded[:n] = ids
            mask = np.zeros(tp, bool)
            mask[:n] = True

            fn = self._get_fn(key)
            stacked = self._get_stacked(key)
            sid = jnp.asarray(bucket.order.index(req.scene), jnp.int32)
        # host-side spans only: dispatch is the async XLA enqueue —
        # nothing here blocks on the device
        with self._span("dispatch", bucket.idx, request,
                        _warmup) as dispatch:
            out = fn(stacked, sid, req.camera, jnp.asarray(padded),
                     jnp.asarray(mask))
        if not _warmup and self._t_first is None:
            self._t_first = dispatch.start
        aux = None
        if self.settings.occupancy:
            out, aux = out
        if not _warmup:
            self._record_phase(bucket.idx, "submit", prep)
            self._record_phase(bucket.idx, "dispatch", dispatch)
        ticket = Ticket(self, out, n, dispatch.start, warmup=_warmup,
                        aux=aux, bucket_idx=bucket.idx, request=request)
        self._inflight.append(ticket)
        # retire already-finished work first so its recorded latency is
        # the device completion, not however long the caller sat on it
        while self._inflight and self._inflight[0].is_ready():
            self._inflight.popleft().result()
        # double buffering: keep at most max_inflight megabatches queued —
        # request N+1 is dispatched above *before* this blocks on N-k.
        while len(self._inflight) > self.max_inflight:
            self._inflight.popleft().result()
        return ticket

    def flush(self):
        while self._inflight:
            self._inflight.popleft().result()

    def render_frame(self, scene: str, cam: render.Camera) -> np.ndarray:
        """Full-frame convenience: split into megabatch tiles, serve them
        through the pipelined queue, reassemble (H, W, 3)."""
        h, w = cam.resolution
        tp = self.settings.tile_pixels
        tickets = []
        for start in range(0, h * w, tp):
            ids = np.arange(start, min(start + tp, h * w), dtype=np.int32)
            tickets.append(self.submit(RenderRequest(scene, cam, ids)))
        parts = [t.result() for t in tickets]
        return np.concatenate(parts, axis=0).reshape(h, w, 3)

    # ------------------------------------------------------------- stats
    def _record(self, latency_s: float, n_pixels: int, t_done: float):
        self._lat_hist.record(latency_s)
        self.obs.counter("serve.requests").inc()
        self.obs.counter("serve.pixels").inc(n_pixels)
        self._pixels += n_pixels
        self._t_last = t_done

    @staticmethod
    def _span(phase: str, bucket_idx: int, request: int, warmup: bool,
              **args):
        """The live span of one request's phase; warmup requests get
        bare stamps and reach no sink."""
        if warmup:
            return Stamps()
        return TRACER.span(f"serve.{phase}", cat="serve", timed=True,
                           bucket=bucket_idx, request=request, **args)

    def _record_phase(self, bucket_idx: int, phase: str, span):
        """A phase histogram's sample: its span's own duration."""
        self.obs.histogram(
            f"serve.{phase}_s.bucket{bucket_idx}").record(span.seconds)

    def _record_aux(self, row: np.ndarray):
        self._samples += row

    def trace_counts(self) -> Dict[BucketKey, int]:
        return {k: b.n_traces for k, b in self._buckets.items()}

    def total_traces(self) -> int:
        return sum(b.n_traces for b in self._buckets.values())

    def stats(self) -> Dict:
        p50_s = self._lat_hist.percentile(50)
        p99_s = self._lat_hist.percentile(99)
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        live, total, dropped = self._samples
        n_req = int(self.obs.counter("serve.requests").value)
        # effective Mpix/s is the *served* throughput — with culling on,
        # the same wall clock serves more pixels, so the win shows up
        # here directly; live_sample_frac explains where it came from.
        mpix = (self._pixels / wall / 1e6) if wall > 0 else float("nan")
        return {
            "n_requests": n_req,
            "p50_ms": p50_s * 1e3,
            "p99_ms": p99_s * 1e3,
            "mpix_per_s": mpix,
            "effective_mpix_per_s": mpix,
            "live_sample_frac": (live / total) if total > 0
            else float("nan"),
            "samples_total": total,
            "samples_dropped": dropped,
            "requests_per_s": (n_req / wall) if wall > 0
            else float("nan"),
            "wall_s": wall,
            "pixels": self._pixels,
            "warmup_s": self._warmup_s,
            "n_traces_total": self.total_traces(),
            "buckets": {
                f"{k.app}/{k.encoding}/tp{k.tile_pixels}/s{k.n_samples}"
                f"/{k.dtype}/T{k.cfg.grid.log2_table_size}"
                f"L{k.cfg.grid.n_levels}"
                + (f"/occ-bgt{k.sample_budget}" if k.occupancy else "")
                + (f"/q-{k.cfg.quant.tag}" if k.cfg.quant else "")
                + f"#{b.idx}": {
                    "n_traces": b.n_traces, "n_scenes": len(b.order)}
                for k, b in self._buckets.items()},
            "metrics": self.obs.snapshot(),
        }


def _warmup_camera() -> render.Camera:
    return render.Camera(height=8, width=8, focal=8.0,
                         c2w=render.look_at((2.2, 1.6, 1.8), (0, 0, 0)))
