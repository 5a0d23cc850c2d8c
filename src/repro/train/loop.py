"""One training engine for both stacks (DESIGN.md §6).

The paper's apps are trained, then served. Serving got a production
engine in repro/serve; this module is the training-side counterpart: a
single chunked-scan loop that both the neural-field trainer
(``core/train.train_field``) and the LM launcher
(``launch/train.train_loop``) run on. The engine owns

  * jitted ``lax.scan`` multi-step chunks with donated
    ``(params, opt_state)`` buffers — one dispatch per chunk instead of
    one per step;
  * on-device batch synthesis (``device_batch_fn``): the per-step batch
    key is ``jax.random.fold_in(data_key, global_step)``, so batches are
    a pure function of the step index — no host round trip per step and
    restart-deterministic by construction;
  * host batch sources (``host_batch_fn``): per-chunk stacked host
    batches, prefetched on a background thread
    (``data/tokens.Prefetcher``) and device_put with the stacked batch
    shardings while the previous chunk computes;
  * gradient accumulation and optional error-feedback gradient
    compression (``train/compression``) on the configured leaves;
  * optional data-parallel ``shard_map`` of the loss/grad over the mesh
    axes that ``common/partitioning`` binds to a logical batch axis
    (``'field_batch'`` for the field apps);
  * ``checkpoint/store.AsyncCheckpointer`` save/resume — the step
    counter continues across restarts (``runtime/elastic.py`` contract);
  * ``runtime/health.py`` heartbeat/straggler hooks per chunk.

Chunk ends are aligned to a *global* step grid (multiples of
``chunk_steps``), not to wherever a restart happened to begin: a resumed
run re-enters the same (start, length) chunk sequence as an
uninterrupted run, so the two execute identical compiled programs on
identical inputs — loss trajectories match bitwise, not just to
tolerance (tests/test_train_engine.py).

Observability (DESIGN.md §8): each chunk is a live ``train.chunk`` span
of the process tracer (``start``, ``n_steps``, ``host``) holding three
more: ``train.dispatch`` (the chunk call), ``train.sync`` (the
``device_get`` of its metrics) and ``train.host`` (everything after that
before the next chunk call: the metric rows, ``on_metrics``, the
checkpoint, ``on_chunk_end`` and the health poll). The engine owns an
``repro.obs.metrics.Registry``; the spans' own stamps feed its
``train.{dispatch,sync,host}_s`` histograms and the per-step
``train.step_s`` (dispatch through sync over the chunk's steps), next to
a structured log row, and the straggler detector's per-host step-time
histograms live in the same registry (``health.step_s.<host>``) — one
measurement substrate for health, metrics snapshots, and traces.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import store
from repro.common import partitioning
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs.trace import TRACER
from repro.runtime.health import (FailurePolicy, HeartbeatMonitor,
                                  StragglerDetector)
from repro.train import compression as compression_mod
from repro.train import optim

_LOG = obs_log.get_logger("train")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Loop-shape knobs; everything task-specific lives in the step fn."""
    steps: int
    chunk_steps: int = 16          # scan length; chunk ends on this grid
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50           # min steps between saves (chunk-end snapped)
    ckpt_keep: int = 3
    prefetch: int = 2              # host-chunk prefetch depth
    donate: bool = True
    heartbeat_timeout_s: float = 600.0
    host: Optional[str] = None     # health-hook host label


def chunk_plan(start: int, steps: int,
               chunk_steps: int) -> List[Tuple[int, int]]:
    """Segment ``[start, steps)`` into (chunk_start, n) pieces whose ends
    sit on the global ``chunk_steps`` grid (plus the final step).

    Grid alignment — NOT ``start``-relative chunking — is what makes a
    resumed run replay the exact chunk sequence of an uninterrupted one
    (same compiled programs, bitwise-matching trajectories), and keeps
    the set of distinct scan lengths (= compiled chunk programs) small.
    """
    plan = []
    cur = start
    while cur < steps:
        end = min((cur // chunk_steps + 1) * chunk_steps - 1, steps - 1)
        plan.append((cur, end - cur + 1))
        cur = end + 1
    return plan


@dataclasses.dataclass(frozen=True)
class _CompressionKnobs:
    """The attribute subset ``compression.apply_inline`` reads."""
    compression: str
    compression_topk: float


def _shard_count(mesh: Optional[Mesh], axes) -> int:
    if mesh is None or axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def data_parallel_grad_fn(loss_fn: Callable, mesh: Optional[Mesh],
                          rules: Optional[partitioning.LogicalRules] = None,
                          batch_axis: str = "field_batch") -> Callable:
    """``(params, batch) -> (loss, grads)``, optionally shard_map'd.

    The batch (every leaf, axis 0) shards over the mesh axes that
    ``rules`` bind to ``batch_axis``; params replicate. Local mean
    loss/grads are ``pmean``-reduced, so the result equals the unsharded
    global-batch gradient (equal shard sizes). Compression sits *after*
    this reduce (see ``make_scanned_step``) — mirroring the LM step,
    where the compressed exchange models the cross-pod (DCN) hop, not
    the intra-pod reduce."""
    base = jax.value_and_grad(loss_fn)
    rules = rules or partitioning.DEFAULT_RULES
    axes = (partitioning.present_axes(mesh, rules.mesh_axes(batch_axis))
            if mesh is not None else None)
    if _shard_count(mesh, axes) == 1:
        return base
    names = (axes,) if isinstance(axes, str) else tuple(axes)

    def local(params, batch):
        loss, grads = base(params, batch)
        loss = jax.lax.pmean(loss, names)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, names), grads)
        return loss, grads

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axes)),
                         out_specs=(P(), P()), check_vma=False)


def make_scanned_step(loss_fn: Callable, opt_cfg: optim.AdamConfig, *,
                      grad_accum: int = 1,
                      compression: Optional[str] = None,
                      compression_topk: float = 0.05,
                      compress_keys: Tuple[str, ...] = ("grid",),
                      mesh: Optional[Mesh] = None,
                      rules=None, batch_axis: str = "field_batch"
                      ) -> Callable:
    """Build an engine step ``(state, step, batch) -> (state, metrics)``
    from a pure ``loss_fn(params, batch)``.

    ``state = {'params', 'opt'[, 'efb']}``; ``efb`` (persistent
    error-feedback, one entry per ``compress_keys`` leaf — for the field
    apps that is the hash-table gradient, the naturally-sparse leaf that
    motivates top-k) is required iff ``compression`` is set; create it
    with :func:`init_train_state`. Metrics include loss, lr, and PSNR of
    an MSE loss."""
    grad_fn = data_parallel_grad_fn(loss_fn, mesh, rules, batch_axis)

    def step_fn(state, step, batch):
        del step                         # data keying happens upstream
        params = state["params"]
        if grad_accum > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def acc(carry, b):
                loss_a, grads_a = carry
                loss, grads = grad_fn(params, b)
                return (loss_a + loss,
                        jax.tree.map(jnp.add, grads_a, grads)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.zeros((), jnp.float32), zeros), mb)
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        else:
            loss, grads = grad_fn(params, batch)

        new_state = dict(state)
        if compression is not None:
            knobs = _CompressionKnobs(compression, compression_topk)
            sub = {k: grads[k] for k in compress_keys}
            sub, cstate = compression_mod.apply_inline(
                sub, {"efb": state["efb"]}, knobs)
            grads = {**grads, **sub}
            new_state["efb"] = cstate["efb"]

        new_params, new_opt, metrics = optim.adam_update(
            grads, state["opt"], params, opt_cfg)
        metrics["loss"] = loss
        metrics["psnr"] = -10.0 * jnp.log10(jnp.maximum(loss, 1e-12))
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        return new_state, metrics

    return step_fn


def init_train_state(params, compression: Optional[str] = None,
                     compress_keys: Tuple[str, ...] = ("grid",)) -> Dict:
    """Fresh engine state for :func:`make_scanned_step` tasks."""
    state = {"params": params, "opt": optim.adam_init(params)}
    if compression is not None:
        state["efb"] = {k: jnp.zeros_like(params[k]) for k in compress_keys}
    return state


def _stack_shardings(batch_shardings):
    """Per-step batch shardings -> shardings of a (chunk, ...) stack."""
    if batch_shardings is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(s.mesh, P(*((None,) + tuple(s.spec)))),
        batch_shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))


class TrainEngine:
    """Chunked-scan training loop (module docstring has the contract).

    ``step_fn(state, step, batch) -> (state, metrics)`` must be pure and
    scannable (metrics: dict of scalars). Exactly one of

      * ``device_batch_fn(step, state) -> batch`` — traced into the
        chunk; the fold-in RNG contract lives in the adapter, which reads
        its data key from the state (so the compiled chunk holds no
        seed), or
      * ``host_batch_fn(step) -> batch`` — host-side (numpy) per-step
        batches, stacked per chunk and prefetched,

    must be provided. ``state_shardings``/``batch_shardings`` pin the
    sharded LM layout; leave None for single-device field training.

    Donation is a lint-checked contract (DESIGN.md §9): RJ203 lowers a
    tiny chunk and asserts ``tf.aliasing_output`` appears iff
    ``cfg.donate``, and RA106 flags any caller that reads a state it
    passed to a chunk without rebinding (``state, out = chunk(state,
    ...)`` is the blessed shape; ``run()``'s ``device_get`` is the one
    allowed sync point per chunk).
    """

    def __init__(self, cfg: EngineConfig, step_fn: Callable, *,
                 device_batch_fn: Optional[Callable] = None,
                 host_batch_fn: Optional[Callable] = None,
                 state_shardings=None, batch_shardings=None,
                 monitor: Optional[HeartbeatMonitor] = None,
                 detector: Optional[StragglerDetector] = None,
                 policy: Optional[FailurePolicy] = None,
                 on_event: Optional[Callable] = None,
                 on_chunk_end: Optional[Callable] = None,
                 metrics_registry: Optional[obs_metrics.Registry] = None):
        if (device_batch_fn is None) == (host_batch_fn is None):
            raise ValueError(
                "exactly one of device_batch_fn / host_batch_fn required")
        self.cfg = cfg
        self.step_fn = step_fn
        self.device_batch_fn = device_batch_fn
        self.host_batch_fn = host_batch_fn
        self.state_shardings = state_shardings
        self.batch_shardings = batch_shardings
        self._stacked = _stack_shardings(batch_shardings)
        # per-engine registry; the default straggler detector stores its
        # per-host step-time histograms IN it (health.step_s.<host>), so
        # straggler medians and the metrics snapshot read the same data
        self.obs = metrics_registry or obs_metrics.Registry()
        self.monitor = monitor or HeartbeatMonitor(
            timeout_s=cfg.heartbeat_timeout_s)
        self.detector = detector or StragglerDetector(registry=self.obs)
        self.policy = policy or FailurePolicy(self.monitor, self.detector,
                                              registry=self.obs)
        self.on_event = on_event if on_event is not None else (
            lambda ev: _LOG.warning("failure_event", kind=ev.kind,
                                    hosts=list(ev.hosts), step=ev.step,
                                    hint="see runtime/elastic.py"))
        # Fires once per completed chunk with (end_step, state) — the
        # natural cadence for auxiliary structures refreshed from the
        # live params (e.g. core.occupancy EMA updates, DESIGN.md §7)
        # without putting them in the scanned/donated training state.
        self.on_chunk_end = on_chunk_end
        self.host = cfg.host or f"host{jax.process_index()}"
        self.events: List = []
        self._chunk_cache: Dict[int, Callable] = {}
        self._state_shapes = None            # of the state run() last took

    # ------------------------------------------------------------- chunks
    def _chunk_fn(self, n: int) -> Callable:
        """Jitted scan over ``n`` steps (cached per distinct length)."""
        fn = self._chunk_cache.get(n)
        if fn is not None:
            return fn
        step_fn = self.step_fn
        donate = (0,) if self.cfg.donate else ()
        if self.device_batch_fn is not None:
            batch_fn = self.device_batch_fn

            def chunk(state, start):
                def body(carry, i):
                    step = start + i
                    return step_fn(carry, step, batch_fn(step, carry))
                return jax.lax.scan(
                    body, state, jnp.arange(n, dtype=jnp.int32))

            fn = jax.jit(chunk, donate_argnums=donate)
        else:
            def chunk(state, start, batches):
                def body(carry, ib):
                    i, batch = ib
                    return step_fn(carry, start + i, batch)
                return jax.lax.scan(
                    body, state,
                    (jnp.arange(n, dtype=jnp.int32), batches))

            kwargs = {}
            if self.state_shardings is not None:
                kwargs = dict(
                    in_shardings=(self.state_shardings, None, self._stacked),
                    out_shardings=(self.state_shardings, None))
            fn = jax.jit(chunk, donate_argnums=donate, **kwargs)
        self._chunk_cache[n] = fn
        return fn

    def compiled_chunk(self, n: int):
        """The compiled program of an ``n``-step chunk of a device-batch
        engine, lowered for the shapes of the state ``run()`` last took."""
        if self._state_shapes is None:
            raise ValueError("run() the engine first: the chunk is lowered "
                             "for the state it takes")
        return self._chunk_fn(n).lower(self._state_shapes,
                                       jnp.int32(0)).compile()

    def _host_chunk_iter(self, plan):
        """Prefetched iterator of device-resident stacked chunk batches."""
        from repro.data.tokens import Prefetcher

        def chunks():
            for (s0, n) in plan:
                per_step = [self.host_batch_fn(s0 + i) for i in range(n)]
                yield {k: np.stack([b[k] for b in per_step])
                       for k in per_step[0]}

        def to_device(stacked):
            if self._stacked is not None:
                return jax.device_put(stacked, self._stacked)
            return jax.tree.map(jnp.asarray, stacked)

        return Prefetcher(chunks(), depth=self.cfg.prefetch,
                          to_device=to_device)

    # --------------------------------------------------------------- run
    def run(self, state, *, on_metrics: Optional[Callable] = None
            ) -> Tuple[Any, List[Dict[str, float]]]:
        """Run (or resume) the loop from ``state``.

        Returns ``(final_state, history)`` where history holds one
        ``{'step': i, 'loss': ..., ...}`` dict per step *executed in this
        invocation* (a resumed run reports only the steps it ran).
        ``on_metrics(step, metrics_row, state)`` fires per step, after
        the enclosing chunk completes — ``state`` is the chunk-end state,
        the freshest one that exists on the host side of a scanned chunk.
        """
        cfg = self.cfg
        ckpt = None
        start = 0
        if cfg.ckpt_dir is not None:
            ckpt = store.AsyncCheckpointer(cfg.ckpt_dir, keep=cfg.ckpt_keep)
            last = store.latest_step(cfg.ckpt_dir)
            if last is not None:
                sds = jax.eval_shape(lambda s: s, state)
                state = store.restore(cfg.ckpt_dir, sds, step=last,
                                      shardings=self.state_shardings)
                start = last + 1
                _LOG.info("resumed", step=last, ckpt_dir=str(cfg.ckpt_dir))

        self._state_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        plan = chunk_plan(start, cfg.steps, cfg.chunk_steps)
        prefetch = (self._host_chunk_iter(plan)
                    if self.host_batch_fn is not None else None)
        history: List[Dict[str, float]] = []
        last_saved = start - 1
        try:
            step_hist = self.obs.histogram("train.step_s")
            steps_ctr = self.obs.counter("train.steps")
            hists = {k: self.obs.histogram(f"train.{k}_s")
                     for k in ("dispatch", "sync", "host")}
            for (s0, n) in plan:
                with TRACER.span("train.chunk", cat="train", start=s0,
                                 n_steps=n, host=self.host):
                    chunk = self._chunk_fn(n)
                    with TRACER.span("train.dispatch", cat="train",
                                     timed=True) as dispatch:
                        if prefetch is not None:
                            state, stacked = chunk(state, jnp.int32(s0),
                                                   next(prefetch))
                        else:
                            state, stacked = chunk(state, jnp.int32(s0))
                    # the device_get is the chunk's natural sync point, so
                    # dispatch through sync covers device completion
                    # without any block_until_ready of the tracer's own
                    with TRACER.span("train.sync", cat="train",
                                     timed=True) as sync:
                        # repro: allow[host-sync] the chunk's one designated sync point
                        stacked = jax.device_get(stacked)
                    hists["dispatch"].record(dispatch.seconds)
                    hists["sync"].record(sync.seconds)
                    dt = sync.end - dispatch.start
                    step_hist.record(dt / n)
                    steps_ctr.inc(n)
                    with TRACER.span("train.host", cat="train",
                                     timed=True) as host:
                        self.monitor.beat(self.host)
                        self.detector.record(self.host, dt / n)
                        _LOG.debug("chunk", start=s0, n_steps=n,
                                   step_ms=round(dt / n * 1e3, 3))
                        for i in range(n):
                            row = {k: float(v[i])
                                   for k, v in stacked.items()}
                            row["step"] = s0 + i
                            row["dt"] = dt / n
                            history.append(row)
                            if on_metrics is not None:
                                on_metrics(s0 + i, row, state)

                        end = s0 + n - 1
                        if ckpt is not None and (
                                end == cfg.steps - 1
                                or end - last_saved >= cfg.ckpt_every):
                            ckpt.save(state, end)   # snapshot before donation
                            last_saved = end
                        if self.on_chunk_end is not None:
                            self.on_chunk_end(end, state)
                        ev = self.policy.poll(end)
                        if ev is not None:
                            self.events.append(ev)
                            self.on_event(ev)
                    hists["host"].record(host.seconds)
        finally:
            if prefetch is not None:
                prefetch.close()
            if ckpt is not None:
                ckpt.wait()
        return state, history
