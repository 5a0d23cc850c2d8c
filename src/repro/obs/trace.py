"""Host-side span tracer whose spans reach the profiler's clock.

Spans are nested host intervals (thread-local stack). Each span goes to
every sink that is on:

  * **buffer**: a bounded in-memory list of Chrome-trace "X" events
    (``obs/export.py``, viewable in ``chrome://tracing``/Perfetto);
  * **profile**: a ``jax.profiler.TraceAnnotation`` (a TraceMe) entered
    and left with the span, so a running profiler session records it on
    its host plane, on the same clock as the device ops. A TraceMe
    cannot be back-dated, so spans are live ``with`` blocks.

A span measures host time only: it never syncs the device, so tracing
cannot perturb XLA's async dispatch. Where a span wraps a wait (the serve
engine's ``serve.block``, the trainer's ``train.sync``), the wait is the
program's own.

The process tracer ``TRACER`` is **disabled by default**; a disabled
``span()`` returns a shared null object (no allocation, no sink). A
``timed=True`` span keeps its ``start``/``end`` stamps whether or not a
sink is on, so an engine's histograms read the very stamps its spans
carry (disabled, it is a two-stamp stopwatch and nothing more).

``annotate(name)`` is the in-trace counterpart: ``jax.named_scope`` so
XLA profiles and HLO ``op_name`` metadata carry the phase names
(taxonomy: encode|mlp|raymarch|compact|composite, and inside encode one
``lvlNN_hash``/``lvlNN_dense`` scope per grid level).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List


def annotate(name: str):
    """``jax.named_scope`` context manager — phase names inside traced
    code (kernel entry points, ``core/pipeline.py``), so XLA profiles
    and HLO op metadata carry the obs phase taxonomy."""
    import jax
    return jax.named_scope(name)


# repro: sync-boundary timing primitive — syncing IS its semantics
def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time (s) of a jitted callable — THE definition of
    warmup-exclusion timing semantics (``warmup`` synced calls excluded,
    median of ``iters`` synced calls reported). ``benchmarks/common``
    re-exports this; the serve engine's ``warmup()`` applies the same
    rule to its latency statistics."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


class _NullSpan:
    """Shared no-op span — what a disabled tracer hands out."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Stamps:
    """A span with no sink: the ``start``/``end`` stamps alone."""
    __slots__ = ("start", "end")

    def __enter__(self):
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _Span(Stamps):
    __slots__ = ("_tracer", "name", "cat", "args", "_depth", "_parent",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._annotation = None

    def __enter__(self):
        stack = self._tracer._stack()
        self._depth = len(stack)
        self._parent = stack[-1] if stack else ""
        stack.append(self.name)
        if self._tracer.profile:
            import jax
            self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                            **self.args)
            self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._stack().pop()
        if self._tracer.buffer:
            self._tracer.add_event(self.name, self.start, self.end,
                                   cat=self.cat, depth=self._depth,
                                   parent=self._parent, **self.args)
        return False


class Tracer:
    """Span factory, its sinks and the bounded event buffer (module
    docstring)."""

    def __init__(self, max_events: int = 200_000):
        self.buffer = False
        self.profile = False
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Dict] = []
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._local = threading.local()

    @property
    def enabled(self) -> bool:
        return self.buffer or self.profile

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # ------------------------------------------------------------ control
    def enable(self, buffer: bool = True, profile: bool = False):
        """Turn sinks on: ``buffer`` (Chrome-trace events) and/or
        ``profile`` (TraceAnnotations for a running profiler session)."""
        if not (buffer or profile):
            raise ValueError("enable() needs at least one sink")
        self.buffer = buffer
        self.profile = profile

    def disable(self):
        self.buffer = False
        self.profile = False

    def clear(self):
        with self._lock:
            self._events = []
            self.dropped = 0
            self._epoch = time.perf_counter()

    # ------------------------------------------------------------- record
    def span(self, name: str, cat: str = "host", timed: bool = False,
             **args):
        """Context manager for one nested span, sent to every sink that
        is on. Disabled: the shared null span, or with ``timed`` a bare
        pair of stamps (``start``, ``end``, ``seconds``)."""
        if not (self.buffer or self.profile):
            return Stamps() if timed else _NULL_SPAN
        return _Span(self, name, cat, args)

    def add_event(self, name: str, t0: float, t1: float,
                  cat: str = "host", **args):
        """Record a complete event from ``perf_counter`` stamps in the
        buffer sink (only there: the profiler takes live spans)."""
        if not self.buffer:
            return
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": (t0 - self._epoch) * 1e6,
                "dur": max(0.0, (t1 - t0) * 1e6),
                "pid": os.getpid(),
                "tid": threading.get_ident() % (1 << 31),
                "args": args,
            })

    # ------------------------------------------------------------- export
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def export(self, path) -> Dict:
        """Write Chrome-trace JSON; returns the trace object."""
        from repro.obs import export as export_mod
        return export_mod.write_chrome_trace(path, self.events(),
                                             dropped=self.dropped)


TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER
