"""Unified observability layer (DESIGN.md §8).

One subsystem, four pieces:

  * ``obs.trace``   — nested host-side spans sent to a Chrome-trace
    buffer and/or, in profile mode, to the running profiler session
    (``jax.profiler.TraceAnnotation``, on the device ops' clock) +
    ``annotate()`` (``jax.named_scope``) for phase names inside traced
    code; the process tracer is **disabled by default** and a disabled
    span is a shared null object (a timed one, bare stamps) — zero
    device syncs and no sink on the async serve path.
  * ``obs.metrics`` — process-global *and* embeddable registries of
    named counters, gauges, and fixed-bucket log histograms (p50/p99
    without unbounded sample lists), snapshot → JSON.
  * ``obs.export``  — Chrome-trace/Perfetto JSON (``chrome://tracing``)
    and a dependency-free JSON-schema-subset validator for the
    checked-in metrics-snapshot schema.
  * ``obs.log``     — leveled JSON-lines structured logging (one
    ``json.loads`` per emitted line), replacing ad-hoc ``print()``.

Phase taxonomy (shared by spans, named scopes, and metrics names):
``encode | mlp | raymarch | compact | composite | host``; inside
``encode`` one ``lvlNN_hash``/``lvlNN_dense`` scope per grid level.
Program spans: ``serve.{submit,dispatch,block,slice}`` and
``train.chunk`` holding ``train.{dispatch,sync,host}``.
"""
from repro.obs.log import Logger, get_logger, set_level
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               REGISTRY, get_registry)
from repro.obs.trace import (TRACER, Stamps, Tracer, annotate, get_tracer,
                             time_fn)
from repro.obs import export

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "get_registry", "Logger", "get_logger", "set_level",
    "TRACER", "Stamps", "Tracer", "annotate", "get_tracer", "time_fn",
    "export",
]
