"""Observability layer (repro.obs, DESIGN.md §8): metrics registry,
span tracer + Chrome-trace export + profiler sink, structured logger,
and the serve engine / train engine / health integration.

Accuracy bar: histogram percentiles match the exact order statistic
within one log-bucket width (a ``bucket_growth`` factor, ~10%).
Overhead bar: a disabled tracer hands out one shared null span (or, for
a timed span, bare stamps), records nothing and enters no profiler
annotation."""
import glob
import io
import json
import math

import numpy as np
import pytest

from repro.obs import export, log as obs_log, metrics as obs_metrics
from repro.obs.trace import _NULL_SPAN, TRACER, Stamps, Tracer, time_fn


# ---------------------------------------------------------------- histogram
def _exact_pct(samples, p):
    s = sorted(samples)
    return s[min(len(s) - 1, int(round(p / 100.0 * (len(s) - 1))))]


@pytest.mark.parametrize("p", [50, 90, 99])
def test_histogram_percentile_within_one_bucket(p):
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(-4.0, 1.5, size=997))   # ~ latencies in s
    h = obs_metrics.Histogram("t")
    for x in samples:
        h.record(float(x))
    exact = _exact_pct(samples, p)
    est = h.percentile(p)
    g = h.bucket_growth
    assert exact / g <= est <= exact * g, (p, est, exact, g)


def test_histogram_snapshot_and_empty():
    h = obs_metrics.Histogram("t")
    assert math.isnan(h.percentile(50))
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p50"] == 0.0
    h.record(0.5)
    h.record(2.0)
    snap = h.snapshot()
    assert snap["count"] == 2 and snap["sum"] == pytest.approx(2.5)
    assert snap["min"] == 0.5 and snap["max"] == 2.0


def test_histogram_window_rotation_forgets_old_samples():
    h = obs_metrics.Histogram("t", window=8)
    for _ in range(16):
        h.record(10.0)          # old regime
    for _ in range(16):
        h.record(0.1)           # new regime: >= 2 full rotations
    assert h.percentile(50) == pytest.approx(0.1, rel=0.15)
    # lifetime aggregates are NOT windowed
    assert h.count == 32 and h.max == 10.0


def test_registry_get_or_create_and_snapshot_schema():
    reg = obs_metrics.Registry()
    reg.counter("serve.requests").inc(3)
    reg.gauge("health.silent_hosts").set(1)
    reg.histogram("serve.latency_s").record(0.01)
    assert reg.counter("serve.requests") is reg.counter("serve.requests")
    snap = reg.snapshot()
    export.validate_snapshot(snap)               # checked-in schema
    assert snap["counters"]["serve.requests"] == 3
    assert snap["gauges"]["health.silent_hosts"] == 1
    assert snap["histograms"]["serve.latency_s"]["count"] == 1
    json.loads(reg.to_json())


# ------------------------------------------------------------------- tracer
def test_disabled_tracer_hands_out_shared_null_span():
    tr = Tracer()
    assert not tr.enabled
    assert tr.span("a") is tr.span("b") is _NULL_SPAN
    with tr.span("a") as sp:
        assert sp is _NULL_SPAN
    with tr.span("a", timed=True) as sp:
        pass
    assert type(sp) is Stamps and sp.seconds == sp.end - sp.start >= 0
    tr.add_event("x", 0.0, 1.0)
    assert tr.events() == []
    with pytest.raises(ValueError):
        tr.enable(buffer=False, profile=False)


def test_span_nesting_depth_parent_and_chrome_schema(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("outer", cat="host"):
        with tr.span("inner", cat="phase", bucket=0):
            pass
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["args"]["depth"] == 1
    assert by_name["inner"]["args"]["parent"] == "outer"
    assert by_name["outer"]["args"]["depth"] == 0
    # inner closes first and nests inside outer's interval
    inner, outer = by_name["inner"], by_name["outer"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    path = tmp_path / "trace.json"
    obj = tr.export(path)
    export.validate_chrome_trace(obj)
    export.validate_chrome_trace(json.loads(path.read_text()))


def test_tracer_event_cap_counts_drops(tmp_path):
    tr = Tracer(max_events=2)
    tr.enable()
    for i in range(5):
        tr.add_event(f"e{i}", 0.0, 1.0)
    assert len(tr.events()) == 2 and tr.dropped == 3
    obj = tr.export(tmp_path / "t.json")
    assert obj["metadata"]["dropped_events"] == 3


def test_time_fn_is_the_shared_benchmark_timer():
    from benchmarks.common import time_fn as bench_time_fn
    assert bench_time_fn is time_fn
    t = time_fn(lambda x: x + 1, 1, warmup=1, iters=3)
    assert t >= 0.0


# ------------------------------------------------------------------- logger
def test_logger_emits_one_json_object_per_line():
    buf = io.StringIO()
    lg = obs_log.Logger("t", level="debug", stream=buf)
    lg.info("hello", a=1, b="x")
    lg.debug("deep", nested={"k": [1, 2]})
    lg.warning("warn")
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 3
    for line in lines:
        rec = json.loads(line)          # exactly one object per line
        assert rec["logger"] == "t" and "ts" in rec and "event" in rec
    assert json.loads(lines[0])["a"] == 1


def test_logger_level_filtering():
    buf = io.StringIO()
    lg = obs_log.Logger("t", level="warning", stream=buf)
    lg.debug("no")
    lg.info("no")
    lg.error("yes")
    recs = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
    assert [r["event"] for r in recs] == ["yes"]


def test_get_logger_is_cached():
    assert obs_log.get_logger("same") is obs_log.get_logger("same")


# ------------------------------------------------- serve engine integration
def _mixed_stream_engine(n_requests=12):
    """A served mixed stream, and the latency of each of its tickets."""
    import jax
    from repro.common.param import unbox
    from repro.core import fields, pipeline
    from repro.data import scenes
    from repro.serve import RenderEngine, RenderRequest
    from tests.conftest import small_field_config

    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    engine = RenderEngine(pipeline.RenderSettings(tile_pixels=64))
    for s in range(2):
        params, _ = unbox(fields.init_field(jax.random.PRNGKey(s), cfg))
        engine.add_scene(f"s{s}", cfg, params)
    engine.warmup()
    cams = [scenes.orbit_camera(8, 8, a) for a in (0.0, 2.1, 4.2)]
    rng = np.random.default_rng(0)
    tickets = []
    for r in range(n_requests):
        ids = rng.integers(0, 64, 48).astype(np.int32)
        tickets.append(engine.submit(RenderRequest(
            scene=f"s{r % 2}", camera=cams[r % 3], pixel_ids=ids)))
    engine.flush()
    return engine, [t.latency_s for t in tickets]


def test_engine_stats_compat_with_legacy_exact_percentiles():
    """Replayed mixed stream: the histogram-derived p50/p99 agree with
    the exact order statistics of the tickets' own latencies within one
    bucket width, and every legacy stats key survives next to the
    metrics snapshot."""
    engine, latencies = _mixed_stream_engine()
    st = engine.stats()
    exact50, exact99 = (_exact_pct(latencies, p) for p in (50, 99))
    g = engine._lat_hist.bucket_growth
    assert exact50 * 1e3 / g <= st["p50_ms"] <= exact50 * 1e3 * g
    assert exact99 * 1e3 / g <= st["p99_ms"] <= exact99 * 1e3 * g
    for key in ("n_requests", "p50_ms", "p99_ms", "mpix_per_s",
                "requests_per_s", "wall_s", "pixels", "warmup_s",
                "n_traces_total", "buckets"):
        assert key in st, key
    export.validate_snapshot(st["metrics"])
    m = st["metrics"]
    assert m["counters"]["serve.requests"] == st["n_requests"] == 12
    assert m["counters"]["serve.compiles"] == st["n_traces_total"] == 1
    assert m["histograms"]["serve.latency_s"]["count"] == 12
    # per-phase histograms for the one bucket, warmup excluded
    for phase in ("submit", "dispatch", "block", "slice"):
        assert m["histograms"][f"serve.{phase}_s.bucket0"]["count"] == 12


def test_engine_async_submit_records_no_trace_events_when_disabled():
    assert not TRACER.enabled      # process default
    n0 = len(TRACER.events())
    engine, _ = _mixed_stream_engine()
    assert len(TRACER.events()) == n0
    assert engine.stats()["n_requests"] == 12


def test_disabled_engines_enter_no_profiler_annotation(monkeypatch):
    """Disabled, the serve and train spans reach no sink: a profiler
    annotation would fail this test, and nothing is buffered."""
    import jax

    def refuse(*a, **k):
        raise AssertionError("a disabled span entered the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not TRACER.enabled
    n0 = len(TRACER.events())
    _mixed_stream_engine(4)
    _train_engine(2)
    assert len(TRACER.events()) == n0


# ----------------------------------------------- spans of the two engines
SERVE_PHASES = ("submit", "dispatch", "block", "slice")
TRAIN_SPANS = ("train.chunk", "train.dispatch", "train.sync", "train.host")


def _train_engine(steps):
    """A field trainer's engine, run for ``steps`` one-step chunks."""
    import jax
    from repro.common.param import unbox
    from repro.core import fields, train as train_mod
    from repro.train import loop, optim
    from tests.conftest import small_field_config

    cfg = small_field_config("gia", "hash", log2_T=8, n_levels=2)
    params, _ = unbox(fields.init_field(jax.random.PRNGKey(0), cfg))
    step = loop.make_scanned_step(
        lambda p, b: train_mod.field_loss(p, cfg, b), optim.AdamConfig())
    engine = loop.TrainEngine(
        loop.EngineConfig(steps=steps, chunk_steps=1), step,
        device_batch_fn=lambda i, state: train_mod.make_batch(
            cfg, jax.random.fold_in(jax.random.PRNGKey(1), i), 32))
    engine.run(loop.init_train_state(params))
    return engine


@pytest.fixture
def tracer_sinks():
    """Turns TRACER's sinks on for one test and restores the default."""
    TRACER.clear()
    yield TRACER.enable
    TRACER.disable()
    TRACER.clear()


def test_serve_spans_share_a_request_id_and_feed_the_histograms(
        tracer_sinks):
    tracer_sinks(buffer=True)
    engine, _ = _mixed_stream_engine(6)
    evs = [e for e in TRACER.events() if e["cat"] == "serve"]
    by_request = {}
    for e in evs:
        by_request.setdefault(e["args"]["request"], []).append(e["name"])
    # warmup requests (one per bucket) reach no sink; each of the six
    # served requests has its four phases under one id
    assert len(by_request) == 6
    assert all(sorted(v) == sorted(f"serve.{p}" for p in SERVE_PHASES)
               for v in by_request.values())
    # every histogram sample is its span's duration
    hists = engine.stats()["metrics"]["histograms"]
    for phase in SERVE_PHASES:
        durs = [e["dur"] * 1e-6 for e in evs if e["name"] == f"serve.{phase}"]
        h = hists[f"serve.{phase}_s.bucket0"]
        assert h["count"] == len(durs) == 6
        assert h["sum"] == pytest.approx(sum(durs), rel=1e-9, abs=1e-12)
        assert h["min"] == pytest.approx(min(durs), rel=1e-9, abs=1e-12)
        assert h["max"] == pytest.approx(max(durs), rel=1e-9, abs=1e-12)


def test_train_spans_nest_in_the_chunk_and_feed_the_histograms(
        tracer_sinks):
    tracer_sinks(buffer=True)
    engine = _train_engine(3)
    evs = [e for e in TRACER.events() if e["cat"] == "train"]
    assert [e["name"] for e in evs] == [
        "train.dispatch", "train.sync", "train.host", "train.chunk"] * 3
    chunks = [e for e in evs if e["name"] == "train.chunk"]
    assert [c["args"]["start"] for c in chunks] == [0, 1, 2]
    assert all(c["args"]["n_steps"] == 1 and c["args"]["host"] == engine.host
               for c in chunks)
    for e in evs:
        if e["name"] != "train.chunk":
            assert e["args"]["parent"] == "train.chunk"
    hists = engine.obs.snapshot()["histograms"]
    for name in ("dispatch", "sync", "host"):
        durs = [e["dur"] * 1e-6 for e in evs if e["name"] == f"train.{name}"]
        h = hists[f"train.{name}_s"]
        assert h["count"] == 3
        assert h["sum"] == pytest.approx(sum(durs), rel=1e-9, abs=1e-12)
        assert h["min"] == pytest.approx(min(durs), rel=1e-9, abs=1e-12)
    # a step's time runs from its dispatch's start to its sync's end
    step = [s["ts"] + s["dur"] - d["ts"] for d, s in zip(evs[0::4],
                                                        evs[1::4])]
    assert hists["train.step_s"]["sum"] == pytest.approx(
        sum(step) * 1e-6, rel=1e-9)


def test_profile_mode_spans_reach_the_profiler(tmp_path, tracer_sinks):
    """In profile mode the engines' spans are TraceMe events of a running
    CPU profiler session, with their names and arguments."""
    import jax
    tracer_sinks(buffer=False, profile=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _mixed_stream_engine(3)
        _train_engine(2)
    finally:
        jax.profiler.stop_trace()
    assert TRACER.events() == []                  # the buffer stayed off
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, {k: str(v) for k, v in dict(e.stats).items()})
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("serve.", "train."))]
    serve = [(n, a) for n, a in spans if n.startswith("serve.")]
    assert len({a["request"] for _, a in serve}) == 3
    for req in {a["request"] for _, a in serve}:
        names = sorted(n for n, a in serve if a["request"] == req)
        assert names == sorted(f"serve.{p}" for p in SERVE_PHASES)
        assert all(a["bucket"] in ("0", "1") for n, a in serve
                   if a["request"] == req)
    train = [(n, a) for n, a in spans if n.startswith("train.")]
    assert sorted(n for n, _ in train) == sorted(TRAIN_SPANS * 2)
    assert sorted(a["start"] for n, a in train if n == "train.chunk") == [
        "0", "1"]


# ------------------------------------------------------- health integration
def test_detector_histograms_are_registry_entries():
    from repro.runtime.health import StragglerDetector
    reg = obs_metrics.Registry()
    det = StragglerDetector(window=8, registry=reg)
    for _ in range(6):
        det.record("h0", 1.0)
        det.record("h2", 1.0)
        det.record("h1", 5.0)
    snap = reg.snapshot()
    assert snap["histograms"]["health.step_s.h0"]["count"] == 6
    assert det.stragglers() == ["h1"]
    # same object, not a copy
    assert det._hist("h0") is reg.histogram("health.step_s.h0")
