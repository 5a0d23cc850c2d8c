"""The trace reduction: busy union, idle share, the phase join from HLO
op_name metadata and the breakdown, on hand-made traces."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

HLO = """HloModule jit_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(fn)/encode/jit(_take)/mul"}
}

ENTRY %main.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %gather_fusion = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %dot.1 = f32[8]{0} dot(%gather_fusion, %p), metadata={op_name="jit(fn)/mlp/while/body/closed_call/dot_general"}
  %scatter.2 = f32[8]{0} scatter(%p), metadata={op_name="jit(chunk)/while/body/transpose(jvp(encode))/scatter-add"}
  %while.1 = f32[8]{0} while(%p), condition=%cond, body=%body, metadata={op_name="jit(fn)/mlp/while"}
  ROOT %copy.4 = f32[8]{0} copy(%dot.1)
}
"""


def test_phase_of_op_name():
    assert tr.phase_of_op_name("jit(fn)/encode/jit(_take)/gather") == "encode"
    assert tr.phase_of_op_name(
        "jit(chunk)/while/body/transpose(jvp(encode))/mul") == "encode"
    assert tr.phase_of_op_name("jit(fn)/raymarch/mlp_like/add") == "raymarch"
    assert tr.phase_of_op_name("jit(fn)/mlp") is None       # a primitive
    assert tr.phase_of_op_name("jit(fn)/while/body/add") is None


def test_instruction_name_of_a_tpu_op_event():
    assert tr.instruction_name(
        "%fusion.12 = f32[131072,2]{0,1} fusion(f32[4] %p), kind=kLoop"
    ) == "fusion.12"
    assert tr.instruction_name("copy-start.1") == "copy-start.1"


def test_gap_label_prefers_the_innermost_host_span():
    spans = [["bench.window", 0, 100], ["$loop.py:1 run", 0, 100],
             ["$loop.py:2 device_get", 10, 30]]
    assert tr._label_gap(spans, 15, 35) == "$loop.py:2 device_get"
    assert tr._label_gap(spans, 50, 60) == "$loop.py:1 run"
    assert tr._label_gap(spans[:1], 50, 60) == "none"


def test_phase_map_joins_fusions_to_their_computation():
    pmap = tr.phase_map([HLO])
    assert pmap[("jit_fn", "gather_fusion")] == "encode"
    assert pmap[("jit_fn", "dot.1")] == "mlp"
    assert pmap[("jit_fn", "scatter.2")] == "encode"
    assert pmap[("jit_fn", "copy.4")] == "other"
    lookup = tr.phase_lookup(pmap)
    assert lookup("jit_fn(7)", "dot.1") == "mlp"
    assert lookup(None, "gather_fusion") == "encode"
    assert lookup("jit_fn", "unknown.9") == "other"


def _trace():
    # window 1000..2000 ns; device 0 busy 1000-1200, then a while op
    # 1200-1500 holding a dot 1250-1450, then 1900-2100 (100 ns inside);
    # device 1 busy 1100-1400
    return {
        "devices": {
            "/device:TPU:0": [["gather_fusion", "jit_fn", 1000, 200],
                              ["while.1", "jit_fn", 1200, 300],
                              ["dot.1", "jit_fn", 1250, 200],
                              ["gather_fusion", "jit_fn", 1900, 200],
                              ["dot.1", "jit_fn", 100, 50]],
            "/device:TPU:1": [["dot.1", "jit_fn", 1100, 300]],
        },
        "spans": [["bench.window", 1000, 1000],
                  ["bench.submit", 1450, 400],
                  ["bench.prep", 1850, 40]],
    }


def test_busy_union_idle_and_phases():
    r = tr.reduce_trace(_trace(), tr.phase_map([HLO]))
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["devices"] == 2
    # device 0: 500 + 100 ns; device 1: 300 ns; mean 450 ns
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    # self times: encode 200 + 100 ns on device 0; mlp the while's own
    # 100 ns and its dot's 200 on device 0, 300 on device 1; two devices
    assert r["phase_s"]["encode"] == pytest.approx(150e-9)
    assert r["phase_s"]["mlp"] == pytest.approx(300e-9)
    assert r["n_ops"] == 5


def test_breakdown_ops_and_gaps():
    r = tr.reduce_trace(_trace(), tr.phase_map([HLO]))
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["dot.1 [mlp]"] == pytest.approx(250e-9)
    assert ops["gather_fusion [encode]"] == pytest.approx(150e-9)
    assert ops["while.1 [mlp]"] == pytest.approx(50e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # device 1 idles 1400-2000 (600 ns, the submit span covers most of
    # it); device 0 idles 1500-1900 (400 ns, inside the submit span)
    assert gaps[0] == ["bench.submit", pytest.approx(600e-9)]
    assert gaps[1] == ["bench.submit", pytest.approx(400e-9)]
    assert len(gaps) <= tr.TOP


def test_window_span_is_required():
    t = _trace()
    t["spans"] = [s for s in t["spans"] if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        tr.reduce_trace(t, {})


# ----------------------------------------------- a trace from the chip
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_chip_trace_of_served_tiles():
    """180 ms of the nvr_hash tile stream on one v5e chip (the device's
    op events as ``read_xplane`` gives them, the benchmark's host spans)
    and the tile program's HLO, cut to each instruction's name, call and
    op_name: the hashed gathers of the encode fill the device."""
    import json
    trace = json.loads((FIXTURES / "tile_trace.json").read_text())
    pmap = tr.phase_map([(FIXTURES / "tile_hlo.txt").read_text()])
    r = tr.reduce_trace(trace, pmap)
    assert r["window_s"] == pytest.approx(0.18)
    assert r["busy_s"] == pytest.approx(0.179952224)
    assert r["idle_share"] == pytest.approx(2.6542222e-4, rel=1e-4)
    assert r["n_ops"] == 3792          # of 3800 events, 8 take no time
    phases = r["phase_s"]
    assert set(phases) == {"encode", "mlp", "raymarch", "composite",
                           "other"}
    assert phases["encode"] == pytest.approx(0.178645679)
    assert phases["encode"] / sum(phases.values()) > 0.99
    assert sum(phases.values()) == pytest.approx(r["busy_s"], rel=1e-3)
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "fusion.238 [encode]"
    assert top[1] == pytest.approx(0.003090205)
    assert all(g[0] == "bench.submit"
               for g in r["breakdown"]["idle_gaps"][:2])


def test_readers_on_the_chip_trace():
    """Every per-layer reader of the tile cell gives a number within its
    range from the chip trace above (its 180 ms held about 1.5 tiles; one
    is counted) and gives nothing where the trace holds no device."""
    import json
    from bench import harness, peaks
    trace = json.loads((FIXTURES / "tile_trace.json").read_text())
    pmap = tr.phase_map([(FIXTURES / "tile_hlo.txt").read_text()])
    cell = harness.load_cell("nvr_hash.tiles")
    counts = {"requests": 2, "held_requests": 1, "held_pixels": 4096,
              "host_s": 0.002, "submits": 2, "tile_pixels": 4096,
              "n_samples": 32, "window_s": 0.18}
    read = harness.ReadCtx(cell, counts, tr.reduce_trace(trace, pmap),
                           peaks.peaks_for("TPU v5 lite"), 1)
    empty = harness.ReadCtx(cell, counts, None,
                            peaks.peaks_for("TPU v5 lite"), 1)
    for m in cell.per_layer:
        fn = harness.reader(m["name"])
        v = fn(read)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, (m["name"], v)
        if m["source"] == "device_trace":
            assert fn(empty) is None, m["name"]
