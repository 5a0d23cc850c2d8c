"""The benchmark's general part: one cell, one run.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``:

* the configuration: the ``file`` its entry names (``bench/configs``), and
  its plain reference ``bench/reference/<app>.py``;
* the traffic mix: ``bench/traffic/<traffic>.json``, whose ``driver`` names
  the general generator that reads it (``bench/drivers/<driver>.py``);
* each per-layer metric: its reader ``bench/metrics/<name>.py``, a
  ``read(ctx)`` that returns the number or None where it finds nothing;
* the limits of the numbers compared with the reference:
  ``bench/limits/<cell>.json``.

A driver sets up the program, measures the window, checks what the timed
path produced against the reference and returns its numbers; this module
checks the device, keeps the compile clock and the trace, and assembles
the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import peaks as peaks_mod
from bench import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"
# JAX's persistent compilation cache: a fixed directory inside the checkout
# (the path is part of the cache's key), whatever the environment names
COMPILE_CACHE = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    pass


# ------------------------------------------------------------- the spec
def load_spec(path: Path = SPEC) -> dict:
    return json.loads(path.read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    limits: dict          # the limit of each number compared
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    spec = load_spec()
    w = _named(spec["workloads"], name, "workload")
    c = _named(spec["configs"], w["config"], "config")
    config = json.loads((ROOT / c["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name, w["chips"], config, traffic, limits, e2e, layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    return load_module(path, f"bench_metric_{metric}").read


def driver(traffic: dict):
    return importlib.import_module(f"bench.drivers.{traffic['driver']}")


def reference(config: dict):
    """The app's plain reference, ``bench/reference/<app>.py``: its
    ``init_weights`` and ``render``, and a trained app's ``batch``,
    ``loss`` and ``adam``."""
    return importlib.import_module(f"bench.reference.{config['app']}")


def program_seed(seed: int) -> int:
    """Seeds may exceed 32 bits; a run takes its seed modulo 2^31, which
    every path of the program accepts."""
    return seed % 2 ** 31


def base_key(seed: int):
    """The PRNG key of a run."""
    import jax
    return jax.random.PRNGKey(program_seed(seed))


def field_config(config: dict):
    """The program's FieldConfig for a configuration file; the file's
    numbers are used as they stand. The grid's L * F features feed the
    first MLP: ``mlp`` alone, or a ``density_mlp``, whose output goes on
    beside the direction's ``sh_degree``^2 spherical harmonics to
    ``mlp``."""
    from repro.core.encoding import GridConfig
    from repro.core.fields import FieldConfig
    from repro.core.mlp import MLPConfig
    g = GridConfig(**config["grid"])
    density = config.get("density_mlp")
    if density is None:
        density_mlp, mlp_in = None, g.out_dim
    else:
        density_mlp = MLPConfig(in_dim=g.out_dim, **density)
        mlp_in = config["sh_degree"] ** 2 + density["out_dim"]
    return FieldConfig(app=config["app"], grid=g, density_mlp=density_mlp,
                       mlp=MLPConfig(in_dim=mlp_in, **config["mlp"]),
                       name=config["name"])


# ---------------------------------------------------------------- clocks
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


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------- context
class Ctx:
    """What a driver gets: the cell, the seed and window, the devices, and
    the window's bookkeeping (compile count, spans, trace)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, devices: list, clock: CompileClock):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.devices = devices
        self.clock = clock
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.window_compiles = None
        self._mark = None
        self._window_ann = None
        self._trace_dir: Optional[str] = None
        self.control = False
        self.keep_trace: Optional[Path] = None
        self.marks: Dict[str, float] = {}
        self.window_stats = WindowStats()

    def mark(self, what: str):
        """Seconds from the process's start to the end of a set-up step."""
        self.marks[what] = time.perf_counter() - self.t_start

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def limits(self) -> dict:
        return self.cell.limits

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op untraced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def open_window(self) -> float:
        if self.trace:
            import jax
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._trace_dir)
            self._window_ann = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self._window_ann.__enter__()
        # set-up's objects go to the permanent generation: a collection in
        # the window scans only what the window itself allocates
        gc.collect()
        gc.freeze()
        self._mark = self.clock.mark()
        self.t_open = time.perf_counter()
        self.window_stats.open(self.t_open)
        return self.t_open

    def done(self, t: float):
        """A unit of the window's work (a request held, a step completed)
        ended at ``t``."""
        self.window_stats.done(t)

    def close_window(self, t_close: Optional[float] = None) -> float:
        self.t_close = t_close or time.perf_counter()
        self.window_stats.close()
        gc.unfreeze()
        if self._window_ann is not None:
            self._window_ann.__exit__(None, None, None)
            self._window_ann = None
        return self.t_close

    def end_window_work(self):
        """After the window's outstanding work is waited for: the compile
        count over the window and that work, and the trace stopped."""
        self.window_compiles = self.clock.since(self._mark)[0]
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    def read_trace(self, hlo_texts: List[str]) -> dict:
        import glob
        try:
            files = glob.glob(f"{self._trace_dir}/**/*.xplane.pb",
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"{len(files)} profiles written")
            raw = trace_reduce.read_xplane(files[0])
            if self.keep_trace is not None:
                self._keep(files[0], raw, hlo_texts)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        return trace_reduce.reduce_trace(raw,
                                         trace_reduce.phase_map(hlo_texts))

    def _keep(self, xplane: str, raw: dict, hlo_texts: List[str]):
        """The trace as read, its planes described, and the HLO."""
        d = self.keep_trace
        d.mkdir(parents=True, exist_ok=True)
        (d / "describe.json").write_text(
            json.dumps(trace_reduce.describe_xplane(xplane), indent=1))
        (d / "raw.json").write_text(json.dumps(raw))
        for i, text in enumerate(hlo_texts):
            (d / f"hlo{i}.txt").write_text(text)


class WindowStats:
    """What the host did in the window, to tell a stall's cause: the
    longest time between two units of work ending, Python's collections,
    the process's CPU time, involuntary context switches (another process
    held the core) and major page faults. Printed on standard error."""

    def __init__(self):
        self.t_open = None
        self.t_last = None
        self.gap = (0.0, 0.0)           # (longest gap, its end in the window)
        self.gc = [0, 0.0, 0.0]         # collections, seconds, longest
        self._gc_t0 = None
        self._usage = None
        self._cpu = None
        self.usage = None

    def open(self, t: float):
        self.t_open = self.t_last = t
        self._cpu = time.process_time()
        self._usage = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dt = time.perf_counter() - self._gc_t0
            self.gc[0] += 1
            self.gc[1] += dt
            self.gc[2] = max(self.gc[2], dt)

    def done(self, t: float):
        if t - self.t_last > self.gap[0]:
            self.gap = (t - self.t_last, t - self.t_open)
        self.t_last = t

    def close(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        u = resource.getrusage(resource.RUSAGE_SELF)
        self.usage = {
            "cpu_s": time.process_time() - self._cpu,
            "involuntary_switches": u.ru_nivcsw - self._usage.ru_nivcsw,
            "major_faults": u.ru_majflt - self._usage.ru_majflt}

    def describe(self) -> str:
        if self.usage is None:
            return "window: not opened"
        u = self.usage
        return (f"window: longest gap {self.gap[0]:.3f} s, ending at "
                f"{self.gap[1]:.3f} s; gc {self.gc[1]:.3f} s in {self.gc[0]} "
                f"collections (longest {self.gc[2]:.3f} s); cpu "
                f"{u['cpu_s']:.3f} s; involuntary switches "
                f"{u['involuntary_switches']}; major faults "
                f"{u['major_faults']}")


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    attempted: int
    failed: int
    values: Dict[str, float]          # end-to-end metrics by name
    checks: Dict[str, tuple]          # name -> (value, limit); value <= limit
    counts: Dict[str, float]          # what the readers count with
    memory_peak_bytes: Optional[int]
    reduced: Optional[dict] = None    # trace reduction (traced runs)


@dataclasses.dataclass
class ReadCtx:
    """What a per-layer reader gets."""
    cell: Cell
    counts: Dict[str, float]
    reduced: Optional[dict]
    peaks: dict
    chips: int


# ------------------------------------------------------------------ run
def check_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def use_compile_cache():
    """Keep every program in the checkout's cache, however quickly it
    compiled, so that a second run finds them all. No eviction: an entry
    that another writer left without its access time would otherwise make
    every write fail."""
    import jax
    COMPILE_CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of one cell, by its name in BENCHMARK.json."""
    return run_loaded(load_cell(workload), seed, seconds, trace, t_start)


def run_loaded(cell: Cell, seed: int, seconds: float, trace: bool,
               t_start: float, require_tpu: bool = True,
               control: bool = False, keep_trace: Optional[Path] = None
               ) -> dict:
    """One run of a loaded cell; returns the result line's object. Raises
    NoAccelerator before any work where the chips are missing.

    With ``control`` the control, the reference one precision step below
    the configuration's, stands in the program's place: the checks and
    ``correct`` are the control's, judged by the cell's own limits, and the
    program's numbers and the planted faults' readings go to
    ``result["counts"]``. The benchmark's own runs never set it."""
    import jax

    devices = check_devices(cell.chips, require_tpu)
    # the per-layer readers need the device's peaks: look them up before
    # any work, so that a device missing from the table fails at once
    peaks = peaks_mod.peaks_for(devices[0].device_kind) if trace else None
    if require_tpu:
        use_compile_cache()
    clock = CompileClock()
    ctx = Ctx(cell, seed, seconds, trace, t_start, devices, clock)
    ctx.control = control
    ctx.keep_trace = keep_trace
    ctx.mark("devices")
    # the program runs at the matmul precision the configuration states
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        out: Outcome = driver(cell.traffic).run(ctx)
    gc.collect()

    checks = dict(out.checks)
    checks["window_compiles"] = (float(ctx.window_compiles), 0.0)
    correct = (out.failed == 0 and all(
        _finite(v) and v <= lim for v, lim in checks.values()))
    device = device_label()
    device["memory_peak_bytes"] = out.memory_peak_bytes
    if trace:
        metrics = {}
        rctx = ReadCtx(cell, out.counts, out.reduced, peaks, cell.chips)
        for m in cell.per_layer:
            v = reader(m["name"])(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = out.reduced["busy_s"]
        device["window_s"] = out.reduced["window_s"]
    else:
        metrics = {m["name"]: {"value": out.values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = out.reduced["breakdown"]
    result["setup_marks"] = ctx.marks
    result["window_stats"] = ctx.window_stats.describe()
    if control:
        result["counts"] = out.counts
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def print_result(result: dict, stdout=None, stderr=None):
    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr
    print("set-up, seconds from the start: " + ", ".join(
        f"{k} {v:.3f}" for k, v in result["setup_marks"].items()),
        file=stderr)
    print(result["window_stats"], file=stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=stderr)
    stderr.flush()
    print(json.dumps(result), file=stdout, flush=True)
