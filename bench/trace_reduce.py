"""From a profiler trace to device busy time, phases and a breakdown.

Three steps, each kept apart so that a small recorded trace can check the
last two without a chip:

1. ``read_xplane``: the profiler's ``.xplane.pb`` -> a plain dict of device
   op events per device (``XLA Ops`` lines of the ``/device:TPU:n`` planes:
   each event is named by its HLO instruction's text, and lies inside an
   event of the ``XLA Modules`` line that names its program), the
   benchmark's own host spans (``bench.*`` trace annotations) and the
   profiler's Python function events (``$file:line function``).
2. ``phase_map``: the compiled program's HLO text -> {instruction: phase},
   from the ``op_name`` metadata that ``jax.named_scope`` writes (a fusion
   without its own scope takes its called computation's).
3. ``reduce_trace``: events clipped to the ``bench.window`` span -> busy
   seconds per device (the union of op intervals), seconds per phase and
   per op (self time: an op such as ``while`` holds its body's ops, which
   are subtracted), the ops that took most time, and the longest idle gaps,
   each labelled by the innermost host span or Python function open
   through most of it.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional, Tuple

PHASES = ("encode", "mlp", "raymarch", "composite", "compact")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PY_PREFIX = "$"           # the profiler's Python function events
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


# ------------------------------------------------------------ 1. xplane
def read_xplane(path: str) -> dict:
    """Device op events and ``bench.*`` host spans of one profile, as
    ``{"devices": {plane: [[name, module, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((float(e.start_ns), float(e.duration_ns), e.name)
                             for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            rows = devices.setdefault(plane.name, [])
            for ev in lines.get(OPS_LINE, []):
                start = float(ev.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                module = (modules[i][2] if i >= 0
                          and start <= modules[i][0] + modules[i][1]
                          else None)
                rows.append([instruction_name(ev.name), module, start,
                             float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((SPAN_PREFIX, PY_PREFIX)):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    return {"devices": devices, "spans": spans}


def describe_xplane(path: str, n_events: int = 3) -> list:
    """Every plane and line of a profile with its event count and its
    first events' names and stats: what to look at by hand before
    trusting ``read_xplane`` on a new platform."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events),
                          "first": [{"name": e.name, "start_ns": e.start_ns,
                                     "duration_ns": e.duration_ns,
                                     "stats": {k: str(v) for k, v in
                                               dict(e.stats).items()}}
                                    for e in events[:n_events]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


# ---------------------------------------------------------- 2. phase map
def phase_of_op_name(op_name: str) -> Optional[str]:
    """The innermost phase scope of an ``op_name`` path; the last path
    element is the primitive and is not a scope."""
    for scope in reversed(op_name.split("/")[:-1]):
        words = set(re.findall(r"[A-Za-z_]+", scope))
        for phase in PHASES:
            if phase in words:
                return phase
    return None


def _module_key(name: Optional[str]) -> Optional[str]:
    """``jit_fn(12)`` and ``jit_fn`` name one module."""
    return None if name is None else re.sub(r"\(\d+\)$", "", name)


def phase_map(hlo_texts: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """{(module, instruction): phase} over the compiled programs."""
    out: Dict[Tuple[str, str], str] = {}
    for text in hlo_texts:
        module = None
        comp = None
        own: Dict[str, Optional[str]] = {}
        calls: Dict[str, str] = {}
        comp_phases: Dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)
        for line in text.splitlines():
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
            m = _COMPUTATION.match(line)
            if m and " = " not in line:
                comp = m.group(1)
                continue
            if " = " not in line:
                continue
            name = line.split(" = ", 1)[0].strip()
            name = name.removeprefix("ROOT ").strip().lstrip("%")
            m = _OP_NAME.search(line)
            phase = phase_of_op_name(m.group(1)) if m else None
            own[name] = phase
            if phase is not None and comp is not None:
                comp_phases[comp][phase] += 1
            m = _CALLS.search(line)
            if m:
                calls[name] = m.group(1)
        for name, phase in own.items():
            if phase is None and name in calls:
                votes = comp_phases.get(calls[name])
                if votes:
                    phase = votes.most_common(1)[0][0]
            out[(module, name)] = phase or "other"
    return out


def phase_lookup(pmap: Dict[Tuple[str, str], str]):
    """``lookup(module, name) -> phase``; an op whose module is unknown
    takes the phase of the one instruction of that name, if the name is
    unique across the programs."""
    by_name: Dict[str, set] = collections.defaultdict(set)
    for (_, n), p in pmap.items():
        by_name[n].add(p)

    def lookup(module: Optional[str], name: str) -> str:
        key = (_module_key(module), name)
        if key in pmap:
            return pmap[key]
        found = by_name.get(name, set())
        return next(iter(found)) if len(found) == 1 else "other"
    return lookup


# ------------------------------------------------------------ 3. reduce
def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _self_times(intervals: List[Tuple[float, float]]) -> List[float]:
    """Each interval's length less that of the intervals nested directly
    inside it (ops inside a ``while`` or ``conditional`` op)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [e - s for s, e in intervals]
    stack: List[int] = []
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, intervals[stack[-1]][1]) - s
        stack.append(i)
    return own


def window_of(trace: dict) -> Tuple[float, float]:
    spans = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {WINDOW_SPAN} spans in the trace")
    _, start, dur = spans[0]
    return start, start + dur


def _label_gap(spans: list, s: float, e: float) -> str:
    """The host span that overlaps the gap most; of equals, the shortest
    (the innermost)."""
    best, best_key = "none", (0.0, 0.0)
    for name, start, dur in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, start + dur) - max(s, start)
        key = (overlap, -dur)
        if overlap > 0 and key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(trace: dict, pmap: Dict[Tuple[str, str], str]) -> dict:
    """Busy, phase and breakdown numbers of the traced window, each device
    clipped to it; per-device numbers are averaged over the devices."""
    w0, w1 = window_of(trace)
    window_s = (w1 - w0) * 1e-9
    devices = sorted(trace["devices"])
    if not devices:
        raise ValueError("the trace holds no device")
    busy: List[float] = []
    phase_s: Dict[str, float] = collections.Counter()
    op_s: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[float, float, float]] = []
    n_ops = 0
    lookup = phase_lookup(pmap)
    for dev in devices:
        clipped = []
        for name, module, start, dur in trace["devices"][dev]:
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                clipped.append((s, e, name, module))
        n_ops += len(clipped)
        for (s, e, name, module), self_ns in zip(clipped, _self_times(
                [(s, e) for s, e, _, _ in clipped])):
            phase = lookup(module, name)
            phase_s[phase] += self_ns * 1e-9
            op_s[f"{name} [{phase}]"] += self_ns * 1e-9
        merged = _union([(s, e) for s, e, _, _ in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    n = len(devices)
    gaps.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "idle_share": 1.0 - sum(busy) / n / window_s,
        "devices": n,
        "n_ops": n_ops,
        "phase_s": {k: v / n for k, v in phase_s.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in
                           sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label_gap(trace["spans"], s, e), d * 1e-9]
                          for d, s, e in gaps[:TOP]],
        },
    }
