"""What the program itself writes into a profiler trace, and what it tells.

With its tracer in profile mode (``repro.obs.trace``) the program's own
spans (``serve.*``, ``train.*``) are TraceMe events on the profile's host
plane, on the device ops' clock; and the grid encode holds one named scope
per level (``lvl07_hash``, ``lvl00_dense``: ``core/encoding.py``), which
the compiled HLO's ``op_name`` keeps, under ``transpose(...)`` in the
backward pass. This module reads both, beside ``trace_reduce``:

* ``read_program_spans``: the program's spans of one profile, with their
  arguments;
* ``scope_map`` / ``level_map``: {(module, instruction): level key} from
  the HLO, the way ``trace_reduce.phase_map`` joins phases (a fusion
  without its own scope takes its called computation's), for the ops of
  the ``encode`` phase;
* ``level_seconds``: device self time in the window per level key
  (``lvl07_hash/forward``, ``lvl07_hash/transpose``);
* ``idle_by_span``: device idle time in the window by the innermost
  program span open through it, or ``none``;
* ``encode_work``: the encode's operations and bytes for a subset of its
  levels, forward or backward, which sum over all levels to
  ``work.encode_flops`` / ``work.encode_bytes``;
* ``readings``: the per-layer numbers these give a traced run of a cell.

``bench/profile_cell.py`` runs a cell with the tracer in profile mode and
prints them.
"""
from __future__ import annotations

import collections
import heapq
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce, work

PROGRAM_PREFIXES = ("serve.", "train.")
FORWARD, TRANSPOSE = "forward", "transpose"
_LEVEL = re.compile(r"(?<![\w])lvl\d+_(?:hash|dense)(?![\w])")


# ------------------------------------------------------------ host plane
def read_program_spans(path: str) -> list:
    """``[[name, start_ns, dur_ns, {arg: value}], ...]``: the program's
    spans on the host planes of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIXES):
                    spans.append([ev.name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  {k: str(v) for k, v in
                                   dict(ev.stats).items()}])
    return spans


# ------------------------------------------------------------ level map
def level_of_op_name(op_name: str) -> Optional[str]:
    """``lvl07_hash/forward`` or ``lvl07_hash/transpose`` for an op inside
    a level scope; the last path element is the primitive. A transform
    wraps the outermost scope it was applied at, so a level scope may read
    ``transpose(jvp(lvl07_hash))`` where no phase scope holds it."""
    scopes = op_name.split("/")[:-1]
    for i in range(len(scopes) - 1, -1, -1):
        m = _LEVEL.search(scopes[i])
        if m:
            back = ("transpose(" in scopes[i][:m.start()]
                    or any("transpose(" in s for s in scopes[:i]))
            return f"{m.group(0)}/{TRANSPOSE if back else FORWARD}"
    return None


def scope_map(hlo_texts: Iterable[str],
              classify: Callable[[str], Optional[str]]
              ) -> Dict[Tuple[str, str], str]:
    """{(module, instruction): class} over the compiled programs, where
    ``classify`` names an ``op_name`` or returns None; an instruction
    without a class of its own that calls a computation takes the class
    most of that computation's instructions have, else ``other``. With
    ``trace_reduce.phase_of_op_name`` this is ``trace_reduce.phase_map``.
    """
    out: Dict[Tuple[str, str], str] = {}
    for text in hlo_texts:
        module = comp = None
        own: Dict[str, Optional[str]] = {}
        calls: Dict[str, str] = {}
        votes: Dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)
        for line in text.splitlines():
            m = trace_reduce._MODULE.match(line)
            if m:
                module = m.group(1)
                continue
            m = trace_reduce._COMPUTATION.match(line)
            if m and " = " not in line:
                comp = m.group(1)
                continue
            if " = " not in line:
                continue
            name = line.split(" = ", 1)[0].strip()
            name = name.removeprefix("ROOT ").strip().lstrip("%")
            m = trace_reduce._OP_NAME.search(line)
            cls = classify(m.group(1)) if m else None
            own[name] = cls
            if cls is not None and comp is not None:
                votes[comp][cls] += 1
            m = trace_reduce._CALLS.search(line)
            if m:
                calls[name] = m.group(1)
        for name, cls in own.items():
            if cls is None and name in calls:
                v = votes.get(calls[name])
                if v:
                    cls = v.most_common(1)[0][0]
            out[(module, name)] = cls or "other"
    return out


def level_map(hlo_texts: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """{(module, instruction): level key}, for the instructions that the
    phase map puts in ``encode`` (a fusion named by an MLP op that took in
    a level's ops stays out), ``other`` for the rest."""
    hlo_texts = list(hlo_texts)
    phases = trace_reduce.phase_map(hlo_texts)
    return {k: (v if phases.get(k) == "encode" else "other")
            for k, v in scope_map(hlo_texts, level_of_op_name).items()}


# ---------------------------------------------------------- device time
def _clipped(trace: dict, dev: str, w0: float, w1: float) -> list:
    out = []
    for name, module, start, dur in trace["devices"][dev]:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            out.append((s, e, name, module))
    return out


def level_seconds(trace: dict, lmap: Dict[Tuple[str, str], str]
                  ) -> Dict[str, float]:
    """Device self time (s) in the window per level key, averaged over
    the devices; ops outside every level scope are left out."""
    w0, w1 = trace_reduce.window_of(trace)
    lookup = trace_reduce.phase_lookup(lmap)
    out: Dict[str, float] = collections.Counter()
    devices = sorted(trace["devices"])
    for dev in devices:
        ops = _clipped(trace, dev, w0, w1)
        own = trace_reduce._self_times([(s, e) for s, e, _, _ in ops])
        for (_, _, name, module), self_ns in zip(ops, own):
            key = lookup(module, name)
            if key != "other":
                out[key] += self_ns * 1e-9
    return {k: v / len(devices) for k, v in sorted(out.items())}


def _innermost(spans: list, w0: float, w1: float):
    """[(start, end, name)]: the window cut where the innermost open
    program span changes (the shortest of those open), ``none`` where no
    span is open."""
    edges = []
    for i, (name, start, dur, *_) in enumerate(spans):
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            edges.append((s, 1, i))
            edges.append((e, 0, i))
    edges.sort()
    open_: list = []                  # heap of (dur, i); closed ones lazily
    closed = set()
    out = []
    t = w0
    for x, is_start, i in edges:
        while open_ and open_[0][1] in closed:
            heapq.heappop(open_)
        if x > t:
            out.append((t, x, spans[open_[0][1]][0] if open_ else "none"))
            t = x
        if is_start:
            heapq.heappush(open_, (spans[i][2], i))
        else:
            closed.add(i)
    if w1 > t:
        out.append((t, w1, "none"))
    return out


def idle_by_span(trace: dict, program_spans: list) -> Dict[str, float]:
    """Device idle seconds in the window, over every gap between the
    device's ops, by the innermost program span open through each part of
    it (``none`` where no span is open), averaged over the devices."""
    w0, w1 = trace_reduce.window_of(trace)
    pieces = _innermost(program_spans, w0, w1)
    out: Dict[str, float] = collections.Counter()
    devices = sorted(trace["devices"])
    for dev in devices:
        busy = trace_reduce._union(
            [(s, e) for s, e, _, _ in _clipped(trace, dev, w0, w1)])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        j = 0
        for s, e in zip(edges[0::2], edges[1::2]):
            while j < len(pieces) and pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                ps, pe, name = pieces[k]
                out[name] += (min(e, pe) - max(s, ps)) * 1e-9
                k += 1
    return {k: v / len(devices) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def span_seconds(program_spans: list, trace: dict, name: str) -> float:
    """Seconds of the spans named ``name`` inside the window."""
    w0, w1 = trace_reduce.window_of(trace)
    return sum(max(0.0, min(s + d, w1) - max(s, w0))
               for n, s, d, *_ in program_spans if n == name) * 1e-9


# ----------------------------------------------------------------- work
def encode_work(grid: dict, n_points: int, levels: Iterable[int],
                backward: bool = False, dtype_bytes: int = work.F32
                ) -> Tuple[float, float]:
    """(operations, bytes) of the encode's forward pass, or with
    ``backward`` of its backward pass alone (the features' gradient in,
    the table gradient out), over ``levels``. The points, read once for
    all levels, are shared out evenly. Summed over every level, forward
    is ``work.encode_*(backward=False)`` and forward plus backward is
    ``work.encode_*(backward=True)``."""
    d, f, n_levels = grid["dim"], grid["n_features"], grid["n_levels"]
    c = work.corners(grid)
    flops = nbytes = 0.0
    for level in levels:
        table = float(min(work.level_rows(grid, level), c * n_points)
                      ) * f * dtype_bytes
        feats = float(n_points) * f * work.F32
        if backward:
            flops += float(n_points) * c * 2 * f
            nbytes += feats + table
        else:
            flops += float(n_points) * (3 * d + c * ((d - 1) + 2 * f))
            nbytes += table + feats + float(n_points) * d * work.F32 / n_levels
    return flops, nbytes


def _kind_levels(grid: dict, hashed: bool) -> List[int]:
    return [l for l in range(grid["n_levels"])
            if work.level_is_hashed(grid, l) == hashed]


def _level_time(level_s: Dict[str, float], kind: Optional[str],
                pass_: str) -> float:
    """Device seconds of the level scopes of one kind (``hash``,
    ``dense``, or None for both) in one pass."""
    total = 0.0
    for key, s in level_s.items():
        scope, p = key.split("/")
        if p == pass_ and (kind is None or scope.endswith("_" + kind)):
            total += s
    return total


# ------------------------------------------------------------- readings
def readings(cell, counts: dict, peaks: dict, chips: int,
             level_s: Dict[str, float], program_spans: list,
             trace: dict) -> Dict[str, float]:
    """The per-layer numbers a traced run of ``cell`` gives, by name; a
    number whose scopes or spans the trace lacks is left out.

    * ``encode_hashed_roofline.serve`` / ``encode_dense_roofline.serve``
      (%): least time of one tile's forward encode over the hashed (dense)
      levels on one device, times the tiles held, over the device time of
      the ``lvl*_hash`` (``lvl*_dense``) scopes;
    * ``encode_transpose_roofline.train`` (%): least time of one step's
      backward encode, times the steps completed, over the device time of
      the level scopes under ``transpose(``;
    * ``host_ms_per_step.train`` (ms): seconds of ``train.dispatch`` and
      ``train.host`` in the window over the steps completed.
    """
    grid = cell.config["grid"]
    out = {}
    if counts.get("held_requests"):
        points = counts["tile_pixels"] * counts["n_samples"] // chips
        for kind, hashed in (("hashed", True), ("dense", False)):
            busy = _level_time(level_s, "hash" if hashed else "dense",
                               FORWARD)
            levels = _kind_levels(grid, hashed)
            if busy and levels:
                least, _ = work.least_time(
                    *encode_work(grid, points, levels), peaks)
                out[f"encode_{kind}_roofline.serve"] = (
                    100.0 * counts["held_requests"] * least / busy)
    if counts.get("steps"):
        points = counts["rays_per_step"] * counts["n_samples"] // chips
        busy = _level_time(level_s, None, TRANSPOSE)
        if busy:
            least, _ = work.least_time(
                *encode_work(grid, points, range(grid["n_levels"]),
                             backward=True), peaks)
            out["encode_transpose_roofline.train"] = (
                100.0 * counts["steps"] * least / busy)
        host = (span_seconds(program_spans, trace, "train.dispatch")
                + span_seconds(program_spans, trace, "train.host"))
        if host:
            out["host_ms_per_step.train"] = host / counts["steps"] * 1e3
    return out
