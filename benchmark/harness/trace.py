"""From a profiler trace to numbers: the reduction every PR shares.

Two stages. ``extract`` reads the ``.xplane.pb`` the JAX profiler wrote
(``jax.profiler.ProfileData``, nothing else) into a small plain structure:
for every device plane the events of its operation and program lines, and
from the host planes the benchmark's own spans (``bench.*``
``TraceAnnotation``s). The structure is JSON, so a recorded one can be kept
beside the tests and a run's can be looked at by hand. The other functions
reduce that structure: busy time as the union of the operation intervals,
idle gaps and the host span open during each, operation time by a pattern
over operation names, the exposed share of collectives, and the top
operations.

All times are seconds on the trace's own clock, which host and device
planes share.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: device planes and the lines read from them, as the TPU profiler names
#: them: the operations in program order (a ``while`` encloses its body's
#: operations), the asynchronous operations from start to done, and the
#: programs' executions
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
LINES = {"ops": "XLA Ops", "async": "Async XLA Ops", "modules": "XLA Modules"}
#: the benchmark's host spans; SLICE encloses the traced window
SPAN_PREFIX = "bench."
SLICE = "bench.slice"

Interval = Tuple[float, float]
#: key under which a trace keeps the event tables its reductions have built
_CACHE = "_events"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str) -> Dict:
    """``{"names": [str], "devices": [{"name", "ops": [[id, start, dur]],
    "async": [...], "modules": [...]}], "spans": [[name, start, dur]]}``.
    ``names[id]`` is an event's name: for an operation the whole HLO
    instruction as the TPU profiler writes it (``%fusion.3 = f32[...]
    fusion(...)``), for a program ``jit_<function>(<fingerprint>)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    names: List[str] = []
    ids: Dict[str, int] = {}

    def name_id(event) -> int:
        if event.name not in ids:
            ids[event.name] = len(names)
            names.append(event.name)
        return ids[event.name]

    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            dev = {"name": plane.name}
            for key, line_name in LINES.items():
                line = lines.get(line_name)
                dev[key] = [] if line is None else [
                    [name_id(e), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9])
    devices.sort(key=lambda d: d["name"])
    spans.sort(key=lambda s: s[1])
    return {"names": names, "devices": devices, "spans": spans}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({k: v for k, v in trace.items() if k != _CACHE}, f)


def load(path: str) -> Dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic ------------------------------------------------------

def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` as disjoint, sorted intervals."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """What ``window`` has outside the disjoint, sorted ``merged``."""
    out, at = [], window[0]
    for start, end in merged:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def subtract(intervals: Sequence[Interval], holes: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of the union of ``intervals`` outside the union of
    ``holes``."""
    out: List[Interval] = []
    holes = merge(holes)
    for start, end in merge(intervals):
        out.extend(gaps(clip(holes, (start, end)), (start, end)))
    return out


# -- the structure's own accessors ---------------------------------------------

def window_of(trace: Dict) -> Interval:
    """The traced window: the ``bench.slice`` span."""
    for name, start, dur in trace["spans"]:
        if name == SLICE:
            return (start, start + dur)
    raise ValueError(f"the trace has no {SLICE!r} span")


def spans_in(trace: Dict, name: str, window: Interval) -> List[Interval]:
    return [(s, s + d) for n, s, d in trace["spans"]
            if n == name and s >= window[0] and s + d <= window[1]]


def short_name(name: str, limit: int = 120) -> str:
    """An HLO instruction cut to what a reader needs: its name, result
    type, operation and first operands, without the layouts."""
    return re.sub(r"\{[^{}]*\}", "", name).replace(" = ", " ")[:limit]


def _matching(names: Sequence[str], pattern: Optional[str],
              exclude: Optional[str] = None) -> np.ndarray:
    inc = re.compile(pattern) if pattern else None
    exc = re.compile(exclude) if exclude else None
    return np.array([(inc is None or bool(inc.search(n)))
                     and not (exc is not None and bool(exc.search(n)))
                     for n in names], bool)


class Events:
    """One line of one device, clipped to a window, as arrays sorted by
    start: ``ids``, ``start``, ``end``, and for a line whose events nest
    (the operations: a ``while`` encloses its body) ``self_s``, an event's
    duration less its direct children's, and ``leaf``. Host and device
    clocks agree to a fraction of a millisecond only, so an event that
    straddles the window's edge is cut there, not dropped."""

    def __init__(self, device: Dict, line: str, window: Interval):
        lo, hi = window
        rows = sorted(((i, max(s, lo), min(s + d, hi))
                       for i, s, d in device[line] if s + d > lo and s < hi),
                      key=lambda e: (e[1], -e[2]))
        self.ids = np.array([e[0] for e in rows], int)
        self.start = np.array([e[1] for e in rows], float)
        self.end = np.array([e[2] for e in rows], float)
        self.self_s = self.end - self.start
        self.leaf = np.ones(len(rows), bool)
        open_events: List[int] = []  # indices of the enclosing events
        for i in range(len(rows)):
            # an event encloses the next only if it holds all of it: two
            # neighbours can overlap by a rounding of their timestamps
            while open_events and (
                    self.end[open_events[-1]] <= self.start[i]
                    or self.end[open_events[-1]] < self.end[i] - 1e-9):
                open_events.pop()
            if open_events:
                parent = open_events[-1]
                self.self_s[parent] -= self.end[i] - self.start[i]
                self.leaf[parent] = False
            open_events.append(i)

    def intervals(self, keep: Optional[np.ndarray] = None) -> List[Interval]:
        keep = np.ones(len(self.ids), bool) if keep is None else keep
        return list(zip(self.start[keep].tolist(), self.end[keep].tolist()))

    def inside(self, intervals: Sequence[Interval]) -> np.ndarray:
        """Which events start inside one of the disjoint, sorted
        ``intervals``."""
        if not intervals:
            return np.zeros(len(self.ids), bool)
        lows = np.array([i[0] for i in intervals])
        highs = np.array([i[1] for i in intervals])
        at = np.searchsorted(lows, self.start, side="right") - 1
        return (at >= 0) & (self.start < highs[np.maximum(at, 0)])


def events(trace: Dict, device: int, line: str, window: Interval) -> Events:
    """The ``Events`` of one device's line in ``window``, built once per
    trace: every metric of a run reduces the same slice."""
    cache = trace.setdefault(_CACHE, {})
    key = (device, line, window)
    if key not in cache:
        cache[key] = Events(trace["devices"][device], line, window)
    return cache[key]


# -- reductions ------------------------------------------------------------------

def busy_seconds(trace: Dict, window: Interval) -> float:
    """Seconds of ``window`` in which an operation ran, averaged over the
    devices: the union of each device's operation intervals."""
    per_device = [total(merge(events(trace, d, "ops", window).intervals()))
                  for d in range(len(trace["devices"]))]
    return float(np.mean(per_device)) if per_device else 0.0


def op_seconds(trace: Dict, window: Interval, pattern: Optional[str] = None,
               exclude: Optional[str] = None,
               within_modules: Optional[str] = None,
               outside_spans: Sequence[str] = ()) -> Optional[float]:
    """Device seconds in ``window`` of the operations whose name matches
    ``pattern`` and not ``exclude``, averaged over the devices: the sum of
    their self times, so an enclosing ``while`` and its body are not
    counted twice. With ``within_modules`` only operations that start while
    a program whose name matches it runs; with ``outside_spans`` none that
    starts while one of those host spans is open. None if nothing
    matches."""
    keep_name = _matching(trace["names"], pattern, exclude)
    barred = merge([i for n in outside_spans
                    for i in spans_in(trace, n, window)])
    sums, seen = [], False
    for device in range(len(trace["devices"])):
        ops = events(trace, device, "ops", window)
        keep = keep_name[ops.ids]
        if within_modules is not None:
            programs = events(trace, device, "modules", window)
            running = _matching(trace["names"], within_modules)[programs.ids]
            keep = keep & ops.inside(merge(programs.intervals(running)))
        keep = keep & ~ops.inside(barred)
        seen = seen or bool(keep.any())
        sums.append(float(ops.self_s[keep].sum()))
    return float(np.mean(sums)) if seen else None


def top_ops(trace: Dict, window: Interval, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operations with most self time in ``window`` (seconds,
    averaged over the devices), under the names XLA gives them."""
    sums = np.zeros(len(trace["names"]))
    for device in range(len(trace["devices"])):
        ops = events(trace, device, "ops", window)
        np.add.at(sums, ops.ids, ops.self_s)
    sums /= max(1, len(trace["devices"]))
    order = np.argsort(-sums)[:k]
    return [(short_name(trace["names"][i]), float(sums[i]))
            for i in order if sums[i] > 0]


def idle_by_span(trace: Dict, window: Interval, span_names: Sequence[str],
                 other: str) -> List[Tuple[str, float]]:
    """Idle seconds of ``window`` (averaged over the devices) by the host
    span open at the time; idle time under none of ``span_names`` goes to
    ``other``. Longest first."""
    spans = {n: merge(spans_in(trace, n, window)) for n in span_names}
    sums = {n: 0.0 for n in list(span_names) + [other]}
    for device in range(len(trace["devices"])):
        busy = merge(events(trace, device, "ops", window).intervals())
        idle = gaps(busy, window)
        covered = 0.0
        for n, where in spans.items():
            part = sum(total(clip(where, gap)) for gap in idle)
            sums[n] += part
            covered += part
        sums[other] += total(idle) - covered
    count = max(1, len(trace["devices"]))
    out = [(n, s / count) for n, s in sums.items() if s > 0]
    return sorted(out, key=lambda item: -item[1])


def exposed_share(trace: Dict, window: Interval, pattern: str
                  ) -> Optional[float]:
    """Of the time the matching (collective) operations are in flight, the
    share during which no other operation runs on that device; over all
    devices. In flight: an asynchronous operation from its start to its
    done (the asynchronous line), a synchronous one while it runs. None if
    there is no such operation."""
    is_collective = _matching(trace["names"], pattern)
    in_flight = exposed = 0.0
    for device in range(len(trace["devices"])):
        ops = events(trace, device, "ops", window)
        flights = events(trace, device, "async", window)
        mine = is_collective[ops.ids]
        collectives = merge(ops.intervals(mine & ops.leaf)
                            + flights.intervals(is_collective[flights.ids]))
        others = ops.intervals(~mine & ops.leaf)
        in_flight += total(collectives)
        exposed += total(subtract(collectives, others))
    return exposed / in_flight if in_flight > 0 else None
