"""Tracing/profiling: per-round wall-clock accounting + jax.profiler hooks.

The reference's only tracing is wall-clock log lines
(``aggregate time cost``, FedAVGAggregator.py:85-86). Here:
- ``RoundTimer`` — cheap named phase timing with running aggregates
  (host-side; call ``block_until_ready`` on outputs before stopping a phase
  to charge async device work to the right bucket). Thread-safe: the round
  prefetcher (parallel/prefetch.py) charges ``pack``/``upload`` phases from
  its worker thread while the main thread times ``dispatch`` — overlapped
  phases record where time went, not critical-path wall-clock. Event
  counters (``count``) track prefetch hits/misses next to the phase means.
  Every literal metric name must be registered in
  ``fedml_tpu/obs/registry.py`` (lint rule FT017): the maps are
  defaultdicts, so a typo'd name silently creates a new key.
- **Per-round timeline** (the flight-recorder substrate): drivers call
  ``begin_round(r)`` / ``end_round(r)`` around each round; end_round
  computes the SNAPSHOT DELTA of every phase/counter since begin_round
  (plus current gauge high-waters) into a per-round record held in a
  bounded ring buffer (``round_records()``) and flushed to a bound
  :class:`~fedml_tpu.obs.flight.FlightRecorder` when observability is
  on. Counters bumped by OTHER threads mid-round (prefetch worker,
  heartbeats) are charged to the round that was open — same overlap
  semantics as the phase means. Begin/end never touch RNG, schedules,
  or device state: timelines are a pure observer. The record
  ``end_round`` returns is also the roofline accountant's input
  (``fedml_tpu/obs/perf.py``): drivers pass it to
  ``Observability.round_end(record=...)`` and the per-round ``perf``
  record (MFU, overlap frac, wire bytes/s) derives from exactly these
  deltas — the derivation never reads the live timer.
- **Spans**: every ``phase()`` is also kept as an interval —
  ``(name, thread, round open when it closed, t0, t1)`` on
  ``time.perf_counter_ns()`` — in a second bounded ring (``spans()``, and
  ``recent_spans()`` for every live timer of the process), listed in the
  per-round record, and written as a ``jax.profiler.TraceAnnotation``
  ``fedml.<name>``: under a profiler (``--profile_dir``, the anomaly
  profiles, the benchmark's tracer) the program's host timeline sits on
  the trace's own clock beside the device's operations, on the thread that
  ran it. ``begin_round`` … ``end_round`` is the ``round`` span. Without a
  profiler an annotation costs a fraction of a microsecond.
- **The starved-device probe** (``starved_probe``): two non-blocking
  ``is_ready()`` queries around a round's host-input half say whether the
  device ran out of queued work while the host prepared the next round —
  phases ``device_starved`` (lower bound) and ``device_starved_max``, with
  no profiler, in every run.
- ``profile`` — context manager around ``jax.profiler.trace`` emitting a
  TensorBoard-loadable trace directory when enabled, a no-op otherwise.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import weakref
from collections import defaultdict, deque
from typing import Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

#: ``(name, thread name, round index open when the span closed or None,
#: t0, t1)``, the times on ``time.perf_counter_ns()``
Span = Tuple[str, str, Optional[int], int, int]

#: what a round closes (round, prefetch_wait, dispatch, produce, pack,
#: upload, a starved interval; device_wait and eval every few rounds): the
#: span ring holds ``ring_capacity`` rounds of them
SPANS_PER_ROUND = 8

#: the process's live timers, for ``recent_spans``
_timers: "weakref.WeakSet[RoundTimer]" = weakref.WeakSet()
_timers_lock = threading.Lock()


def recent_spans() -> List[Span]:
    """The spans still in the ring of every live ``RoundTimer`` of this
    process, by start: the program's host timeline for a reader that has no
    handle on the driver (the benchmark's ``idle_by_program_span``)."""
    with _timers_lock:
        timers = list(_timers)
    return sorted((s for t in timers for s in t.spans()),
                  key=lambda s: s[3])


def _is_ready(leaf) -> bool:
    """Whether the device has finished everything enqueued up to ``leaf``.
    A host array (a model restored from a checkpoint) has nothing behind
    it."""
    ready = getattr(leaf, "is_ready", None)
    return True if ready is None else ready()


class RoundTimer:
    def __init__(self, ring_capacity: int = 512) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.counters: Dict[str, int] = defaultdict(int)
        #: high-water marks (``gauge`` keeps the max, not a sum) —
        #: ``host_rss_peak_mb`` and friends
        self.gauges: Dict[str, float] = {}
        self._lock = threading.Lock()
        #: per-round records, newest last, bounded (multi-thousand-round
        #: schedules must not grow host memory; the flight log is the
        #: durable copy)
        self._rounds: deque = deque(maxlen=max(1, int(ring_capacity)))
        #: closed spans, newest last, and how many were ever closed
        self._spans: deque = deque(
            maxlen=max(1, int(ring_capacity)) * SPANS_PER_ROUND)
        self._spans_closed = 0
        #: (round_idx, t0_ns, phase-totals snapshot, phase-counts snapshot,
        #: counter snapshot, spans closed so far, the round's annotation)
        #: for the open round
        self._open_round = None
        self._flight = None
        with _timers_lock:
            _timers.add(self)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Time the block as phase ``name`` and keep it as a span."""
        t0 = time.perf_counter_ns()
        try:
            with TraceAnnotation("fedml." + name):
                yield
        finally:
            self._close_span(name, t0, time.perf_counter_ns())

    def _close_span(self, name: str, t0: int, t1: int) -> None:
        thread = threading.current_thread().name
        with self._lock:
            self.totals[name] += (t1 - t0) * 1e-9
            self.counts[name] += 1
            open_round = self._open_round
            self._spans.append((name, thread,
                                open_round[0] if open_round else None,
                                t0, t1))
            self._spans_closed += 1

    def add(self, name: str, seconds: float) -> None:
        """Charge ``seconds`` to a phase directly (time measured elsewhere,
        e.g. the scheduler's gate waits): totals only, no span."""
        with self._lock:
            self.totals[name] += seconds
            self.counts[name] += 1

    @contextlib.contextmanager
    def starved_probe(self, leaf) -> Iterator[None]:
        """Around the host-input half of a round (``_host_round_inputs``).
        ``leaf`` is one array of the previous round's output: not ready
        until the device has run everything enqueued so far. Ready when the
        block opens: the device had nothing queued for the whole of it,
        charged to ``device_starved``. Ready only when it closes: the device
        ran dry somewhere inside, charged to ``device_starved_max``. Not
        ready then either: the host's work was hidden, nothing charged.
        ``device_starved`` is a lower bound and the two together an upper
        bound on the device time this block cost; ``starved_rounds`` counts
        the rounds charged either way."""
        idle_at_open = _is_ready(leaf)
        t0 = time.perf_counter_ns()
        yield
        t1 = time.perf_counter_ns()
        if _is_ready(leaf):
            self._close_span("device_starved" if idle_at_open
                             else "device_starved_max", t0, t1)
            self.count("starved_rounds")

    def spans(self) -> List[Span]:
        """The span ring, oldest first."""
        with self._lock:
            return list(self._spans)

    def count(self, name: str, n: int = 1) -> None:
        """Bump an event counter (e.g. ``prefetch_hit``/``prefetch_miss``,
        the wire accounting ``comm_bytes_up``/``comm_bytes_down``, or the
        client-state store tiers ``state_cache_hits``/``state_cache_misses``/
        ``state_evictions``/``state_bytes_read``/``state_bytes_written``)."""
        with self._lock:
            self.counters[name] += n

    def gauge(self, name: str, value: float) -> None:
        """Record a high-water mark: the gauge keeps ``max(old, value)``
        (peaks must survive aggregation — a mean of RSS samples would
        hide exactly the spike the memory-flat claim cares about)."""
        with self._lock:
            self.gauges[name] = max(self.gauges.get(name, value), value)

    @staticmethod
    def host_rss_mb() -> float:
        """This process's peak resident set size in MB (linux ru_maxrss
        is KB). The population benches read it per leg — each leg runs
        in its own subprocess because the high-water mark never goes
        back down."""
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    def update_rss(self) -> float:
        """Sample peak host RSS into the ``host_rss_peak_mb`` gauge —
        called per round from the cohort-consume path so the memory-flat
        claim is measured by the run itself, not asserted after it."""
        mb = self.host_rss_mb()
        self.gauge("host_rss_peak_mb", mb)
        return mb

    @property
    def comm_bytes_up(self) -> int:
        """Client->server wire bytes (actual encoded frame lengths,
        credited by the cross-silo launcher from the comm backends)."""
        with self._lock:
            return self.counters["comm_bytes_up"]

    @property
    def comm_bytes_down(self) -> int:
        """Server->client wire bytes (actual encoded frame lengths)."""
        with self._lock:
            return self.counters["comm_bytes_down"]

    # -- the per-round timeline (fedml_tpu/obs flight-recorder substrate) --
    def bind_flight(self, recorder) -> None:
        """Flush every future ``end_round`` record through ``recorder``
        (a :class:`~fedml_tpu.obs.flight.FlightRecorder`); None unbinds."""
        with self._lock:
            self._flight = recorder

    def begin_round(self, round_idx: int) -> None:
        """Open round ``round_idx``: snapshot every phase/counter so
        ``end_round`` can attribute the deltas to this round. An
        already-open round is silently superseded (a crashed server's
        unfinished round must not poison its successor's record)."""
        t0 = time.perf_counter_ns()
        annotation = TraceAnnotation("fedml.round")
        annotation.__enter__()
        with self._lock:
            superseded, self._open_round = self._open_round, (
                int(round_idx), t0, dict(self.totals), dict(self.counts),
                dict(self.counters), self._spans_closed, annotation)
        if superseded is not None:
            superseded[-1].__exit__(None, None, None)

    def end_round(self, round_idx: int,
                  extra: Optional[Dict] = None) -> Optional[Dict]:
        """Close round ``round_idx``: the phase/counter deltas since
        ``begin_round`` (and current gauge high-waters) become one
        per-round record — appended to the ring buffer, flushed to the
        bound flight recorder, and returned. Returns None (and resets)
        on a round mismatch or when no round is open, so resumed /
        partially-wired drivers degrade to no record instead of a wrong
        one. ``extra`` keys (cohort, reported, partial, ...) are merged
        into the record."""
        thread = threading.current_thread().name
        with self._lock:
            open_round, self._open_round = self._open_round, None
            if open_round is None:
                return None
            idx, t0, tot0, cnt0, ctr0, closed0, annotation = open_round
            if idx != int(round_idx):
                annotation.__exit__(None, None, None)
                return None
            phases = {}
            for k in sorted(self.totals):
                ds = self.totals[k] - tot0.get(k, 0.0)
                dn = self.counts[k] - cnt0.get(k, 0)
                if dn or ds:
                    phases[k] = {"s": round(ds, 6), "n": dn}
            counters = {}
            for k in sorted(self.counters):
                d = self.counters[k] - ctr0.get(k, 0)
                if d:
                    counters[k] = d
            # what closed while the round was open, on any thread, oldest
            # first, from the round's start in ns; the round itself is
            # [0, duration_s]
            inside = min(self._spans_closed - closed0, len(self._spans))
            spans = [[name, who, s0 - t0, s1 - t0] for name, who, _, s0, s1
                     in itertools.islice(reversed(self._spans), inside)]
            spans.reverse()
            # the round span closes here, so it holds this bookkeeping too
            annotation.__exit__(None, None, None)
            t1 = time.perf_counter_ns()
            self._spans.append(("round", thread, idx, t0, t1))
            self._spans_closed += 1
            rec = {"kind": "round", "round": idx,
                   "duration_s": round((t1 - t0) * 1e-9, 6),
                   "phases": phases, "spans": spans, "counters": counters,
                   "gauges": {k: self.gauges[k]
                              for k in sorted(self.gauges)}}
            if extra:
                rec.update(extra)
            self._rounds.append(rec)
            flight = self._flight
        if flight is not None:
            flight.append(rec)  # file I/O outside the timer lock
        return rec

    def round_records(self) -> List[Dict]:
        """The ring buffer's per-round records, oldest first."""
        with self._lock:
            return list(self._rounds)

    def means(self) -> Dict[str, float]:
        with self._lock:
            return {k: self.totals[k] / max(1, self.counts[k])
                    for k in self.totals}

    def report(self) -> str:
        out = " | ".join(f"{k}: {v * 1e3:.1f}ms"
                         for k, v in sorted(self.means().items()))
        with self._lock:
            counters = dict(self.counters)
            gauges = dict(self.gauges)
        if counters:
            out += " | " + " | ".join(
                f"{k}: {v}" for k, v in sorted(counters.items()))
        if gauges:
            out += " | " + " | ".join(
                f"{k}: {v:.1f}" for k, v in sorted(gauges.items()))
        return out


@contextlib.contextmanager
def profile(log_dir: Optional[str] = None) -> Iterator[None]:
    """``with profile('/tmp/trace'):`` wraps jax.profiler.trace; with None
    it is a no-op (so call sites need no conditionals)."""
    if log_dir is None:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
