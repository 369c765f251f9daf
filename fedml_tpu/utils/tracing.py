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
- **Device scopes** (``device_scopes``): the drivers name their device work
  with ``jax.named_scope("fedml.<layer>")``, and the profiler's device
  events carry only an instruction's name. ``run_round`` hands the timer,
  the first time it dispatches a given operand shape, the jitted round
  program and the abstract values of its operands
  (``RoundTimer.register_program``: one tuple of shapes and a lookup a
  round, nothing lowered). On demand ``device_scopes()`` lowers each
  registered program from those abstract values - which finds the
  lowering the call itself made and on it the executable that ran, no
  second compilation - reads the optimised HLO and returns, for every
  instruction, the ``fedml.*`` scopes it ran under: what joins a device
  trace to the program's layers by instruction name.
- ``profile`` — context manager around ``jax.profiler.trace`` emitting a
  TensorBoard-loadable trace directory when enabled, a no-op otherwise.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import re
import sys
import threading
import time
import weakref
from collections import defaultdict, deque
from typing import (Dict, FrozenSet, Iterator, List, NamedTuple, Optional,
                    Tuple)

import jax
import numpy as np
from jax.profiler import TraceAnnotation

#: ``(name, thread name, round index open when the span closed or None,
#: t0, t1)``, the times on ``time.perf_counter_ns()``
Span = Tuple[str, str, Optional[int], int, int]

#: what a round closes (round, prefetch_wait, dispatch, produce, pack,
#: upload, a starved interval; device_wait and eval every few rounds): the
#: span ring holds ``ring_capacity`` rounds of them
SPANS_PER_ROUND = 8

#: the ``fedml.*`` scopes an instruction ran under, outermost first
Chain = Tuple[str, ...]

#: the process's live timers, for ``recent_spans`` and ``device_scopes``
_timers: "weakref.WeakSet[RoundTimer]" = weakref.WeakSet()
_timers_lock = threading.Lock()


def _live_timers() -> List["RoundTimer"]:
    with _timers_lock:
        return list(_timers)


def recent_spans() -> List[Span]:
    """The spans still in the ring of every live ``RoundTimer`` of this
    process, by start: the program's host timeline for a reader that has no
    handle on the driver (the benchmark's ``idle_by_program_span``)."""
    return sorted((s for t in _live_timers() for s in t.spans()),
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
        #: the round programs dispatched so far, by jitted function and
        #: operand shapes (``register_program``)
        self._programs: Dict[tuple, _Program] = {}
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

    def register_program(self, fn, model, operands: tuple) -> None:
        """``fn(model, *operands)`` is about to be dispatched: the first
        time for these operand shapes, keep the jitted ``fn`` and the
        abstract values (shape, dtype, sharding; never the arrays, the
        model is donated) of what it is called with, for
        ``device_scopes``. Every other round costs the tuple of shapes and
        one lookup; nothing is lowered here."""
        key = (fn,) + tuple(a.shape for a in operands)
        if key in self._programs:
            return
        with self._lock:
            self._programs[key] = _Program(fn, _abstract((model,) + operands))

    def programs(self) -> List["_Program"]:
        """The registered round programs, oldest first."""
        with self._lock:
            return list(self._programs.values())

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


# -- device scopes: the compiled round programs' own names for their work ----

class ScopeMap(NamedTuple):
    """One HLO module's instructions by the ``fedml.*`` scopes they ran
    under. ``chains[instruction]`` is the scope chain, outermost first and
    empty where nobody named the work; ``mixed`` names the fusions whose
    fused instructions carry more than one chain, where the fusion rule of
    ``parse_hlo_scopes`` decided; ``kinds[instruction]`` is its result type
    and operation without layouts (``f32[8,128] fusion``), by which a
    reader tells that an event of that name is this instruction and not
    another compilation's."""

    chains: Dict[str, Chain]
    mixed: FrozenSet[str]
    kinds: Dict[str, str]


class _Program:
    """A registered round program; ``scopes`` is ``(module name, ScopeMap)``
    once read, None where reading failed."""

    __slots__ = ("fn", "avals", "scopes")
    _UNREAD = object()

    def __init__(self, fn, avals):
        self.fn, self.avals, self.scopes = fn, avals, self._UNREAD


def _abstract(tree):
    """The abstract values ``jit`` would see: shape, dtype, weak type, and
    the sharding of a committed array only - so that lowering them again
    finds the lowering, and with it the executable, the call itself made
    (a placement the call never named would be another program)."""
    def one(a):
        committed = getattr(a, "committed", False)
        return jax.ShapeDtypeStruct(
            np.shape(a), jax.numpy.result_type(a),
            sharding=a.sharding if committed else None,
            weak_type=getattr(a, "weak_type", False))
    return jax.tree.map(one, tree)


_SCOPE = re.compile(r"fedml\.\w+")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"^(.*?[\w\-])\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([^\s,)}]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}")
#: the operations whose scope a fusion takes before its root's
_PRODUCTS = ("dot", "convolution")


def scope_chain(op_name: str) -> Chain:
    """The ``fedml.*`` tokens of an HLO ``op_name`` in order, each once:
    ``jit(f)/fedml.local_train/transpose(jvp(fedml.mamba2))/fedml.ssd/dot``
    is ``(fedml.local_train, fedml.mamba2, fedml.ssd)``."""
    return tuple(dict.fromkeys(_SCOPE.findall(op_name)))


def instruction_kind(rest: str) -> str:
    """Of an instruction as HLO text prints it after its ``=``, the result
    type and the operation, layouts dropped: what precedes the operands."""
    found = _KIND.match(_LAYOUT.sub("", rest))
    return found.group(1) if found else ""


def parse_hlo_scopes(text: str) -> Tuple[str, ScopeMap]:
    """``(module name, ScopeMap)`` of an HLO module as ``as_text()``
    prints it, metadata included. An instruction's chain is
    ``scope_chain`` of its own ``op_name``; one without a ``fedml.*`` token
    of its own (a copy a layout pass inserted, a parameter, a tuple)
    inherits the chain of the instruction that calls its computation
    (``while`` body and condition, ``call``, ``conditional``). A fusion
    takes the chain of the ``dot`` / ``convolution`` inside its fused
    computation where that has one (a weight gradient with the SGD update
    fused in has the update as its root), else its own. The fused
    computations' own instructions are left out: a trace never shows
    them."""
    module = ""
    own: Dict[str, Chain] = {}
    kinds: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    home: Dict[str, str] = {}  # instruction -> its computation
    members: Dict[str, List[str]] = defaultdict(list)
    caller: Dict[str, str] = {}  # computation -> an instruction calling it
    fused: Dict[str, str] = {}  # fusion instruction -> its computation
    computation = None
    for line in text.splitlines():
        if computation is None:
            found = _COMPUTATION.match(line)
            if found:
                computation = found.group(1)
            elif not module:
                found = _MODULE.match(line)
                module = found.group(1) if found else ""
            continue
        if line.startswith("}"):
            computation = None
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, rest = found.groups()
        named = _OP_NAME.search(rest)
        own[name] = scope_chain(named.group(1)) if named else ()
        kinds[name] = instruction_kind(rest)
        opcode[name] = kinds[name].rpartition(" ")[2]
        home[name] = computation
        members[computation].append(name)
        for one, many in _CALLED.findall(rest):
            for called in (one,) if one else many.split(","):
                called = called.strip().lstrip("%")
                caller.setdefault(called, name)
                if opcode[name] == "fusion":
                    fused[name] = called

    resolved: Dict[str, Chain] = {}

    def chain(name: str) -> Chain:
        if name not in resolved:
            above = caller.get(home[name])
            resolved[name] = own[name] or (chain(above) if above else ())
        return resolved[name]

    inside = set(fused.values())
    chains, mixed = {}, set()
    for name, computation in home.items():
        if computation in inside:
            continue
        chains[name] = chain(name)
        if name in fused:
            held = [own[m] for m in members[fused[name]] if own[m]]
            products = [own[m] for m in members[fused[name]]
                        if own[m] and opcode[m] in _PRODUCTS]
            if products:
                chains[name] = products[0]
            if len(set(held)) > 1:
                mixed.add(name)
    return module, ScopeMap(chains, frozenset(mixed),
                            {name: kinds[name] for name in chains})


def _read_scopes(program: _Program) -> Optional[Tuple[str, ScopeMap]]:
    """Lower ``program`` from its abstract operands - which finds the
    lowering the call made, and on it the executable that ran - and read
    its optimised HLO; what it cost goes to standard error. None, with the
    reason there, on any failure."""
    t0 = time.perf_counter()
    try:
        device = jax.local_devices()[0]
        before = (device.memory_stats() or {}).get("bytes_in_use")
        compiled = program.fn.lower(*program.avals).compile()
        module, scopes = parse_hlo_scopes(compiled.as_text())
        during = (device.memory_stats() or {}).get("bytes_in_use")
    except Exception as why:  # noqa: BLE001 - an observer must not raise
        print(f"[fedml] device_scopes: no map of a round program: "
              f"{type(why).__name__}: {str(why)[:300]}", file=sys.stderr,
              flush=True)
        return None
    print(f"[fedml] device_scopes: {module} lowered and read in "
          f"{time.perf_counter() - t0:.2f} s, {len(scopes.chains)} "
          f"instructions, {len(scopes.mixed)} mixed fusions; device bytes "
          f"in use {before} -> {during}", file=sys.stderr, flush=True)
    return module, scopes


def device_scopes() -> Dict[str, ScopeMap]:
    """``{HLO module name: ScopeMap}`` of the round programs the live
    ``RoundTimer``s of this process have registered (dropped drivers
    collected first): the join between a device trace, whose events carry
    an instruction's name and nothing else, and the program's ``fedml.*``
    scopes (the benchmark's ``scope_ops``). Each program is read once
    (``_read_scopes``) and kept. Two programs of one module name (a second
    padded length) are merged; an instruction they disagree on is left
    out, so a reader's coverage says so. Empty where nothing was
    registered or nothing could be read."""
    gc.collect()  # a dropped driver sits in cycles with its prefetcher
    timers = _live_timers()
    out: Dict[str, ScopeMap] = {}
    disagreed: Dict[str, set] = defaultdict(set)
    for program in (p for timer in timers for p in timer.programs()):
        if program.scopes is _Program._UNREAD:
            program.scopes = _read_scopes(program)
        if program.scopes is None:
            continue
        module, scopes = program.scopes
        chains, mixed, kinds = out.get(module, ScopeMap({}, frozenset(), {}))
        disagreed[module] |= {
            name for name, chain in scopes.chains.items()
            if (chains.setdefault(name, chain),
                kinds.setdefault(name, scopes.kinds[name]))
            != (chain, scopes.kinds[name])}
        out[module] = ScopeMap(chains, mixed | scopes.mixed, kinds)
    return {module: ScopeMap(
        {n: c for n, c in chains.items() if n not in disagreed[module]},
        mixed - disagreed[module],
        {n: k for n, k in kinds.items() if n not in disagreed[module]})
        for module, (chains, mixed, kinds) in out.items()}


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
