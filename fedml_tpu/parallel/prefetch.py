"""Async round pipeline: overlap host cohort packing + H2D upload with
device compute.

Every federated round used to be a strictly serial host→device chain:
sample the cohort, pack it on host, ``device_put`` it, and only then
dispatch — pack and upload paid their full latency on the critical path
every round. But ``sample_clients(round_idx, ...)`` is a deterministic
function of the round index, so round r+1's cohort is fully known while
round r is still executing on device, and JAX's async dispatch makes the
overlap free to exploit. This is flax's ``prefetch_to_device``
double-buffering pattern applied to federated cohorts instead of batches.

(This lives next to ``pipeline.py`` — GPipe *model* pipelining over a
``pp`` mesh axis; this module pipelines the *input side* of the round.)

:class:`RoundPrefetcher` runs a caller-supplied ``produce(key)`` (host
pack + sharded upload) on ONE background thread, keeping up to ``depth``
produced slots in flight — depth 2 is classic double buffering, and the
bound is what caps HBM growth. Correctness contract:

- **bit-identical trajectories**: the prefetcher never computes anything
  itself; it runs the exact serial-path ``produce`` for the exact key, so
  the arrays a round consumes are the arrays the serial path would build.
- **donation-safe**: payloads are data arrays only (the round programs
  donate the model buffer, never the data operands); a slot is popped at
  ``get`` and dropped by the caller after its round's dispatch holds it.
- **graceful degradation**: ``depth<=0`` (or ``FEDML_TPU_PREFETCH=0``)
  means the serial path runs; a worker-thread exception is re-raised on
  the caller at ``get``; :meth:`invalidate` discards every in-flight slot
  (the mid-run dataset-swap contract, mirroring the drivers'
  ``_pack_cache``).
- **speculation misses are safe**: an out-of-sequence ``get`` (resume at
  an arbitrary round, an async server re-sampling) simply produces
  inline and re-aims the speculation stream at the new key's successors.
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

#: env kill switch / override: ``FEDML_TPU_PREFETCH=0`` forces the serial
#: path everywhere regardless of config (to A/B the pipeline against the
#: serial loop without touching configs); any other integer overrides the
#: configured depth.
PREFETCH_ENV = "FEDML_TPU_PREFETCH"

_SHUTDOWN = object()


def resolve_prefetch_depth(requested: int) -> int:
    """The effective prefetch depth: ``$FEDML_TPU_PREFETCH`` wins over the
    configured value when set; negative values clamp to 0 (serial)."""
    env = os.environ.get(PREFETCH_ENV)
    if env is not None and env.strip() != "":
        try:
            return max(0, int(env))
        except ValueError as exc:
            raise ValueError(
                f"${PREFETCH_ENV}={env!r} is not an integer") from exc
    return max(0, int(requested))


def _worker(ref: "weakref.ref", requests: "queue.SimpleQueue") -> None:
    """Worker loop. Holds NO strong reference to the prefetcher between
    requests (only the weakref + queue), so dropping the prefetcher lets
    its ``weakref.finalize`` enqueue the shutdown sentinel and the thread
    exits instead of leaking."""
    while True:
        item = requests.get()
        if item is _SHUTDOWN:
            return
        key, gen, produce = item
        try:
            payload, exc = produce(key), None
        except BaseException as e:  # noqa: BLE001 — re-raised at get()
            payload, exc = None, e
        pf = ref()
        if pf is None:
            return
        with pf._cond:
            if pf._inflight.get(key) == gen:
                del pf._inflight[key]
            if gen == pf._gen and key in pf._window:
                pf._ready[key] = (payload, exc)
            else:  # invalidated or mispredicted past: drop the stale slot
                pf._stats["invalidated"] += 1
            pf._cond.notify_all()
        del pf, payload, exc, item, produce  # hold nothing while idle


class RoundPrefetcher:
    """Speculative producer of per-round host payloads.

    ``produce(key) -> payload`` is the serial path's host work for one
    round (pack + upload), called either on the worker thread (hit) or
    inline on the caller (miss). ``next_key`` predicts the key sequence
    (default ``key + 1`` for plain round indices; fused block windows use
    ``(r0, R) -> (r0 + R, R)``). After every :meth:`get` the next
    ``depth`` keys are scheduled, so steady state keeps ``depth`` slots
    in flight/ready — the HBM bound.
    """

    def __init__(self, produce: Callable[[Any], Any], depth: int,
                 next_key: Optional[Callable[[Any], Any]] = None,
                 name: str = "round-prefetch"):
        self.produce = produce
        self.depth = max(0, int(depth))
        self.next_key = next_key or (lambda k: k + 1)
        self.name = name
        self._cond = threading.Condition()
        self._ready: Dict[Any, Tuple[Any, Optional[BaseException]]] = {}
        self._inflight: Dict[Any, int] = {}  # key -> generation
        self._window: set = set()  # keys speculation currently expects
        self._gen = 0
        self._requests: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._stats = {"hits": 0, "misses": 0, "invalidated": 0}
        # GC of the prefetcher (or interpreter exit) stops the worker
        self._finalizer = weakref.finalize(self, self._requests.put,
                                           _SHUTDOWN)

    # -- caller side -------------------------------------------------------
    def get(self, key, upcoming=None, timer=None) -> Tuple[Any, bool]:
        """Payload for ``key``: ``(payload, hit)``.

        Hit = the slot was produced (or is being produced) by the worker;
        the time this call blocks on an in-flight slot is ``timer``'s
        ``prefetch_wait`` span, one a call, empty when the slot was ready.
        Miss = produced inline on this thread (the serial path, charged to
        the producer's own timer phases, never to the wait). Either way the
        speculation stream is re-aimed before any inline work, so the
        worker packs ahead while a miss packs here.

        ``upcoming`` — when the caller KNOWS its future key sequence
        (a driver's chunked schedule, a round loop that ends at
        ``comm_round``), pass it and exactly those keys are speculated:
        an empty list means "nothing follows; speculate nothing" (the
        end-of-run case — without it the worker would pack slots nothing
        ever consumes and they would pin HBM for the API's lifetime).
        ``None`` falls back to ``next_key`` prediction."""
        if self.depth <= 0 or self._closed:
            self._stats["misses"] += 1
            return self.produce(key), False
        wait_span = (timer.phase("prefetch_wait") if timer is not None
                     else contextlib.nullcontext())
        with self._cond:
            gen = self._gen
            with wait_span:
                if key not in self._ready and self._inflight.get(key) == gen:
                    while (self._gen == gen and key not in self._ready
                           and key in self._inflight):
                        self._cond.wait()
            slot = self._ready.pop(key, None)
            self._schedule_locked(key, upcoming)
        if slot is not None:
            payload, exc = slot
            if exc is not None:
                raise exc
            self._stats["hits"] += 1
            return payload, True
        self._stats["misses"] += 1
        return self.produce(key), False

    def _schedule_locked(self, key, upcoming=None) -> None:
        """Queue the next speculation window — ``upcoming[:depth]`` when
        the caller supplied its real schedule, else ``depth`` successors
        of ``key`` via ``next_key`` — and evict ready slots outside that
        window (caller holds the lock). The eviction is what bounds
        resident slots to ``depth`` even under persistent mispredictions —
        orphaned speculative payloads must not pin HBM."""
        gen = self._gen
        if upcoming is None:
            upcoming, k = [], key
            for _ in range(self.depth):
                k = self.next_key(k)
                upcoming.append(k)
        upcoming = list(upcoming)[:self.depth]
        window = set(upcoming)
        for k in upcoming:
            if k in self._ready or k in self._inflight:
                continue
            self._inflight[k] = gen
            self._requests.put((k, gen, self.produce))
        self._window = window  # the worker drops deliveries outside it
        for stale in [r for r in self._ready if r not in window]:
            del self._ready[stale]
            self._stats["invalidated"] += 1
        if window and (self._thread is None
                       or not self._thread.is_alive()):
            self._thread = threading.Thread(
                target=_worker, args=(weakref.ref(self), self._requests),
                name=self.name, daemon=True)
            self._thread.start()

    def invalidate(self) -> None:
        """Discard every ready and in-flight slot (mid-run dataset swap:
        the exact contract of the drivers' ``_pack_cache``). Slots already
        being produced are dropped on arrival via the generation check."""
        with self._cond:
            self._gen += 1
            self._stats["invalidated"] += len(self._ready)
            self._stats["invalidated"] += len(self._inflight)
            self._ready.clear()
            self._inflight.clear()
            self._cond.notify_all()

    def close(self) -> None:
        """Stop the worker and drop all slots; subsequent ``get`` calls
        produce inline (serial path)."""
        with self._cond:
            self._closed = True
            self._gen += 1
            self._ready.clear()
            self._inflight.clear()
            self._cond.notify_all()
        if self._finalizer.detach() is not None:
            self._requests.put(_SHUTDOWN)
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def stats(self) -> Dict[str, int]:
        """Counters for evidence rows: ``hits``/``misses``/``invalidated``.
        The durations are the round timer's: ``prefetch_wait`` is what the
        caller waited, ``produce`` less that what the pipeline hid."""
        with self._cond:
            return dict(self._stats)


class PackBufferPool:
    """The host buffers packed cohorts are written into, recycled.

    A cohort's ``(x, y, mask)`` triple is ``take``n by the packer, written
    (every byte: ``pack_clients(out=...)``), uploaded, and ``give``n back
    by whoever knows the upload is over and that nothing placed shares
    its arrays' memory (``FedAvgAPI._pack_cohort``). A buffer that is
    reused has its pages already; a fresh one faults each of them in.

    Triples are kept by ``key`` (the cohort's slots and padded length)
    for one dataset: a ``take`` or ``give`` for another dataset drops all
    of them (the ``_pack_cache`` contract), and at most ``capacity``
    are held, the longest unused going first - so a padded length that no
    round has any more is not kept. Thread-safe: the prefetch worker and
    the round thread (a miss) pack at the same time."""

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._dataset = None
        self._free: list = []  # (key, triple), the longest unused first

    def take(self, dataset, key):
        """A free triple for ``key``, or None (the caller allocates)."""
        with self._lock:
            if dataset is not self._dataset:
                self._free.clear()
                self._dataset = dataset
            for i in range(len(self._free) - 1, -1, -1):
                if self._free[i][0] == key:
                    return self._free.pop(i)[1]
            return None

    def give(self, dataset, key, triple) -> None:
        """``triple`` is free again (dropped where ``dataset`` is gone)."""
        with self._lock:
            if dataset is not self._dataset:
                return
            self._free.append((key, triple))
            del self._free[:-self.capacity]

    def clear(self) -> None:
        with self._lock:
            self._free.clear()
            self._dataset = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._free)


def aliases_host(host, placed) -> bool:
    """Whether the placed array's memory lies inside the host array it was
    made from, read off the buffers' addresses. The CPU backend takes a
    64-byte-aligned numpy array without copying it (``jnp.asarray`` and
    ``device_put`` alike), so rewriting the host array would rewrite the
    "device" array; a chip's memory is apart. Where a backend will not
    say, the answer is yes."""
    lo = host.ctypes.data
    hi = lo + host.nbytes
    for shard in placed.addressable_shards:
        try:
            ptr = shard.data.unsafe_buffer_pointer()
        except (RuntimeError, NotImplementedError, AttributeError):
            return True  # this backend will not say: assume it is shared
        if lo <= ptr < hi:
            return True
    return False


def bind_prefetcher(slot, dataset, build):
    """Driver-side slot management, ONE definition for every consumer:
    ``slot`` is ``(RoundPrefetcher, dataset-at-bind) | None``. Builds the
    prefetcher lazily on first use; a dataset swap invalidates every
    in-flight slot (the drivers' ``_pack_cache`` contract). Returns the
    updated slot tuple."""
    if slot is None:
        return (build(), dataset)
    if slot[1] is not dataset:
        slot[0].invalidate()
        return (slot[0], dataset)
    return slot


def consume(pf: RoundPrefetcher, key, timer, dataset, repack,
            upcoming=None, round_bound=None):
    """Driver-side consume protocol, ONE definition so the sim, mesh, and
    fused-block paths cannot drift: ``get`` the slot, verify its payload
    was packed against the CURRENT dataset (``repack(key)`` serially and
    drop everything speculative if a produce raced a swap), and charge
    the ``prefetch_wait`` span + hit/miss counters to the round timer. The
    payload contract is ``(dataset, ...)`` — produce snapshots the dataset
    it packed from as element 0.

    With a store-backed virtual population (fedml_tpu/state/), the
    ``produce`` running on the worker IS the streaming cohort
    materialization: shard fetch (LRU/disk/generate) + pack + upload for
    round r+1 overlaps round r's device compute, and the store's cache —
    not a resident ``_pack_cache`` — is what absorbs repeat-sampled
    clients. Every consume also samples peak host RSS into the timer's
    ``host_rss_peak_mb`` gauge: the round loop's choke point is where
    the O(cohort + cache) memory claim gets measured, round by round.

    ``round_bound`` (integer keys only): speculate successor rounds
    strictly below it — the round-loop clamp that keeps the last rounds
    from packing slots nothing will consume."""
    if round_bound is not None:
        upcoming = [r for r in range(key + 1, key + 1 + pf.depth)
                    if r < round_bound]
    payload, hit = pf.get(key, upcoming=upcoming, timer=timer)
    if payload[0] is not dataset:
        pf.invalidate()
        hit = False
        payload = repack(key)
    timer.count("prefetch_hit" if hit else "prefetch_miss")
    timer.update_rss()
    return payload
