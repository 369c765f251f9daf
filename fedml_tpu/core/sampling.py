"""Client sampling with exact RNG parity to the reference.

The reference seeds numpy with the round index before each draw so that any
two implementations select the same clients every round (reference:
fedml_api/distributed/fedavg/FedAVGAggregator.py:89-97 and
fedml_api/standalone/fedavg/fedavg_api.py:96-114). We preserve that contract
bit-for-bit — it is the hook all cross-implementation parity tests hang on.

Sampling happens on the host (it is O(clients) integer work per round); the
resulting index vector is what gets fed to the device gather that re-points
each mesh core at its sampled client's shard (client virtualization, see
reference FedAVGTrainer.update_dataset semantics).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np

#: the reference contract pins the draw to the GLOBAL numpy RNG
#: (np.random.seed(round_idx) then choice). That global state is shared
#: process-wide, so the async round pipeline's prefetch worker (and the
#: cross-silo silo threads) drawing round r+1 concurrently with the main
#: thread's round r would interleave seed/draw pairs and corrupt both
#: cohorts. Each call re-seeds, so mutual exclusion alone restores the
#: exact per-round stream regardless of thread arrival order. RLock, not
#: Lock: callers holding the lock across a seed+draw sequence (the
#: partitioners) nest inside per-draw acquisitions without deadlocking.
_GLOBAL_RNG_LOCK = threading.RLock()


@contextlib.contextmanager
def locked_global_numpy_rng(seed: Optional[int] = None):
    """THE sanctioned way to touch the process-global numpy RNG.

    Everything outside this module that the reference contract pins to
    the global stream (the LDA/homo partitioners' exact
    seed-then-draw-sequence bit-parity, topology coin flips) holds this
    lock across the whole seed+draws sequence, so no concurrent
    ``sample_clients`` (prefetch worker, silo thread) can interleave
    with — and corrupt — either stream. Reentrant: a partitioner
    holding the outer lock may call helpers that take it per draw.

    ``seed`` is applied inside the lock (atomically with the caller's
    subsequent draws). Yields the ``np.random`` module so call sites
    read as draws on the locked stream. The static analyzer (rule
    FT001) recognizes draws lexically inside this context as safe.
    """
    with _GLOBAL_RNG_LOCK:
        if seed is not None:
            np.random.seed(seed)
        yield np.random

#: sentinel fold indices OUTSIDE the client-id range: client c's training
#: key is fold_in(round_key, c), so server-side draws use ids no client can
#: occupy (client ids are int32-positive)
AGG_KEY_SENTINEL = 2**31 - 1
DEVICE_SAMPLE_SENTINEL = 2**31 - 2

#: population size above which ``sample_clients`` switches to the O(k)
#: virtualized draw (partial Fisher–Yates) instead of numpy's O(N)
#: permutation-based ``choice``. At or below it the reference's exact
#: draw stream is preserved bit-for-bit — the threshold sits ABOVE every
#: population this repo has ever run resident (the largest is
#: stackoverflow_nwp's 342,477 clients), so no existing scenario's
#: cohort sequence changes and a pre-virtualization checkpoint resumes
#: onto the identical trajectory. Above it (the new 10^6 territory)
#: there is no prior behavior to match, so the virtualized stream
#: DEFINES the contract at population scale (seeded, deterministic,
#: thread-safe under the same global-RNG lock).
#: ``$FEDML_TPU_VIRTUAL_SAMPLE_THRESHOLD`` overrides.
VIRTUAL_SAMPLE_THRESHOLD = 1 << 19


def _virtual_sample_threshold() -> int:
    import os
    env = os.environ.get("FEDML_TPU_VIRTUAL_SAMPLE_THRESHOLD")
    return int(env) if env else VIRTUAL_SAMPLE_THRESHOLD


def round_keys(base_key, round_idx, client_ids):
    """The per-round RNG chain EVERY FedAvg-family driver shares:
    ``round_key = fold_in(base, round)``, per-client training keys
    ``fold_in(round_key, client_id)``, and the aggregation key at the
    ``AGG_KEY_SENTINEL`` fold. One definition — host loop
    (FedAvgAPI._pack_round), fused scans (FusedRounds), and mesh scans
    (make_spmd_multiround) all call it, so host/fused/mesh trajectory
    parity cannot drift. ``client_ids`` must be uint32 (traced or host).

    Returns ``(round_key, per_client_keys, agg_key)``.
    """
    round_key = jax.random.fold_in(base_key, round_idx)
    keys = jax.vmap(lambda c: jax.random.fold_in(round_key, c))(client_ids)
    agg_key = jax.random.fold_in(round_key, AGG_KEY_SENTINEL)
    return round_key, keys, agg_key


def sample_clients(
    round_idx: int,
    client_num_in_total: int,
    client_num_per_round: int,
    delete_client: Optional[int] = None,
) -> np.ndarray:
    """Sample the participating client indices for one round.

    Full participation (``per_round == total``) returns ``[0..total)`` in
    order with no RNG draw. Otherwise numpy is seeded with ``round_idx`` and
    ``min(per_round, total)`` clients are drawn without replacement.
    ``delete_client`` (leave-one-out contribution measurement, reference
    fedml_api/contribution/horizontal/fedavg_api.py) removes one client from
    the candidate pool before drawing.

    Populations above :data:`VIRTUAL_SAMPLE_THRESHOLD` take the
    virtualized O(k) path (:func:`sample_clients_virtual`): numpy's
    ``choice(replace=False)`` materializes a full N-permutation (plus the
    candidate array) per round, which at N=10^6 is two 8 MB transients
    and ~10 ms of shuffling for a 10-client cohort — per round. Below
    the threshold the draw stream is byte-identical to before.
    """
    if client_num_in_total == client_num_per_round and delete_client is None:
        return np.arange(client_num_in_total)
    if client_num_in_total > _virtual_sample_threshold():
        return _sample_clients_floyd(round_idx, client_num_in_total,
                                     client_num_per_round, delete_client)
    num_clients = min(client_num_per_round, client_num_in_total)
    candidates: Sequence[int] = range(client_num_in_total)
    if delete_client is not None:
        candidates = [c for c in range(client_num_in_total) if c != delete_client]
        num_clients = min(num_clients, len(candidates))
    with _GLOBAL_RNG_LOCK:  # seed+draw must be atomic across threads
        np.random.seed(round_idx)
        return np.random.choice(candidates, num_clients, replace=False)


def sample_clients_virtual(
    round_idx: int,
    client_num_in_total: int,
    client_num_per_round: int,
    delete_client: Optional[int] = None,
    threshold: Optional[int] = None,
) -> np.ndarray:
    """Population-virtualized cohort sampling — the explicit entry point.

    For populations at or under ``threshold`` (default
    :data:`VIRTUAL_SAMPLE_THRESHOLD`) this DELEGATES to
    :func:`sample_clients`, so the cohort is bit-identical to the
    resident-dict path — the parity hook the exact-equality test hangs
    on. Above it, a seeded partial Fisher–Yates draws ``k`` distinct ids
    from ``[0, N)`` in O(k) time and memory — no per-client array of any
    kind is materialized, which is what lets a 10^6-client population
    sample in microseconds per round. Same locking contract: the seed
    and every draw happen atomically under the global-RNG lock.
    """
    if threshold is None:
        threshold = _virtual_sample_threshold()
    if client_num_in_total <= threshold:
        return sample_clients(round_idx, client_num_in_total,
                              client_num_per_round, delete_client)
    return _sample_clients_floyd(round_idx, client_num_in_total,
                                 client_num_per_round, delete_client)


def _sample_clients_floyd(round_idx: int, total: int, per_round: int,
                          delete_client: Optional[int]) -> np.ndarray:
    """k distinct draws from [0, N) via partial Fisher–Yates over a
    virtual ``arange(N)``: only the swapped positions live in a dict, so
    cost is O(k) regardless of N. ``delete_client`` shrinks the virtual
    pool by one and remaps ids past the hole (uniformity preserved)."""
    pool = total if delete_client is None else total - 1
    k = min(per_round, pool)
    out = np.empty(k, dtype=np.int64)
    with _GLOBAL_RNG_LOCK:  # same seed+draw atomicity as the exact path
        np.random.seed(round_idx)
        swaps: dict = {}
        for i in range(k):
            j = int(np.random.randint(i, pool))
            out[i] = swaps.get(j, j)
            swaps[j] = swaps.get(i, i)
    if delete_client is not None:
        out[out >= delete_client] += 1
    return out


def sample_clients_available(
    round_idx: int,
    client_num_in_total: int,
    client_num_per_round: int,
    is_available,
    threshold: Optional[int] = None,
    stats: Optional[dict] = None,
) -> np.ndarray:
    """Availability-restricted cohort draw — ``sample_clients`` composed
    with a WAN availability trace (``fedml_tpu/wan``): cohorts come only
    from clients ``is_available`` marks online, and the draw stays
    bit-reproducible under a fixed ``round_idx`` seed.

    ``is_available(cids: int64[n]) -> bool[n]`` must be a PURE vectorized
    predicate (the trace is a pure function of ``(seed, cid, t)``), so
    the whole draw is a pure function of ``(round_idx, predicate)``.

    Two regimes, split at the same :data:`VIRTUAL_SAMPLE_THRESHOLD` the
    unrestricted sampler uses:

    - **at or below**: the available set is enumerated exactly (O(N),
      fine at resident scale) and the cohort drawn from it with the
      seeded global stream. Fewer available clients than the cohort
      means every one participates and the remainder is filled by seeded
      draws WITH replacement from the available set (a shrunken live
      population re-samples its members more often — the cross-device
      semantic);
    - **above**: seeded REJECTION sampling over uniform ids — expected
      O(k / availability) time and memory, so a 10^6-client population
      still samples in microseconds and no per-client array exists.

    **Graceful degradation**: a (near-)fully-dark population must degrade
    the schedule, never stall it — when the draw cannot find enough
    distinct available clients inside its budget, the remainder comes
    from the unrestricted stream and ``stats['forced']`` counts it
    (surfaced as ``wan_forced_cohorts``). ``stats['rejected']`` counts
    unavailable candidates skipped along the way.
    """
    if threshold is None:
        threshold = _virtual_sample_threshold()
    total = int(client_num_in_total)
    k = min(int(client_num_per_round), total)
    if stats is None:
        stats = {}
    if total <= threshold:
        avail = np.zeros(0, dtype=np.int64)
        for lo in range(0, total, 1 << 17):
            ids = np.arange(lo, min(lo + (1 << 17), total), dtype=np.int64)
            on = ids[np.asarray(is_available(ids), dtype=bool)]
            avail = np.concatenate([avail, on])
        stats["rejected"] = stats.get("rejected", 0) + int(total
                                                          - len(avail))
        with _GLOBAL_RNG_LOCK:  # seed+draw atomic, same contract as always
            np.random.seed(round_idx)
            if len(avail) >= k:
                return np.random.choice(avail, k, replace=False)
            if len(avail) == 0:
                # fully dark population: unrestricted fallback — the
                # schedule degrades (stale cohorts) instead of stalling
                stats["forced"] = stats.get("forced", 0) + k
                return np.random.choice(total, k, replace=False)
            stats["forced"] = stats.get("forced", 0) + (k - len(avail))
            fill = np.random.choice(avail, k - len(avail), replace=True)
            return np.concatenate([avail, fill])
    # -- virtual regime: seeded rejection, O(k / availability) --------------
    out: list = []
    seen: set = set()
    rejected = 0
    batch = max(4 * k, 64)
    budget = max(64 * k, 4096)  # total candidate draws before giving up
    with _GLOBAL_RNG_LOCK:
        np.random.seed(round_idx)
        while len(out) < k and budget > 0:
            cand = np.random.randint(0, total, size=min(batch, budget))
            budget -= len(cand)
            ok = np.asarray(is_available(cand), dtype=bool)
            for c, on in zip(cand.tolist(), ok.tolist()):
                if not on:
                    rejected += 1
                    continue
                if c in seen:
                    continue
                seen.add(c)
                out.append(c)
                if len(out) == k:
                    break
        forced = k - len(out)
        while len(out) < k:
            # budget exhausted (population nearly dark): fill from the
            # unrestricted stream — degrade, don't stall
            c = int(np.random.randint(0, total))
            if c in seen:
                continue
            seen.add(c)
            out.append(c)
    stats["rejected"] = stats.get("rejected", 0) + rejected
    if forced:
        stats["forced"] = stats.get("forced", 0) + forced
    return np.asarray(out, dtype=np.int64)


def eval_subsample(x, y, limit: Optional[int], seed: int):
    """Seeded eval-set subsample, ONE formula for every driver.

    Full-union eval at flagship scale costs more than the training rounds
    it measures (FEMNIST-shape: ~90k test images per eval on the host CPU
    fallback), so drivers accept an eval subsample limit. Both drivers
    must draw the identical subset or the sim==SPMD history parity tests
    would compare different eval sets — hence one shared helper keyed
    only on (len, limit, seed). Returns (x, y) unchanged when ``limit``
    is falsy or already covers the set.
    """
    if limit and len(x) > limit:
        sel = np.random.RandomState(seed).choice(len(x), limit,
                                                 replace=False)
        return x[sel], y[sel]
    return x, y
