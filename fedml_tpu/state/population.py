"""Virtualized client populations: N=10^6 clients, O(cohort + cache) RSS.

A :class:`VirtualFederatedDataset` duck-types the parts of
:class:`fedml_tpu.data.base.FederatedDataset` the round drivers consume
(``client_num`` / ``pack_clients`` / ``client_weights`` /
``cohort_padded_len`` / the eval unions / the per-client size mapping)
WITHOUT holding any per-client Python object for the population. Client
shards are either

- **generative**: a pure function of ``(seed, client_id)`` — per-client
  sizes come from a vectorized integer-hash → Pareto transform, content
  from a per-client ``RandomState`` — so a million-client population
  costs O(1) to construct and O(cohort) per round; or
- **store-backed**: read from :class:`~fedml_tpu.state.store
  .ClientStateStore` shard files a streaming builder emitted
  (``write_federation_store``), with the one O(N) host artifact — the
  int32 sizes index — memory-mapped, not resident.

Either way the shards flow through the store's LRU tier, so repeat
cohort members hit RAM (``state_cache_hits``) and RSS is bounded by the
cache budget, not the population. ``pack_clients`` is thread-safe (the
round prefetcher packs round r+1 from a worker thread) and pins the
cohort's shards for the duration of the gather.

The module doubles as the population-scale measurement harness::

    python -m fedml_tpu.state.population --population 1000000 \
        --rounds 5 --cohort 10

runs FedAvg rounds over the virtual population IN THIS PROCESS and
prints one JSON line with rounds/sec, ``host_rss_peak_mb``, and the
store-tier counters — ``bench.py``'s ``population_scale`` stage shells
out one subprocess per population so each leg's peak-RSS high-water mark
is its own, and ``ci/run_fast.sh`` runs the 100k-client smoke.
"""

from __future__ import annotations

import json
import threading
from typing import Callable, Dict, Iterable, Iterator, Optional, Tuple

import numpy as np

from fedml_tpu.data.base import pack_buffers
from fedml_tpu.state.store import ClientStateStore

_M64 = (1 << 64) - 1


def _mix64(v: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer: uint64 -> well-mixed uint64. The
    per-client hash every size/content derivation keys on — stateless,
    so any client's draw is computable without touching the others.
    Wraparound is the algorithm, so the overflow warning is silenced."""
    with np.errstate(over="ignore"):
        v = (v + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(_M64)
        v = ((v ^ (v >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)) & np.uint64(_M64)
        v = ((v ^ (v >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)) & np.uint64(_M64)
        return v ^ (v >> np.uint64(31))


def client_uniform(cids, seed: int, salt: int = 0) -> np.ndarray:
    """Per-client uniform in (0, 1): hash of (seed, salt, cid)."""
    cids = np.asarray(cids, dtype=np.uint64)
    base = _mix64(np.uint64((seed * 0x5851F42D4C957F2D + salt) & _M64))
    u = _mix64(cids ^ base)
    # top 53 bits -> [0, 1); nudge off zero so Pareto's u**-1/a is finite
    return np.maximum((u >> np.uint64(11)) * (2.0 ** -53), 2.0 ** -53)


def pareto_sizes(cids, seed: int, min_samples: int = 10,
                 max_samples: int = 400,
                 alpha: float = 1.3) -> np.ndarray:
    """LEAF-style heavy-tailed per-client sample counts as a PURE function
    of (seed, client id): Pareto(xm=min_samples, alpha) by inverse CDF on
    the hashed uniform, clamped at ``max_samples``. Vectorized — sizing a
    10^6-id chunk is one hash pass, no per-client RNG objects."""
    u = client_uniform(cids, seed, salt=0x51)
    sizes = (min_samples * u ** (-1.0 / alpha)).astype(np.int64)
    return np.clip(sizes, min_samples, max_samples)


def iter_size_chunks(sizes_for, client_num: int, chunk: int = 1 << 17):
    """Walk ``[0, client_num)`` through a vectorized size function in
    fixed chunks — THE population-scan helper every consumer shares
    (dataset reductions, the lazy dict view, ``data/stats``), so the
    chunking policy and any indexing fix live in exactly one place."""
    for lo in range(0, client_num, chunk):
        yield sizes_for(np.arange(lo, min(lo + chunk, client_num)))


class _LazySizeDict:
    """Read-only ``train_data_local_num_dict`` view over a size function:
    O(1) per lookup, nothing resident. Iteration walks the full id range
    (only reached by opt-in diagnostics; the hot paths use the vectorized
    ``sizes_for``)."""

    def __init__(self, n: int, sizes_for: Callable[[np.ndarray], np.ndarray]):
        self._n = n
        self._sizes_for = sizes_for

    def __getitem__(self, cid: int) -> int:
        cid = int(cid)
        if not 0 <= cid < self._n:
            raise KeyError(cid)
        return int(self._sizes_for(np.asarray([cid]))[0])

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        return iter(range(self._n))

    def __contains__(self, cid) -> bool:
        return 0 <= int(cid) < self._n

    def keys(self):
        return range(self._n)

    def values(self) -> Iterator[int]:
        for chunk in iter_size_chunks(self._sizes_for, self._n):
            for s in chunk:
                yield int(s)

    def items(self):
        return zip(self.keys(), self.values())


class VirtualFederatedDataset:
    """A population that is sampled into existence, never resident.

    ``gen(cid) -> (x, y)`` produces a client's train shard on demand
    (None for store-backed corpora, where shards must already exist on
    disk); ``sizes_for(cids) -> int64[len(cids)]`` is the vectorized
    per-client sample count (callable, or an array/memmap indexed
    directly). Packing semantics (pad-and-mask, cohort pow-2 buckets)
    are IDENTICAL to ``FederatedDataset`` so the compiled round programs
    cannot tell the two apart.
    """

    def __init__(self, client_num: int, class_num: int,
                 sizes_for, gen: Optional[Callable] = None,
                 store: Optional[ClientStateStore] = None,
                 test_global: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                 eval_clients: int = 64, eval_cap: int = 4096,
                 name: str = "virtual"):
        self.client_num = int(client_num)
        self.class_num = int(class_num)
        self.name = name
        self._sizes = sizes_for
        self.gen = gen
        self.store = store if store is not None else ClientStateStore()
        for f in ("train_x", "train_y"):
            # respect a factory's earlier persistence decision (e.g. a
            # generative population whose state_dir persists shards as a
            # cross-run cache); default: persist iff there is no
            # generator to fall back on
            if not self.store.field_registered(f):
                self.store.register_field(f, persist=gen is None)
        self._eval_clients = min(int(eval_clients), self.client_num)
        self._eval_cap = int(eval_cap)
        self._test_global = test_global
        self._train_global = None
        self._max_samples: Optional[int] = None
        self._total_samples: Optional[int] = None
        # pack_clients runs on the prefetch worker concurrently with main-
        # thread eval-union builds; the store has its own lock, this one
        # guards the dataset-level lazy caches
        self._lock = threading.Lock()
        self.train_data_local_num_dict = _LazySizeDict(self.client_num,
                                                       self.sizes_for)

    # -- sizes -------------------------------------------------------------
    def sizes_for(self, cids) -> np.ndarray:
        cids = np.asarray(cids)
        if callable(self._sizes):
            return np.asarray(self._sizes(cids), dtype=np.int64)
        if not len(cids):
            return np.zeros(0, np.int64)
        # index FIRST, convert after: a dtype-converting asarray on the
        # whole backing array would copy the full O(N) index (and read
        # the entire memmap file) on every cohort lookup
        return np.asarray(self._sizes[cids], dtype=np.int64)

    def _scan_sizes(self, reduce_fn):
        out = None
        for s in iter_size_chunks(self.sizes_for, self.client_num):
            v = reduce_fn(s)
            out = v if out is None else reduce_fn(np.asarray([out, v]))
        return out

    @property
    def max_client_samples(self) -> int:
        with self._lock:
            if self._max_samples is None:
                self._max_samples = int(self._scan_sizes(np.max))
            return self._max_samples

    @property
    def train_data_num(self) -> int:
        with self._lock:
            if self._total_samples is None:
                self._total_samples = int(self._scan_sizes(np.sum))
            return self._total_samples

    @property
    def test_data_num(self) -> int:
        xt, _ = self.test_data_global
        return len(xt)

    # -- padding (same formulas as data/base.py, sizes vectorized) ---------
    def padded_len(self, batch_size: Optional[int]) -> int:
        n = self.max_client_samples
        if not batch_size:
            return n
        return ((n + batch_size - 1) // batch_size) * batch_size

    def cohort_padded_len(self, client_idxs,
                          batch_size: Optional[int]) -> int:
        n = int(self.sizes_for(np.asarray(client_idxs,
                                          dtype=np.int64)).max())
        b = batch_size or 1
        nb = (n + b - 1) // b
        bucket = 1 << max(0, (nb - 1).bit_length())
        return min(bucket * b, self.padded_len(batch_size))

    # -- shards ------------------------------------------------------------
    def _client_shard(self, cid: int) -> Tuple[np.ndarray, np.ndarray]:
        """One client's (x, y) through the store tiers: RAM hit, disk
        shard read, or generated (and LRU-cached) on a full miss."""
        cid = int(cid)
        try:
            return (self.store.get("train_x", cid),
                    self.store.get("train_y", cid))
        except KeyError:
            if self.gen is None:
                raise KeyError(
                    f"store-backed population has no shard for client "
                    f"{cid} (corpus incomplete under "
                    f"{self.store.state_dir!r})") from None
        x, y = self.gen(cid)
        self.store.put("train_x", cid, x)
        self.store.put("train_y", cid, y)
        return x, y

    def pack_clients(self, client_idxs, batch_size: Optional[int] = None,
                     n_pad: Optional[int] = None, out=None):
        """Streaming cohort materialization: fetch each sampled client's
        shard through the store and place it into the padded-and-masked
        ``[P, n_pad, ...]`` round input. Memory: the cohort block plus
        whatever the LRU holds — never the population. ``out=(x, y,
        mask)`` as in ``FederatedDataset.pack_clients``: the caller's
        buffers, every byte of them rewritten."""
        n_pad = n_pad or self.padded_len(batch_size)
        with self.store.pinned("train_x", client_idxs), \
                self.store.pinned("train_y", client_idxs):
            x0, y0 = self._client_shard(client_idxs[0])
            P = len(client_idxs)
            x, y, mask = pack_buffers(P, n_pad, x0, y0, out, alloc=np.zeros)
            if out is not None:
                for a in out:
                    a.fill(0)
            for i, c in enumerate(client_idxs):
                cx, cy = (x0, y0) if i == 0 else self._client_shard(c)
                n = len(cx)
                if n > n_pad:
                    raise ValueError(
                        f"client {c} has {n} samples > n_pad={n_pad}")
                if n != len(cy):
                    raise ValueError(f"client {c}: {n} samples but "
                                     f"{len(cy)} labels")
                x[i, :n], y[i, :n], mask[i, :n] = cx, cy, 1.0
        return x, y, mask

    def client_weights(self, client_idxs) -> np.ndarray:
        return self.sizes_for(
            np.asarray(client_idxs, dtype=np.int64)).astype(np.float32)

    # -- eval unions (fixed seeded cohort, NOT the full population) --------
    def _eval_ids(self) -> np.ndarray:
        """Evenly strided eval cohort: deterministic, spans the size
        distribution, and independent of the per-round sampling stream."""
        stride = max(1, self.client_num // self._eval_clients)
        return np.arange(self._eval_clients, dtype=np.int64) * stride

    @property
    def train_data_global(self) -> Tuple[np.ndarray, np.ndarray]:
        """At population scale the 'global train union' is a FIXED seeded
        eval cohort's union, capped at ``eval_cap`` samples — evaluating
        10^6 clients' union would cost more than the training it
        measures (the reference subsamples evaluation the same way,
        fedavg_api.py:115)."""
        with self._lock:
            if self._train_global is None:
                xs, ys, left = [], [], self._eval_cap
                for c in self._eval_ids():
                    cx, cy = self._client_shard(int(c))
                    take = min(len(cx), left)
                    xs.append(cx[:take])
                    ys.append(cy[:take])
                    left -= take
                    if left <= 0:
                        break
                self._train_global = (np.concatenate(xs),
                                      np.concatenate(ys))
            return self._train_global

    @property
    def test_data_global(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._test_global is None:
            xg, yg = self.train_data_global
            self._test_global = (xg[:0], yg[:0])
        return self._test_global


def make_virtual_powerlaw_population(
    client_num: int = 1_000_000,
    dim: int = 32,
    class_num: int = 10,
    seed: int = 0,
    min_samples: int = 10,
    max_samples: int = 400,
    alpha: float = 1.3,
    noise: float = 1.0,
    state_dir: Optional[str] = None,
    cache_clients: int = 4096,
    test_samples: int = 1024,
    eval_clients: int = 64,
) -> VirtualFederatedDataset:
    """The virtual twin of ``make_powerlaw_blob_federated``: gaussian-blob
    clients with LEAF-style power-law sizes, at any population, generated
    client-by-client on demand. Content is a pure function of
    (seed, client id), so the same cohort packs the same bytes whether it
    was generated fresh, LRU-cached, or (with ``state_dir``) read back
    from shard files: ``state_dir`` makes the generated shards a
    PERSISTENT cross-run cache — clients a run touches are written back
    on eviction/flush, and a later run with the same ``state_dir`` reads
    them from disk instead of regenerating (bit-identical either way;
    only touched clients occupy disk, never the population)."""
    master = np.random.RandomState(seed)
    centers = master.randn(class_num, dim) * 3.0

    def sizes_for(cids):
        return pareto_sizes(cids, seed, min_samples, max_samples, alpha)

    def gen(cid: int):
        rng = np.random.RandomState(
            int(_mix64(np.asarray([cid], np.uint64)
                       ^ np.uint64(seed * 0x9E3779B9 & _M64))[0]
                % (2 ** 31 - 1)))
        n = int(sizes_for(np.asarray([cid]))[0])
        y = rng.randint(0, class_num, n).astype(np.int32)
        x = (centers[y] + noise * rng.randn(n, dim)).astype(np.float32)
        return x, y

    # held-out test union from the SAME blob distribution, disjoint stream
    trng = np.random.RandomState(seed + 9973)
    yt = trng.randint(0, class_num, test_samples).astype(np.int32)
    xt = (centers[yt] + noise * trng.randn(test_samples, dim)
          ).astype(np.float32)

    # one-client shards: generated entries are sparse over a huge id
    # space, so shard granularity must equal client granularity for the
    # cache budget to mean what the flag says (disk corpora use fat
    # shards instead — there a shard read amortizes one file open).
    # With a state_dir the generated shards persist as a cross-run
    # cache; without one they are a RAM-only LRU (regenerable content,
    # nothing ever written — the bench's O(cache)-RSS configuration)
    store = ClientStateStore(state_dir, shard_clients=1,
                             cache_clients=cache_clients)
    store.register_field("train_x", persist=state_dir is not None)
    store.register_field("train_y", persist=state_dir is not None)
    return VirtualFederatedDataset(
        client_num, class_num, sizes_for, gen=gen, store=store,
        test_global=(xt, yt), eval_clients=eval_clients,
        name=f"virtual_powerlaw_{client_num}")


# -- store-backed corpora (streaming builders write, this loads) -----------
def write_federation_store(
    state_dir: str,
    stream: Iterable[Tuple[int, Tuple[np.ndarray, np.ndarray],
                           Optional[Tuple[np.ndarray, np.ndarray]]]],
    class_num: int,
    shard_clients: int = 256,
    cache_clients: int = 1024,
) -> int:
    """Consume a streaming builder — ``(cid, (xtr, ytr), (xte, yte) |
    None)`` per client — into shard files + a memory-mapped sizes index.
    Peak memory is O(cache), never O(population): the store's LRU
    write-back flushes full shards to disk as the stream advances.
    Returns the client count."""
    import os

    store = ClientStateStore(state_dir, shard_clients=shard_clients,
                             cache_clients=cache_clients)
    for f in ("train_x", "train_y", "test_x", "test_y"):
        store.register_field(f, persist=True)
    sizes = []
    n = 0
    for cid, (xtr, ytr), test in stream:
        if cid != n:
            # sizes.npy is indexed BY CLIENT ID at load time; an
            # out-of-order or gapped stream would silently misalign
            # every weight and pad bound downstream
            raise ValueError(
                f"write_federation_store requires a dense in-order "
                f"stream: expected client {n}, got {cid}")
        store.put("train_x", cid, np.ascontiguousarray(xtr))
        store.put("train_y", cid, np.ascontiguousarray(ytr))
        if test is not None and len(test[0]):
            store.put("test_x", cid, np.ascontiguousarray(test[0]))
            store.put("test_y", cid, np.ascontiguousarray(test[1]))
        sizes.append(len(xtr))
        n += 1
    store.flush()
    np.save(os.path.join(state_dir, "sizes.npy"),
            np.asarray(sizes, dtype=np.int32))
    with open(os.path.join(state_dir, "meta.json"), "w") as f:
        json.dump({"client_num": n, "class_num": int(class_num),
                   "shard_clients": shard_clients}, f)
    return n


def load_federation_store(state_dir: str, cache_clients: int = 4096,
                          eval_clients: int = 64) -> VirtualFederatedDataset:
    """Open a corpus ``write_federation_store`` emitted: shards stay on
    disk behind the LRU, the sizes index is an mmap (the one O(N) file is
    not resident), the test union is the eval cohort's stored test
    shards."""
    import os

    with open(os.path.join(state_dir, "meta.json")) as f:
        meta = json.load(f)
    sizes = np.load(os.path.join(state_dir, "sizes.npy"), mmap_mode="r")
    store = ClientStateStore(state_dir,
                             shard_clients=meta.get("shard_clients", 256),
                             cache_clients=cache_clients)
    ds = VirtualFederatedDataset(
        meta["client_num"], meta["class_num"], sizes, gen=None,
        store=store, eval_clients=eval_clients,
        name=f"store:{os.path.basename(os.path.normpath(state_dir))}")
    # test union: the eval cohort's held-out shards, read once
    xs, ys = [], []
    for c in ds._eval_ids():
        try:
            xs.append(store.get("test_x", int(c)))
            ys.append(store.get("test_y", int(c)))
        except KeyError:
            continue  # single-sample clients have empty test splits
    if xs:
        ds._test_global = (np.concatenate(xs), np.concatenate(ys))
    return ds


# -- measurement harness (bench legs + CI smoke) ---------------------------
def _run_population_leg(population: int, rounds: int, cohort: int,
                        mode: str, batch_size: int, dim: int,
                        cache_clients: int, state_dir: Optional[str],
                        seed: int) -> Dict:
    """One population leg in THIS process: build the dataset, run FedAvg
    rounds, report rounds/sec + peak RSS + store counters. bench.py runs
    each leg in its own subprocess so ru_maxrss high-water marks don't
    bleed across legs."""
    import time

    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    t_build = time.perf_counter()
    vds = make_virtual_powerlaw_population(
        client_num=population, dim=dim, class_num=10, seed=seed,
        state_dir=state_dir, cache_clients=cache_clients)
    if mode == "resident":
        # the baseline leg: the IDENTICAL population materialized into
        # resident dicts (same per-client bytes, same sampling stream,
        # same packing), so the rounds/sec delta isolates the store
        # machinery — not a dataset-shape difference
        from fedml_tpu.data.base import FederatedDataset
        ds = FederatedDataset.from_client_arrays(
            {c: vds.gen(c) for c in range(population)},
            {c: None for c in range(population)}, vds.class_num)
    else:
        ds = vds
    build_s = time.perf_counter() - t_build

    api = FedAvgAPI(ds, LogisticRegression(num_classes=10),
                    config=FedAvgConfig(
                        comm_round=rounds + 1, client_num_per_round=cohort,
                        frequency_of_the_test=10 ** 9, seed=seed,
                        train=TrainConfig(epochs=1, batch_size=batch_size,
                                          lr=0.05)))
    # warm every cohort bucket shape outside the timed window (bounded:
    # <= log2 distinct shapes), same protocol as bench_powerlaw_1000
    from fedml_tpu.core.sampling import sample_clients
    warmed = set()
    for r in range(rounds + 1):
        n_pad = ds.cohort_padded_len(
            sample_clients(r, ds.client_num, cohort), batch_size)
        if n_pad not in warmed:
            warmed.add(n_pad)
            api.run_round(r)
    jax.block_until_ready(api.variables)
    t0 = time.perf_counter()
    for r in range(1, rounds + 1):
        api.run_round(r)
    jax.block_until_ready(api.variables)
    wall = time.perf_counter() - t0
    api.timer.update_rss()
    store_stats = (ds.store.stats() if hasattr(ds, "store") else {})
    sb = (store_stats.get("state_bytes_read", 0)
          + store_stats.get("state_bytes_written", 0))
    return {
        "population": population,
        "mode": mode,
        "rounds_timed": rounds,
        "rounds_per_sec": round(rounds / max(wall, 1e-9), 3),
        "build_s": round(build_s, 3),
        "host_rss_peak_mb": round(RoundTimer.host_rss_mb(), 1),
        "state_bytes_per_round": round(sb / max(1, rounds), 1),
        **store_stats,
    }


def main(argv=None) -> int:
    import argparse

    from fedml_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    p = argparse.ArgumentParser("python -m fedml_tpu.state.population")
    p.add_argument("--population", type=int, default=100_000)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--cohort", type=int, default=10)
    p.add_argument("--mode", choices=["virtual", "resident"],
                   default="virtual")
    p.add_argument("--batch_size", type=int, default=10)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--state_cache_clients", type=int, default=4096)
    p.add_argument("--state_dir", type=str, default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    out = _run_population_leg(
        args.population, args.rounds, args.cohort, args.mode,
        args.batch_size, args.dim, args.state_cache_clients,
        args.state_dir, args.seed)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
