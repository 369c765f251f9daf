"""FedAvg — the flagship algorithm, as one compiled round program.

Reference semantics (kept exactly): per-round seeded client sampling
(FedAVGAggregator.py:89-97), local SGD from the current global model
(FedAVGTrainer/MyModelTrainer), sample-weighted averaging of the full model
state (FedAVGAggregator.py:58-87), periodic evaluation over the federation
(fedavg_api.py:142-207).

TPU-first re-design (SURVEY §7): the reference runs clients as MPI processes
(distributed) or a sequential Python loop (standalone). Here one round =

    vmap over sampled clients ( local_train: lax.scan over epochs x batches )
    -> tree_weighted_mean over the client axis

compiled once; the same round body runs under ``shard_map`` on a device mesh
for the distributed path (fedml_tpu/parallel/spmd.py), where the weighted
mean lowers to a ``psum`` over ICI. Client heterogeneity (ragged LEAF sizes)
is handled by pad-and-mask packing (data/base.py), client virtualization
(total clients >> per-round slots) by re-pointing each slot at its sampled
client's shard every round — the same trick as the reference's
``update_dataset`` (FedAVGTrainer.py:25-30).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core import pytree as pt
from fedml_tpu.core.sampling import (DEVICE_SAMPLE_SENTINEL, eval_subsample,
                                     round_keys, sample_clients)
from fedml_tpu.data.base import NATIVE_PACK_FLOOR_BYTES, FederatedDataset
from fedml_tpu.trainer.functional import (TrainConfig, make_eval,
                                          make_local_train, real_batches,
                                          round_lr_scale)

#: per-round heartbeat for long host loops (the eval records land only every
#: frequency_of_the_test rounds, which leaves multi-minute CPU rounds
#: invisible); scoped to its own logger so callers can silence it alone
_progress_log = logging.getLogger("fedml_tpu.progress")

#: clients a tier of a ragged cohort holds (see ``make_vmapped_body``);
#: chosen once on the chip, PERF.md section 6 "PR 27" has the sweep
TIER_CLIENTS = 32


def cohort_tiers(clients: int, tier_clients: Optional[int]) -> int:
    """How many tiers the round body cuts a cohort of ``clients`` into: as
    many whole tiers of ``tier_clients`` as it holds if those are at least
    two and leave nobody over, else 1 (the untiered body)."""
    if not tier_clients or clients % tier_clients:
        return 1
    return max(1, clients // tier_clients)  # an empty cohort: one, too


def make_vmapped_clients(local_train, tier_clients: Optional[int] = None,
                         train: Optional[TrainConfig] = None):
    """vmap local training over the client axis: ``(variables, x, y, mask,
    keys, lr_scale=None) -> (stacked clients, per-client stats)``. The one
    place the cohort is trained side by side: ``make_vmapped_body`` sums
    its stats for a single device, the mesh rounds of ``parallel/spmd.py``
    run it on each chip's shard of the cohort and sum after their ``psum``
    mean. ``lr_scale`` (optional scalar, broadcast to every client)
    applies TrainConfig.lr_decay_round's per-round schedule; None traces
    the identical constant-LR program as before.

    ``tier_clients`` (with ``train``, the config ``local_train`` was built
    from) is for ragged federations: a cohort that ``cohort_tiers`` cuts
    into T > 1 equal slices of the client axis trains them one after
    another inside the same program, each tier's step loop ending at the
    last real batch of its longest client (a bound read from ``mask`` on
    the device, so the shapes and the compiled program do not depend on the
    round). The batches left out are the pure-padding ones ``local_train``
    gates into no-ops, so the result is exact in any client order; packed
    by size (``FedAvgAPI._size_ordered``) the short tiers stop early."""

    def train_clients(variables, x, y, mask, keys, lr_scale, n_steps=None):
        # lr_scale=None traces the identical constant-LR program
        # (local_train skips the multiply at trace time), so one vmap
        # covers both the scheduled and unscheduled paths; n_steps=None
        # likewise keeps the whole-length scan
        return jax.vmap(
            lambda v, xc, yc, mc, kc: local_train(
                v, xc, yc, mc, kc, lr_scale=lr_scale, n_steps=n_steps),
            in_axes=(None, 0, 0, 0, 0))(variables, x, y, mask, keys)

    def clients(variables, x, y, mask, keys, lr_scale=None):
        tiers = cohort_tiers(x.shape[0], tier_clients)
        if tiers == 1:
            return train_clients(variables, x, y, mask, keys, lr_scale)

        def tier(inp):
            xt, yt, mt, kt = inp
            # unbatched under the vmap: its loop stays one ``while``
            n_steps = jnp.max(jax.vmap(
                lambda m: real_batches(m, train))(mt))
            return train_clients(variables, xt, yt, mt, kt, lr_scale,
                                 n_steps)

        def split(a):
            return a.reshape((tiers, a.shape[0] // tiers) + a.shape[1:])

        def join(a):
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

        return jax.tree.map(join, jax.lax.map(
            tier, jax.tree.map(split, (x, y, mask, keys))))

    return clients


def make_vmapped_body(local_train, tier_clients: Optional[int] = None,
                      train: Optional[TrainConfig] = None):
    """``make_vmapped_clients`` with the stats summed over the cohort:
    ``(stacked clients, stat totals)`` - the shared round body every
    FedAvg-family algorithm composes with its own aggregation rule."""
    clients = make_vmapped_clients(local_train, tier_clients, train)

    def body(variables, x, y, mask, keys, lr_scale=None):
        stacked, stats = clients(variables, x, y, mask, keys, lr_scale)
        return stacked, jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)

    return body


def make_folded_body(local_train, interpret: bool = False):
    """The round body for models a cohort cannot hold a copy each of: the
    clients train *one after another* inside the one round program (a
    ``lax.scan`` over the client axis), every one from the global model
    with ``local_train``'s own steps, and each result is folded into a
    running float32 sum with weight ``n_k / sum n`` by the Pallas kernel
    that updates the sum in place (``ops/aggregate.py::tree_fold_pallas``).
    The global model has to outlive every client but the last, so the body
    tells ``local_train`` that its start is shared (``shared_init``): a
    client's first step reads the global leaves and writes the client's
    own, where a step loop started on them would have each leaf copied
    first, once a client (8 bytes a parameter, 38-44 ms a round of the 3 GB
    language models).
    After the last client the sum is the FedAvg mean, so the body returns
    ``(new variables, stat totals)`` - aggregation included, where
    ``make_vmapped_body`` hands back the stacked clients. The device holds
    the global model, the sum, one training client and its gradients,
    whatever the cohort."""
    from fedml_tpu.ops.aggregate import tree_fold_pallas

    def body(variables, x, y, mask, keys, weights, lr_scale=None):
        share = weights.astype(jnp.float32)
        share = share / jnp.sum(share)

        def client(acc, inp):
            xc, yc, mc, kc, wc = inp
            result, stats = local_train(variables, xc, yc, mc, kc,
                                        lr_scale=lr_scale, shared_init=True)
            return tree_fold_pallas(acc, result, wc,
                                    interpret=interpret), stats

        zeros = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32),
                             variables)
        acc, stats = jax.lax.scan(client, zeros, (x, y, mask, keys, share))
        new_vars = jax.tree.map(lambda a, v: a.astype(v.dtype), acc,
                                variables)
        return new_vars, jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)

    return body


def _normalized(stats, prefix: str) -> Dict[str, float]:
    """Stat sums -> {prefix}_{acc,loss,total} means (+precision/recall)."""
    total = max(1.0, float(stats["count"]))
    out = {
        f"{prefix}_acc": float(stats["correct_sum"]) / total,
        f"{prefix}_loss": float(stats["loss_sum"]) / total,
        f"{prefix}_total": float(stats["count"]),
    }
    if "precision_sum" in stats:
        out[f"{prefix}_precision"] = float(stats["precision_sum"]) / total
        out[f"{prefix}_recall"] = float(stats["recall_sum"]) / total
    return out


@dataclasses.dataclass(frozen=True)
class FedAvgConfig:
    """Round-level knobs (reference argparse: --comm_round
    --client_num_in_total --client_num_per_round --frequency_of_the_test)."""

    comm_round: int = 10
    client_num_per_round: int = 10
    frequency_of_the_test: int = 5
    seed: int = 0
    # evaluate train metrics on a fixed seeded subsample of the global train
    # union instead of sweeping all of it every test round (the reference
    # subsamples evaluation the same way for its largest federation,
    # fedavg_api.py:115 _generate_validation_set). None = full union.
    eval_train_subsample: Optional[int] = None
    # same knob for the test union (reference subsamples only train, but
    # its test sets fit a GPU; the flagship-scale generated test unions do
    # not fit a CPU eval budget — seeded via core.sampling.eval_subsample
    # so sim and mesh drivers score the identical subset). None = full.
    eval_test_subsample: Optional[int] = None
    # padding policy for the per-round client pack: "cohort" pads to the
    # sampled cohort's pow-2 bucket (data/base.py cohort_padded_len — big
    # FLOP win on power-law federations, a few extra compiles), "global"
    # pads every round to the dataset-wide max (one compile ever). Full
    # participation produces identical shapes either way.
    pack: str = "cohort"
    # async round pipeline (parallel/prefetch.py): pack + upload round r+1
    # on a background thread while round r's dispatch executes, holding at
    # most this many cohorts in flight (2 = double buffering; 0 = today's
    # serial path; $FEDML_TPU_PREFETCH overrides). Sampling is a pure
    # function of the round index, so the pipelined trajectory is
    # bit-identical to the serial one. Only engages for partial
    # participation — full participation already reuses the resident
    # _pack_cache cohort.
    prefetch_depth: int = 2
    # fold the cohort client by client (make_folded_body) instead of
    # training it under one vmap and averaging the stacked results: for
    # models whose copies a cohort cannot hold side by side. Peak device
    # memory is then four copies of the model and one client's
    # activations, whatever the cohort; clients run one after another.
    # Not for aggregate_hook users, whose hook reads the stacked clients.
    fold_clients: bool = False
    # observability (fedml_tpu/obs): directory for the flight recorder's
    # per-round timeline (flight_rank0.jsonl) + anomaly-armed one-shot
    # profiles. None (default) = off; on, it is a pure observer —
    # trajectories stay bit-exact (test_obs.py pins this).
    obs_dir: Optional[str] = None
    # flight-record correlation id; unset derives a collision-safe
    # "sim-<8 hex>" per run (obs.default_job_id)
    job_id: Optional[str] = None
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)


class FedAvgAPI:
    """Standalone simulation API (parity:
    fedml_api/standalone/fedavg/fedavg_api.py), all clients vmapped - and
    the one round driver: the host half of a round (sampling, the cohort's
    order, pack, upload, keys, prefetcher, counters, the loop) is written
    here only. ``parallel/spmd.py::DistributedFedAvgAPI`` places it on a
    mesh by overriding the seams below (placement, cohort padding,
    programs, evaluation)."""

    #: prefix of the flight-record job id a run derives when none is set
    _job_prefix = "sim"
    #: the attributes that hold a (prefetcher, dataset-at-build) pair once
    #: built; ``prefetch_stats`` / ``release_prefetch`` read all of them
    _prefetch_slots = ("_prefetch",)

    def __init__(self, dataset: FederatedDataset, module,
                 task: str = "classification",
                 config: Optional[FedAvgConfig] = None,
                 delete_client: Optional[int] = None,
                 aggregate_hook=None):
        """``aggregate_hook(variables, stacked, weights, key) -> new_vars``
        customizes server aggregation (e.g. robust defenses) while keeping
        one round body; default is the sample-weighted mean."""
        self.dataset = dataset
        self.module = module
        self.task = task
        self.config = config or FedAvgConfig()
        self.delete_client = delete_client
        cfg = self.config.train

        from fedml_tpu.trainer.functional import validate_accum_steps
        validate_accum_steps(cfg, dataset.train_data_local_num_dict)
        if self.config.pack not in ("cohort", "global"):
            raise ValueError(f"unknown pack policy: {self.config.pack!r}")
        self._n_pad = dataset.padded_len(cfg.batch_size)
        self._base_key = jax.random.key(self.config.seed)
        from fedml_tpu.utils.tracing import RoundTimer
        self.timer = RoundTimer()

        sample_x = jnp.asarray(dataset.train_data_global[0][:1])
        self.variables = module.init(jax.random.key(self.config.seed),
                                     sample_x, train=False)
        # how much of the model the local step never reads at this
        # federation's row shape (models/common.py::LiveTapConv)
        from fedml_tpu.models.common import dead_tap_params
        self.timer.count("conv_dead_tap_params", dead_tap_params(
            module, self.variables, sample_x))
        # and how much of it a client's local loop carries: the rest stays
        # at the global model's value (trainer/functional.py)
        from fedml_tpu.trainer.functional import carried_params
        self.timer.count("local_carried_params", carried_params(
            module, cfg, self.variables, sample_x))
        self._build_programs(aggregate_hook)
        self.history: List[Dict] = []
        # packed-cohort cache: when a round samples the same client set
        # (e.g. full participation), skip host packing and re-upload — the
        # device-side analogue of the reference's update_dataset re-pointing
        # (FedAVGTrainer.py:25-30)
        self._pack_cache = None
        # eval arrays live on device across test rounds (re-uploading the
        # global unions every evaluation dominated host time on image sets)
        self._eval_cache = None
        # cohort prefetcher (parallel/prefetch.py), built lazily on the
        # first partial-participation round; (prefetcher, dataset-at-build)
        self._prefetch = None
        # host buffers of packed cohorts, recycled: what the prefetcher's
        # depth can have in the packer's hands at once, and the round
        # thread's on a miss
        from fedml_tpu.parallel.prefetch import (PackBufferPool,
                                                 resolve_prefetch_depth)
        self._pack_pool = PackBufferPool(1 + resolve_prefetch_depth(
            getattr(self.config, "prefetch_depth", 0)))
        # virtualized populations (fedml_tpu/state/) front the per-client
        # shards with a tiered store; binding its counters here puts
        # state_cache_hits/misses/evictions + state_bytes_read/written on
        # the same evidence row as the phase timings
        store = getattr(dataset, "store", None)
        if store is not None and hasattr(store, "bind_timer"):
            store.bind_timer(self.timer)
        # observability (fedml_tpu/obs): flight recorder + slow-round
        # anomaly profiling; config.obs_dir None (default) keeps this
        # fully off
        from fedml_tpu.obs import build_observability, default_job_id
        devices = self._round_devices()
        self._obs = build_observability(
            getattr(self.config, "obs_dir", None),
            # collision-safe default: two unconfigured runs sharing an
            # obs dir must not interleave under one literal id
            job_id=(getattr(self.config, "job_id", None)
                    or default_job_id(self._job_prefix)),
            rank=0, role="server",
            # fleet MFU denominator: every device the round program spans
            # (on a mesh all of data x fsdp x tp, not just the federation
            # axis), its kind read from one of them so a mixed host (CPU
            # coordinator + TPU mesh) rates the mesh
            perf_device_count=len(devices), perf_device=devices[0])
        if self._obs is not None:
            self._obs.bind_timer(self.timer)

    # -- the seams a mesh overrides ----------------------------------------
    def _build_programs(self, aggregate_hook) -> None:
        """Programs: build ``_round_fn`` and ``_eval_fn``; ``self.variables``
        is the fresh model and the timer is there for counters."""
        cfg = self.config.train
        module, task = self.module, self.task
        self._local_train = make_local_train(module, task, cfg)
        # tiers engage where they can save steps: clients that differ in
        # their number of batches (and a cohort of at least two tiers,
        # which the body reads from its input's shape)
        ragged = cfg.batch_size and len(
            {-(-n // cfg.batch_size)
             for n in self.dataset.train_data_local_num_dict.values()}) > 1
        self._tier_clients = TIER_CLIENTS if ragged else None
        self._vmapped_body = make_vmapped_body(
            self._local_train, self._tier_clients, cfg)
        from fedml_tpu.utils import on_tpu
        if aggregate_hook is not None:
            hook = aggregate_hook
        elif on_tpu():
            # the mean leaf by leaf, the wide leaves through a Pallas kernel
            # that reads them as the trainer left them
            # (fedml_tpu/ops/aggregate.py)
            from fedml_tpu.ops import tree_weighted_mean_pallas

            def hook(variables, stacked, weights, key):
                return tree_weighted_mean_pallas(stacked, weights)
        else:
            hook = (lambda variables, stacked, weights, key:
                    pt.tree_weighted_mean(stacked, weights))
        body = self._vmapped_body

        def round_fn(variables, x, y, mask, keys, weights, agg_key,
                     round_idx):
            stacked, totals = body(variables, x, y, mask, keys,
                                   round_lr_scale(cfg, round_idx))
            new_vars = hook(variables, stacked, weights, agg_key)
            return new_vars, totals

        if self.config.fold_clients:
            if aggregate_hook is not None:
                raise ValueError(
                    "fold_clients folds every client into a running sum as "
                    "it finishes; an aggregate_hook reads the stacked "
                    "clients, which the folded round never holds")
            folded = make_folded_body(self._local_train,
                                      interpret=not on_tpu())

            def round_fn(variables, x, y, mask, keys, weights,  # noqa: F811
                         agg_key, round_idx):
                del agg_key  # the plain weighted mean draws nothing
                return folded(variables, x, y, mask, keys, weights,
                              round_lr_scale(cfg, round_idx))

        # unjitted round body, shared with FusedRounds so the fused and
        # host paths cannot diverge semantically
        self._round_fn_py = round_fn

        # donate the variables buffer: the old global model is dead the
        # moment the round closes, so XLA reuses its HBM for the new one
        # instead of holding both live (free bandwidth on big models)
        self._round_fn = jax.jit(round_fn, donate_argnums=(0,))
        self._eval_fn = jax.jit(make_eval(module, task))
        if (aggregate_hook is None and on_tpu()
                and not self.config.fold_clients):
            # how much of the model the stacked mean's kernel takes
            from fedml_tpu.ops import mean_kernel_params
            kernel, xla = mean_kernel_params(
                self.variables, self.config.client_num_per_round)
            self.timer.count("agg_kernel_params", kernel)
            self.timer.count("agg_xla_params", xla)

    def _round_devices(self) -> list:
        """The devices the round program spans."""
        return jax.devices()[:1]

    def _put(self, a):
        """Placement: a packed host array, or the round's per-client keys,
        onto the device(s) as the round program takes them."""
        return jnp.asarray(a)

    def _pad_round(self, idxs):
        """Cohort padding: ``(slots, alive)``, the clients the round's
        slots hold and a 0/1 weight a slot (None: every slot is a sampled
        client, the case on one device)."""
        return idxs, None

    def _round_inputs(self, x, y, mask, keys, weights, agg_key) -> tuple:
        """Of a round's placed inputs, those ``_round_fn`` takes."""
        return x, y, mask, keys, weights, agg_key

    def _round_operands(self, args: tuple, round_idx: int) -> tuple:
        """``_round_fn``'s operands after the model: ``_pack_round``'s
        inputs and the round index (the rate's decay)."""
        return args + (jnp.uint32(round_idx),)

    # -- one round ---------------------------------------------------------
    def _size_ordered(self, idxs, dataset):
        """The cohort in the order it is packed: longest client first where
        the round body runs it in tiers (so each tier's loop ends early),
        as sampled otherwise. Keys and weights follow the client, not the
        slot, so the order changes only the mean's summation order."""
        if cohort_tiers(len(idxs), self._tier_clients) == 1:
            return idxs
        idxs = np.asarray(idxs)
        sizes = dataset.train_data_local_num_dict
        return idxs[np.argsort([-sizes[int(c)] for c in idxs],
                               kind="stable")]

    def _rows_stepped(self, slots, n_pad: int) -> int:
        """The rows the round program steps through, padding and all, from
        the padded slot list (``_pad_round``'s, duplicates included): every
        slot's padded length, or per tier its clients x its longest
        client's batches x the batch size (what the body's bound comes to,
        reckoned from the sizes the packer holds)."""
        tiers = cohort_tiers(len(slots), self._tier_clients)
        if tiers == 1:
            return len(slots) * n_pad
        bsz = self.config.train.batch_size
        sizes = self.dataset.train_data_local_num_dict
        steps = np.array([-(-sizes[int(c)] // bsz)
                          for c in slots]).reshape(tiers, -1).max(axis=1)
        return int(steps.sum()) * (len(slots) // tiers) * bsz

    def _pack_cohort(self, idxs, ds):
        """Cache-free pad + pack + upload of one sampled cohort of ``ds``:
        ``(slots, (x, y, mask, weights))`` (thread-safe: the one shared
        state is the locked buffer pool — the prefetcher worker calls this
        concurrently with the main thread's dispatch).

        A cohort the native packer serves (``NATIVE_PACK_FLOOR_BYTES``) is
        packed into a host triple from ``_pack_pool`` where one is free,
        and its triple goes (back) to the pool once the upload has read
        it, less any array that a placed one shares memory with; smaller
        cohorts allocate and enqueue as ever."""
        from fedml_tpu.parallel.prefetch import aliases_host
        cfg = self.config
        with self.timer.phase("pack"):
            slots, alive = self._pad_round(idxs)
            n_pad = (ds.cohort_padded_len(slots, cfg.train.batch_size)
                     if cfg.pack == "cohort" else self._n_pad)
            key = (len(slots), n_pad)
            bufs = self._pack_pool.take(ds, key)
            try:
                x, y, mask = ds.pack_clients(slots, cfg.train.batch_size,
                                             n_pad=n_pad, out=bufs)
            except ValueError:
                if bufs is None:
                    raise
                # not the buffers this cohort wants (clients of another
                # dtype), or a bad cohort: the fresh call says which
                bufs = None
                x, y, mask = ds.pack_clients(slots, cfg.train.batch_size,
                                             n_pad=n_pad)
            self.timer.count("pack_buffers_fresh" if bufs is None
                             else "pack_buffers_recycled")
            weights = ds.client_weights(slots)
            if alive is not None:  # zero-weight duplicate slots
                np.multiply(mask, alive[:, None], out=mask)
                weights = weights * alive
        with self.timer.phase("upload"):
            placed = (self._put(x), self._put(y), self._put(mask),
                      self._put(weights))
            if x.nbytes >= NATIVE_PACK_FLOOR_BYTES:
                # the triple may be written again once the transfers have
                # read it: wait for them here, on the packer's thread
                # ft: allow[FT003] the upload's end, inside its own phase
                jax.block_until_ready(placed)
                # an array that a placed one shares memory with (the CPU
                # backend takes an aligned one as it is) stays that one's
                self._pack_pool.give(ds, key, tuple(
                    np.empty_like(host) if aliases_host(host, put) else host
                    for host, put in zip((x, y, mask), placed)))
            return slots, placed

    def _pack_round(self, round_idx: int):
        """The full host side of one round — seeded sampling, the cohort's
        order, pack (or the resident cohort), upload, per-client keys — as
        a pure function of the round index: ``(dataset, idxs, placed
        inputs)``. The prefetcher's ``produce`` *and* the serial path. The
        dataset reference is snapshot once so a concurrent mid-run swap can
        never mix two datasets' arrays inside one payload (the caller's
        identity check then discards the stale payload). ``_pack_cache`` is
        written only under full participation, where ``_round_prefetcher``
        builds no prefetcher: so only ever on the round thread."""
        ds = self.dataset
        with self.timer.phase("produce"):
            idxs = self._size_ordered(
                sample_clients(round_idx, ds.client_num,
                               self.config.client_num_per_round,
                               delete_client=self.delete_client), ds)
            # the key holds a strong reference to the dataset object
            # (mid-run swaps, e.g. escalating a poisoning attack, must
            # invalidate — and holding the reference prevents CPython
            # id-reuse false hits); cache only under full participation —
            # partial cohorts are seeded per round and would just pin dead
            # device buffers without ever hitting
            full = len(idxs) == ds.client_num
            cohort = tuple(int(i) for i in idxs) if full else None
            cache = self._pack_cache
            if (full and cache is not None and cache[0] is ds
                    and cache[1] == cohort):
                slots, (xd, yd, maskd, wd) = cache[2]
            else:
                if cache is not None:
                    self._pack_cache = None  # free the old buffers first
                slots, (xd, yd, maskd, wd) = self._pack_cohort(idxs, ds)
                if full:
                    self._pack_cache = (ds, cohort,
                                        (slots, (xd, yd, maskd, wd)))
                    # packed once: its host buffers are not worth keeping
                    self._pack_pool.clear()
            _, keys, agg_key = round_keys(
                self._base_key, round_idx,
                jnp.asarray(np.asarray(slots), dtype=jnp.uint32))
            keys = self._put(keys)
        return ds, idxs, self._round_inputs(xd, yd, maskd, keys, wd,
                                            agg_key)

    def _round_prefetcher(self):
        """The cohort prefetcher for the current config/dataset, or None
        when the serial path should run: depth 0 (flag or
        $FEDML_TPU_PREFETCH kill switch) or full participation (the
        resident ``_pack_cache`` already skips pack+upload there). A
        dataset swap invalidates every in-flight slot, exactly like
        ``_pack_cache``."""
        from fedml_tpu.parallel.prefetch import (RoundPrefetcher,
                                                 bind_prefetcher,
                                                 resolve_prefetch_depth)
        depth = resolve_prefetch_depth(
            getattr(self.config, "prefetch_depth", 0))
        # full participation keeps the resident _pack_cache — EXCEPT
        # under delete_client (leave-one-out), whose per-round-seeded
        # permuted cohorts never cache and so do want the pipeline
        if (depth <= 0 or (self.config.client_num_per_round
                           >= self.dataset.client_num
                           and self.delete_client is None)):
            if self._prefetch is not None:
                # kill switch flipped mid-run: free the resident slots
                # instead of pinning them until the API dies
                self._prefetch[0].invalidate()
            return None
        self._prefetch = bind_prefetcher(
            self._prefetch, self.dataset,
            lambda: RoundPrefetcher(self._pack_round, depth,
                                    name="fedavg-cohort-prefetch"))
        return self._prefetch[0]

    def _prefetchers(self) -> list:
        """Every prefetcher built so far (``_prefetch_slots``)."""
        return [pair[0] for pair in (getattr(self, slot)
                                     for slot in self._prefetch_slots)
                if pair is not None]

    def prefetch_stats(self):
        """Prefetcher counters (hits/misses/invalidated), summed over the
        prefetchers built, or None when the serial path ran — evidence
        hook for bench/tests; the durations are the timer's
        ``prefetch_wait`` and ``produce``."""
        out = None
        for pf in self._prefetchers():
            stats = pf.stats()
            out = stats if out is None else {k: out[k] + v
                                             for k, v in stats.items()}
        return out

    def release_prefetch(self):
        """Drop every speculative slot (their device buffers) without
        stopping the workers — for callers driving ``run_round`` (or a
        mesh's ``run_rounds_fused``) in patterns the ``comm_round``
        speculation clamp can't see."""
        for pf in self._prefetchers():
            pf.invalidate()
        self._pack_pool.clear()

    def fused_rounds(self, device_sampling: bool = False) -> "FusedRounds":
        """The fused multi-round driver PAIRED with this API class
        (subclasses fusing richer server state override
        ``_fused_driver_cls``; subclasses whose round leaves the device —
        e.g. secure aggregation — set it to None); always construct
        through here so an API cannot be mispaired with a driver that
        drops its server state."""
        if self._fused_driver_cls is None:
            raise TypeError(
                f"{type(self).__name__} cannot fuse rounds: its round has "
                "a host-side stage (e.g. the secure share exchange) that "
                "cannot run inside a scan, or is not the single-device "
                "program FusedRounds scans (a mesh: train_fused)")
        if self._obs is not None:
            # per-round boundaries don't exist inside a fused scan — say
            # so instead of leaving an empty timeline to be discovered
            logging.warning(
                "observability is on but the fused multi-round driver "
                "dispatches whole round BLOCKS — the flight log gets one "
                "record a block, not a round (and the slow-round detector "
                "no durations) for fused spans; use the host round loop "
                "for per-round timelines")
        return self._fused_driver_cls(self, device_sampling)

    def _host_round_inputs(self, round_idx: int):
        """Pipelined-or-serial host inputs for one round — ``run_round``'s
        input half, shared with subclasses that override only the
        dispatch half (FedOpt's server-optimizer step, TurboAggregate's
        secure exchange), so every FedAvg-family driver gets the async
        pipeline. Speculation is clamped to ``comm_round``: past it
        nothing follows, so the last get() must not leave never-consumed
        packed slots pinning HBM."""
        pf = self._round_prefetcher()
        if pf is None:
            _, idxs, args = self._pack_round(round_idx)
            self.timer.update_rss()  # consume() samples it on the
            return idxs, args        # pipelined path; mirror it here
        from fedml_tpu.parallel.prefetch import consume
        _, idxs, args = consume(pf, round_idx, self.timer, self.dataset,
                                self._pack_round,
                                round_bound=self.config.comm_round)
        return idxs, args

    def run_round(self, round_idx: int):
        # flight-recorder round boundary (pure observer: no RNG, no
        # schedule effect; ~2 dict copies when no recorder is bound)
        self.timer.begin_round(round_idx)
        if self._obs is not None:
            self._obs.round_begin(round_idx)
        # the previous round's output is a future until the device has run
        # everything queued: was it starved while the host made this
        # round's inputs?
        with self.timer.starved_probe(jax.tree.leaves(self.variables)[0]):
            idxs, args = self._host_round_inputs(round_idx)
        if self._obs is not None:
            # one-shot roofline probe (obs/perf.py): the analytic FLOP
            # count of THE round program about to dispatch, traced from
            # the live inputs (on a mesh at GLOBAL shapes, so the count is
            # the whole mesh's, as the fleet peak is) BEFORE any donation
            # invalidates them. Tracing touches no RNG/device state — a
            # pure observer.
            from fedml_tpu.utils.flops import analytic_flops
            fn = getattr(self, "_round_fn_py", None) or self._round_fn
            operands = self._round_operands(args, round_idx)
            self._obs.probe_round_flops(
                lambda: analytic_flops(fn, self.variables, *operands),
                source="analytic_conv_gn_jaxpr")
        # the rows the round program runs: mesh and length padding and all
        # where the slots are trained whole, the tiers' bounds otherwise
        x = args[0]
        rows = self._rows_stepped(self._pad_round(idxs)[0], x.shape[1])
        self.timer.count("rows_dispatched", rows)
        if x.ndim == 3 and jnp.issubdtype(x.dtype, jnp.integer):
            # rows of token ids: the positions the round steps through
            self.timer.count("tokens_dispatched", rows * x.shape[2])
        if getattr(self.config, "fold_clients", False):
            self.timer.count("clients_folded", len(idxs))
            # make_folded_body starts every one of them out of place
            self.timer.count("clients_first_step_out_of_place", len(idxs))
        operands = self._round_operands(args, round_idx)
        # the program's own map of its device work, on demand
        # (utils/tracing.py::device_scopes): a lookup a round
        self.timer.register_program(self._round_fn, self.variables, operands)
        with self.timer.phase("dispatch"):
            self.variables, stats = self._round_fn(self.variables, *operands)
        rec = self.timer.end_round(
            round_idx, extra={"cohort": [int(i) for i in idxs]})
        if self._obs is not None:
            self._obs.round_end(round_idx,
                                rec["duration_s"] if rec else None,
                                record=rec)
        return idxs, stats

    # -- the outer loop (reference fedavg_api.py:46-95) ---------------------
    def train(self) -> Dict:
        return self._train_rounds(0)

    def _train_rounds(self, start: int, after_round=None) -> Dict:
        """Rounds ``start .. comm_round - 1`` with the evaluation cadence;
        ``after_round(round_idx)`` runs at the end of each (a checkpoint)."""
        cfg = self.config
        t0 = time.time()
        for round_idx in range(start, cfg.comm_round):
            _, train_stats = self.run_round(round_idx)
            # dispatch is an async enqueue; the wall clock here still tracks
            # real progress because the host blocks once the device queue
            # fills (and at every eval)
            _progress_log.info("round %d/%d dispatched (wall %.1fs)",
                               round_idx + 1, cfg.comm_round,
                               time.time() - t0)
            last = round_idx == cfg.comm_round - 1
            if round_idx % cfg.frequency_of_the_test == 0 or last:
                # run_round is an async enqueue: block on the pending round
                # compute in its own phase so the eval timer measures eval,
                # not the device queue draining (the r4 femnist flagship
                # read 571s/eval that was really round compute)
                with self.timer.phase("device_wait"):
                    # ft: allow[FT003] eval-boundary sync: one measured drain per test interval, by design
                    jax.block_until_ready(self.variables)
                rec = self.evaluate(round_idx)
                # mean local-optimization loss this round (distinct from the
                # post-aggregation train_loss evaluate() reports)
                rec["train_loss_local"] = float(train_stats["loss_sum"]) / max(
                    1.0, float(train_stats["count"]))
                rec["wall_s"] = time.time() - t0
                # host/device phase breakdown (pack / dispatch / eval means)
                rec.update({f"phase_{k}_ms": v * 1e3
                            for k, v in self.timer.means().items()})
                self.history.append(rec)
                logging.info("round %d: %s", round_idx, rec)
            if after_round is not None:
                after_round(round_idx)
        return self.history[-1] if self.history else {}

    # -- evaluation (reference _local_test_on_all_clients; the per-client
    #    weighted sums equal the global-union sums, so we evaluate globally) --
    def _eval_arrays(self):
        """Device-resident eval unions, uploaded once per dataset (with the
        optional seeded train subsample)."""
        if self._eval_cache is None or self._eval_cache[0] is not self.dataset:
            xg, yg = self.dataset.train_data_global
            xg, yg = eval_subsample(xg, yg,
                                    self.config.eval_train_subsample,
                                    self.config.seed)
            train = (jnp.asarray(xg), jnp.asarray(yg),
                     jnp.ones(len(xg), jnp.float32))
            xt, yt = self.dataset.test_data_global
            if len(xt):
                xt, yt = eval_subsample(xt, yt,
                                        self.config.eval_test_subsample,
                                        self.config.seed)
            test = ((jnp.asarray(xt), jnp.asarray(yt),
                     jnp.ones(len(xt), jnp.float32)) if len(xt) else None)
            self._eval_cache = (self.dataset, train, test)
        return self._eval_cache[1], self._eval_cache[2]

    def evaluate(self, round_idx: int) -> Dict:
        """Normalized federation metrics: {train,test}_{acc,loss,total} as
        means over the global train/test unions (equal to the reference's
        per-client weighted sums in _local_test_on_all_clients)."""
        rec = {"round": round_idx}
        with self.timer.phase("eval"):
            train, test = self._eval_arrays()
            rec.update(_normalized(self._eval_fn(self.variables, *train),
                                   "train"))
            if test is not None:
                rec.update(_normalized(
                    self._eval_fn(self.variables, *test), "test"))
        return rec


class FusedRounds:
    """Multi-round on-device driver: R FedAvg rounds under ONE ``lax.scan``,
    so the host syncs once per R rounds instead of once per round (SURVEY §7
    "keep the entire round on-device"). Three modes:

    - **full participation** (``client_num_per_round == client_num``): data
      is packed and uploaded once; per-round/per-client RNG keys are derived
      *inside* the scan by the same ``fold_in`` chain the host loop uses
      (FedAvgAPI._pack_round), so the fused trajectory is equal to the
      host loop's round for round.
    - **block sampling** (the default when ``client_num_per_round <
      client_num``): the R cohorts are drawn host-side UP FRONT with the
      host loop's exact sampling stream (core/sampling.sample_clients, the
      reference's ``np.random.seed(round_idx)`` contract,
      FedAVGAggregator.py:89-97), packed as ONE ``[R, k, n_pad, ...]``
      block at the pow-2 bucket of the block's max cohort size
      (data/base.py cohort_padded_len), and scanned in one dispatch. This
      composes the two throughput levers — cohort-bucket padding AND fused
      multi-round scans — while staying trajectory-identical to the host
      loop (same cohorts, same fold_in key chain). HBM holds only the
      R-block, not the federation.
    - **device-side sampling** (``device_sampling=True``): the WHOLE
      federation is packed once as ``[client_num, n_pad, ...]`` device
      arrays; each scanned round draws ``client_num_per_round`` indices
      without replacement with ``jax.random.choice`` and gathers its cohort
      on device — zero host work per round, but the sampling stream is
      jax-native, NOT the host loop's contract, and HBM holds the full
      federation at global-max padding (the in-scan gather needs one
      static shape). Use block sampling unless the per-block host pack is
      the bottleneck.

    Stats come back stacked ``[R, ...]`` per scan, so per-round local-loss
    trajectories survive fusion.

    The fused drivers pack cohorts as sampled. Where the host loop packs a
    ragged cohort by size for ``make_vmapped_body``'s tiers, every client's
    model is still the same to the bit, but the weighted mean sums them in
    another order (last bits of a float32), and the fused tiers, exact in
    any order, stop later than sorted ones would.
    """

    def __init__(self, api: FedAvgAPI, device_sampling: bool = False):
        if (api._fused_driver_cls is None
                or type(self) is not api._fused_driver_cls):
            # e.g. plain FusedRounds(FedOptAPI) would silently run FedAvg
            # aggregation and drop the server optimizer; FusedRounds on a
            # SecureFedAvgAPI would skip the secure share exchange. Exact
            # type match: a subclass driver on a base API would pass an
            # isinstance check and then fail deep in _round on missing
            # server state (ADVICE r3)
            want = (api._fused_driver_cls.__name__
                    if api._fused_driver_cls else "no fused driver")
            raise TypeError(
                f"{type(api).__name__} pairs with {want} "
                f"(use api.fused_rounds()), not {type(self).__name__}")
        self.api = api
        cfg = api.config
        ds = api.dataset
        self.k = cfg.client_num_per_round
        self.N = ds.client_num
        self.device_sampling = device_sampling
        self.mode = ("device" if device_sampling
                     else "full" if self.k == self.N else "block")
        if api.delete_client is not None and self.mode != "block":
            raise ValueError(
                "full/device-sampled fused rounds do not honor "
                "delete_client (the in-scan cohort covers all clients); "
                "block mode (partial participation) samples host-side and "
                "honors it")
        bsz = cfg.train.batch_size
        round_step = self._round
        base_key = api._base_key
        k, N = self.k, self.N

        if self.mode in ("full", "device"):
            # federation resident on device, packed once at the global max
            pool = np.arange(self.N)
            x, y, mask = ds.pack_clients(pool, bsz, n_pad=api._n_pad)
            self._data = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
                          jnp.asarray(ds.client_weights(pool)))
        else:
            self._data = None  # block mode packs per run_rounds call

        def one_round(carry, r, x, y, mask, weights):
            if device_sampling and k != N:
                idx = jax.random.choice(
                    jax.random.fold_in(jax.random.fold_in(base_key, r),
                                       DEVICE_SAMPLE_SENTINEL),
                    N, (k,), replace=False)
                x, y, mask, weights = (jnp.take(a, idx, axis=0)
                                       for a in (x, y, mask, weights))
                ids = idx.astype(jnp.uint32)
            else:
                ids = jnp.arange(N, dtype=jnp.uint32)
            _, keys, agg_key = round_keys(base_key, r, ids)
            return round_step(carry, x, y, mask, keys, weights, agg_key, r)

        def run(carry, x, y, mask, weights, r0, rounds):
            return jax.lax.scan(
                lambda c, r: one_round(c, r, x, y, mask, weights),
                carry, r0 + jnp.arange(rounds))

        self._run = jax.jit(run, static_argnums=(6,), donate_argnums=(0,))

        def block_round(carry, inp):
            r, x, y, mask, ids, weights = inp
            _, keys, agg_key = round_keys(base_key, r, ids)
            return round_step(carry, x, y, mask, keys, weights, agg_key, r)

        def run_block(carry, xs, ys, masks, ids, ws, r0):
            rs = r0 + jnp.arange(xs.shape[0], dtype=jnp.uint32)
            return jax.lax.scan(block_round, carry,
                                (rs, xs, ys, masks, ids, ws))

        # recompiles per (R, n_pad-bucket) pair — both bounded (R is the
        # caller's chunk size; buckets are O(log2 max batches))
        self._run_block = jax.jit(run_block, donate_argnums=(0,))

    def _block_inputs(self, r0: int, rounds: int):
        """Host side of a fused block: draw the R cohorts with the host
        loop's sampling stream, pack them as one [R, k, n_pad, ...] batch
        at the block's cohort bucket (one pack_clients call — the native
        packer parallelizes over all R*k slots)."""
        api, cfg, ds = self.api, self.api.config, self.api.dataset
        bsz = cfg.train.batch_size
        cohorts = [sample_clients(r, self.N, self.k,
                                  delete_client=api.delete_client)
                   for r in range(r0, r0 + rounds)]
        flat = np.concatenate([np.asarray(c) for c in cohorts])
        n_pad = (max(ds.cohort_padded_len(c, bsz) for c in cohorts)
                 if cfg.pack == "cohort" else api._n_pad)
        x, y, mask = ds.pack_clients(flat, bsz, n_pad=n_pad)
        lead = (rounds, self.k)
        return (jnp.asarray(x.reshape(lead + x.shape[1:])),
                jnp.asarray(y.reshape(lead + y.shape[1:])),
                jnp.asarray(mask.reshape(lead + mask.shape[1:])),
                jnp.asarray(flat.astype(np.uint32).reshape(lead)),
                jnp.asarray(ds.client_weights(flat).reshape(lead)))

    # -- carry protocol: subclasses fusing richer server state (e.g.
    #    FedOpt's optimizer) override these three -------------------------
    def _init_carry(self):
        return self.api.variables

    def _store_carry(self, carry) -> None:
        self.api.variables = carry

    def _round(self, carry, x, y, mask, keys, weights, agg_key, r):
        """One round on the scan carry; the base carry is the variables
        tree and the body is the exact host-loop round program (``r`` is
        the traced round index — the lr_decay_round schedule inside
        round_fn depends on it)."""
        return self.api._round_fn_py(carry, x, y, mask, keys, weights,
                                     agg_key, r)

    def run_rounds(self, r0: int, rounds: int):
        """Advance the api's model by ``rounds`` fused rounds starting at
        round index ``r0``; returns stacked per-round stat totals."""
        api = self.api
        api.timer.begin_round(r0)  # one span and one record a block
        if self.mode == "block":
            with api.timer.phase("pack"):
                inputs = self._block_inputs(r0, rounds)
            with api.timer.phase("dispatch"):
                carry, stats = self._run_block(
                    self._init_carry(), *inputs, jnp.uint32(r0))
        else:
            with api.timer.phase("dispatch"):
                carry, stats = self._run(
                    self._init_carry(), *self._data, jnp.uint32(r0), rounds)
        self._store_carry(carry)
        api.timer.end_round(r0, extra={"rounds": rounds})
        return stats

    def cost_analysis(self, r0: int = 0, rounds: int = 1) -> Dict:
        """XLA cost model of the fused block program itself (whole-block
        totals — divide by ``rounds`` for per-round figures). Lowers and
        compiles the same jitted scan ``run_rounds`` dispatches, so the
        flops/"bytes accessed" accounting describes the program that is
        actually timed (scan-carry residency and cross-round fusion
        included), not the standalone single-round program. Costs one
        compile; lowering does not execute (donated args are safe)."""
        if self.mode == "block":
            inputs = self._block_inputs(r0, rounds)
            lowered = self._run_block.lower(self._init_carry(), *inputs,
                                            jnp.uint32(r0))
        else:
            lowered = self._run.lower(self._init_carry(), *self._data,
                                      jnp.uint32(r0), rounds)
        analysis = lowered.compile().cost_analysis()
        return dict(analysis or {})

    def train(self, max_rounds_per_dispatch: Optional[int] = None) -> Dict:
        """The FedAvgAPI.train loop with the scan chunked at eval points:
        one device dispatch per test interval instead of per round.

        Eval cadence matches the host loop exactly — records after rounds
        0, freq, 2*freq, ..., and the last round (FedAvgAPI.train's
        ``round_idx % freq == 0 or last``) — so fused and host histories
        line up round for round. ``max_rounds_per_dispatch`` caps the scan
        length per device call (the --fused_rounds CLI value); None fuses
        each full eval interval."""
        api, cfg = self.api, self.api.config
        if cfg.comm_round <= 0:
            return api.history[-1] if api.history else {}
        freq = cfg.frequency_of_the_test
        t0 = time.time()
        evals = sorted({r for r in range(0, cfg.comm_round, freq)}
                       | {cfg.comm_round - 1})
        r = 0
        for e in evals:
            stats = None
            while r <= e:
                chunk = e + 1 - r
                if max_rounds_per_dispatch:
                    chunk = min(chunk, max_rounds_per_dispatch)
                stats = self.run_rounds(r, chunk)
                r += chunk
            with api.timer.phase("device_wait"):
                # ft: allow[FT003] eval-boundary sync after a fused chunk
                jax.block_until_ready(api.variables)
            rec = api.evaluate(r - 1)
            rec["train_loss_local"] = (
                float(stats["loss_sum"][-1])
                / max(1.0, float(stats["count"][-1])))
            rec["wall_s"] = time.time() - t0
            rec.update({f"phase_{k}_ms": v * 1e3
                        for k, v in api.timer.means().items()})
            api.history.append(rec)
            logging.info("fused round %d: %s", r - 1, rec)
        return api.history[-1] if api.history else {}


# the paired fused driver (set after both classes exist); FedOptAPI and
# other subclasses fusing more server state override this attribute
FedAvgAPI._fused_driver_cls = FusedRounds


# -- static-analysis hook (fedml_tpu.analysis layer 2) ----------------------
from fedml_tpu.analysis.registry import AuditSpec, hot_entry_point  # noqa: E402


@hot_entry_point("fedavg.round_fn")
def _audit_round_fn() -> AuditSpec:
    """The flagship hot program, audited over three REAL rounds' host
    inputs: sampled-cohort packing at the global pad (constant shapes),
    per-round keys, uint32 round index. The sweep asserts the driver's
    signature-stability contract — every round of a run must hit the one
    compiled program (the r5 recompile class fails here, not in a bench
    window)."""
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression

    ds = make_blob_federated(client_num=4, n_samples=200, seed=0)
    api = FedAvgAPI(
        ds, LogisticRegression(num_classes=ds.class_num),
        config=FedAvgConfig(
            comm_round=3, client_num_per_round=2, pack="global",
            prefetch_depth=0,
            train=TrainConfig(epochs=1, batch_size=8)))

    def inputs(r):
        _, _, (x, y, mask, keys, w, agg_key) = api._pack_round(r)
        return (api.variables, x, y, mask, keys, w, agg_key, jnp.uint32(r))

    return AuditSpec(fn=api._round_fn, sweep=[inputs(r) for r in range(3)],
                     max_lowerings=1, grad_path=True)
