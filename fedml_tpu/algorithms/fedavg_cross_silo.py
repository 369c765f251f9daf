"""Distributed FedAvg over the message layer — the cross-silo path.

When clients are separate trust domains / hosts (no shared mesh), the round
cannot be one SPMD program; it is the reference's actor protocol
(fedml_api/distributed/fedavg/): server broadcasts the global model, each
client runs local training and sends back ``(model_params, num_samples)``,
the server aggregates when all have arrived and starts the next round.

Parity map:
- message schema  -> reference message_define.py:1-31 (same 4 types)
- FedAvgAggregator -> FedAVGAggregator.py:13-107 (all-received barrier,
  sample-weighted average, per-round seeded sampling)
- FedAvgServerManager / FedAvgClientManager -> FedAvgServerManager.py:18-93,
  FedAvgClientManager.py:18-71 — minus the off-by-one Abort shutdown quirk;
  here the server sends an explicit FINISH message.

TPU-first deltas: each silo's local training is the jitted
``make_local_train`` program (scan over epochs x batches on its own chip) —
if a silo packs several virtual clients they are vmapped; aggregation is a
jitted weighted tree-mean on the server's device; transport frames are the
zero-copy codec, not pickled dicts.

Wire compression (comm/policy.py ladder, ``--compression``): uplink
replies compress the delta against the silo's held global (int8 and/or
top-k with a per-silo error-feedback residual, held round-keyed on the
client-state store under ``checkpoint_dir/silo_<rank>/`` —
``fedml_tpu.state.residuals``, which also reads the PR-4
``round_<r>`` msgpack layout for old resumes); the round-based servers compress
downlink broadcasts against the *mirror* — the model state every silo
holds, advanced by exactly what each broadcast decodes to — falling back
to full precision on the first broadcast and whenever a silo's reported
base fingerprint mismatches. Wire bytes are counted from actual encoded
frames into the launcher's RoundTimer (``comm_bytes_up``/``_down``).
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.comm import (ClientManager, Message, ServerManager,
                            create_comm_manager)
from fedml_tpu.comm.inproc import InProcRouter
from fedml_tpu.comm.policy import resolve_compression
from fedml_tpu.comm.serialization import SharedPayload
from fedml_tpu.core import pytree as pt
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.trainer.functional import (TrainConfig, make_eval,
                                          make_local_train, round_lr_scale)
from fedml_tpu.utils.watchdog import SiloLivenessTable

# -- message schema (reference message_define.py) ---------------------------
MSG_TYPE_S2C_INIT_CONFIG = 1
MSG_TYPE_S2C_SYNC_MODEL = 2
MSG_TYPE_S2C_FINISH = 3
MSG_TYPE_C2S_SEND_MODEL = 4
#: self-addressed deadline tick (the quorum/deadline servers' timer posts
#: it so the state machine stays single-threaded)
MSG_TYPE_ROUND_TIMEOUT = 9
#: periodic proof of life from an idle silo; ANY inbound silo message
#: (model replies included) also beats the server's liveness table
MSG_TYPE_C2S_HEARTBEAT = 10
#: a restarted or evicted silo asking back in; the server re-admits it
#: with a full-precision resync of the silo mirror
MSG_TYPE_C2S_JOIN = 11
#: admission control (control/admission.py): the JOIN was rate-limited —
#: no resync now; carries ``retry_after_s`` and the silo defers its next
#: JOIN attempt by that long (heartbeats keep beating: backpressure
#: rejects the resync, not the proof of life)
MSG_TYPE_S2C_JOIN_BACKPRESSURE = 12

MSG_ARG_KEY_MODEL_PARAMS = Message.MSG_ARG_KEY_MODEL_PARAMS
MSG_ARG_KEY_NUM_SAMPLES = Message.MSG_ARG_KEY_NUM_SAMPLES
MSG_ARG_KEY_CLIENT_INDEX = Message.MSG_ARG_KEY_CLIENT_INDEX
MSG_ARG_KEY_ROUND = "round_idx"
#: broadcast sequence number: the silo's held-model version, echoed back
#: on replies so the server knows which base each silo confirmed holding
MSG_ARG_KEY_BCAST_SEQ = "bcast_seq"
MSG_ARG_KEY_BASE_SEQ = "base_seq"
#: structure fingerprint of the silo's held model — the server's
#: automatic full-precision fallback trigger on mismatch
MSG_ARG_KEY_BASE_FP = "base_fp"
#: JOIN payload: how many rounds the (re)joining silo completed before it
#: went away — logged, and available for smarter re-admission policies
MSG_ARG_KEY_ROUNDS_COMPLETED = "rounds_completed"
#: BACKPRESSURE payload: seconds until the admission token bucket refills
MSG_ARG_KEY_RETRY_AFTER = "retry_after_s"
#: observability piggyback (fedml_tpu/obs): a compact counter digest a
#: silo attaches to replies/heartbeats when the flight recorder is on —
#: the server turns it into per-silo rows in ITS flight log, so one
#: merged timeline carries every process's view of round r. Read
#: optionally server-side; absent in the (default) obs-off wire format.
MSG_ARG_KEY_OBS_DIGEST = "obs_digest"

#: All silo actors in one process compute on the default device, which has
#: ONE dispatch queue — so serializing jax compute across actor threads
#: costs nothing in steady state, and one lock around every
#: device-touching section keeps the actors' first compiles from racing
#: the server's init. (On a multi-chip host this backend therefore uses
#: one chip; ROADMAP S1 owns that.)
#: Under the federation scheduler (fedml_tpu/sched) every actor holds a
#: per-job JobDeviceGate INSTEAD, which takes a fair-share slot and then
#: THIS lock — so gated and ungated paths still serialize on one mutex.
# ft: allow[FT018] sanctioned singleton: the physical device has ONE dispatch queue shared by every tenant — a per-job mutex could not serialize cross-job dispatch; job-fair ordering is layered on top by sched.RoundInterleaver
_DEVICE_LOCK = threading.RLock()

#: One jitted local_train per (module, task, cfg): in-process silos share
#: one device, and per-silo ``jax.jit`` instances would compile the
#: IDENTICAL program once per silo (a 10-silo round 0 paid ten compiles
#: before this cache). Real multi-host cross-silo deployments have one silo per
#: process, where this cache is a no-op.
# ft: allow[FT018] sanctioned singleton: a cache of PURE jitted programs keyed by (module, task, cfg) — entries carry no job state, so tenants sharing an identical program is exactly the deduplication the cache exists for
_LOCAL_TRAIN_CACHE: Dict = {}


def _shared_local_train(module, task: str, train_cfg: TrainConfig):
    try:
        fn = _LOCAL_TRAIN_CACHE.get((module, task, train_cfg))
    except TypeError:  # exotic unhashable module/cfg: private jit
        return jax.jit(make_local_train(module, task, train_cfg))
    if fn is None:
        if len(_LOCAL_TRAIN_CACHE) > 64:  # bound (long test sessions)
            _LOCAL_TRAIN_CACHE.clear()
        fn = _LOCAL_TRAIN_CACHE[(module, task, train_cfg)] = jax.jit(
            make_local_train(module, task, train_cfg))
    return fn


def _to_numpy(tree):
    return jax.tree.map(np.asarray, tree)


class FedAvgAggregator:
    """Server state machine: collect worker results, barrier, aggregate.

    Reference: FedAVGAggregator.py — ``add_local_trained_result`` (:44),
    ``check_whether_all_receive`` (:50), ``aggregate`` (:58), seeded
    ``client_sampling`` (:89).

    Aggregation is a streaming in-order prefix fold (default path): as
    each report arrives, the contiguous worker-index prefix is folded
    into a weighted running sum (``pt.tree_weighted_fold_*``), and only
    out-of-order arrivals wait in ``model_dict`` — O(out-of-order) host
    memory instead of O(cohort), and round close shrinks to draining the
    residual suffix. The overall fold order is ALWAYS ascending worker
    index (contiguous prefix first, then the sorted remainder at close),
    so any arrival order, any partial close, and a restore-from-snapshot
    mid-fold all produce bit-identical results — the fold IS the
    canonical reduction. (It matches the old stacked
    ``tree_weighted_mean`` only to float tolerance: XLA reassociates the
    stacked axis-0 reduce.) A custom ``aggregate_fn`` (order-statistic
    robust rules need the full cohort) keeps the legacy buffered path.
    """

    def __init__(self, worker_num: int, aggregate_fn=None):
        self.worker_num = worker_num
        #: streaming path: ONLY the out-of-order / not-yet-folded
        #: reports; legacy path (custom aggregate_fn): every report
        self.model_dict: Dict[int, object] = {}
        self.sample_num_dict: Dict[int, float] = {}
        self.flag_client_model_uploaded = [False] * worker_num
        self._streaming = aggregate_fn is None
        self._aggregate = jax.jit(aggregate_fn or pt.tree_weighted_mean)
        # per-instance jits (matching _aggregate's style): the fold steps
        # are THE canonical reduction — every path (incremental, close
        # drain, restored-from-snapshot) must run these exact programs
        self._fold_init = jax.jit(pt.tree_weighted_fold_init)
        self._fold_step = jax.jit(pt.tree_weighted_fold_step)
        self._fold_finish = jax.jit(pt.tree_fold_finish)
        #: running weighted sum of the folded prefix (None: nothing folded)
        self._fold_acc = None
        #: next contiguous worker index the fold is waiting for
        self._fold_next = 0
        #: reports folded so far this round
        self._fold_count = 0
        #: f32 running total of folded weights (sequential f32 adds —
        #: part of the canonical reduction, so snapshots roundtrip it
        #: exactly via float64)
        self._fold_total = np.float32(0.0)
        #: True once any weight > 0 was seen this round; while False the
        #: fold defers (all-empty-shard rounds close with the uniform
        #: fallback, which needs the reports unfolded)
        self._any_pos = False
        #: peak len(model_dict) this round (the agg_buffered_peak gauge)
        self.buffered_peak = 0
        #: optional cohort-draw override (``fedml_tpu/wan``: the WAN
        #: world's availability-restricted sampler). None (default) =
        #: the reference seeded stream, byte-identical legacy behavior.
        #: Any override MUST stay a pure function of its arguments —
        #: the silos' prefetch prediction and the failover replay both
        #: re-derive cohorts from the round index alone.
        self.sampler = None

    def add_local_trained_result(self, worker_idx: int, model_params,
                                 sample_num: float) -> None:
        """Record one report and fold the ready prefix. Device compute
        happens here (the fold steps), so callers invoke this under the
        device lock — same contract as decode/aggregate."""
        if self._streaming and worker_idx < self._fold_next:
            # already folded into the running sum: a transport-level
            # duplicate delivers an identical payload, so dropping it
            # preserves the result; it cannot be un-folded anyway
            logging.debug("aggregator: duplicate report from folded "
                          "worker %d ignored", worker_idx)
            self.flag_client_model_uploaded[worker_idx] = True
            return
        self.model_dict[worker_idx] = model_params
        self.sample_num_dict[worker_idx] = sample_num
        self.flag_client_model_uploaded[worker_idx] = True
        if sample_num > 0:
            self._any_pos = True
        self.buffered_peak = max(self.buffered_peak, len(self.model_dict))
        if self._streaming:
            self._drain_ready()

    def check_whether_all_receive(self) -> bool:
        if all(self.flag_client_model_uploaded):
            self.flag_client_model_uploaded = [False] * self.worker_num
            return True
        return False

    # -- streaming fold ------------------------------------------------------
    def _fold_in(self, idx: int, weight=None) -> None:
        """Fold pending report ``idx`` into the running sum (arrival
        weight unless the uniform-fallback close overrides it)."""
        model = self.model_dict.pop(idx)
        w32 = np.float32(self.sample_num_dict.pop(idx)
                         if weight is None else weight)
        wj = jnp.asarray(w32)
        if self._fold_acc is None:
            self._fold_acc = self._fold_init(model, wj)
        else:
            self._fold_acc = self._fold_step(self._fold_acc, model, wj)
        self._fold_total = np.float32(self._fold_total + w32)
        self._fold_count += 1

    def _drain_ready(self) -> None:
        """Fold the contiguous worker-index prefix now in hand. Deferred
        until a positive weight is seen: an all-empty-shard round must
        close with the uniform fallback, which re-weights every report."""
        if not self._any_pos:
            return
        while self._fold_next in self.model_dict:
            self._fold_in(self._fold_next)
            self._fold_next += 1

    def _reset_round(self) -> None:
        self.model_dict.clear()
        self.sample_num_dict.clear()
        self.flag_client_model_uploaded = [False] * self.worker_num
        self._fold_acc = None
        self._fold_next = 0
        self._fold_count = 0
        self._fold_total = np.float32(0.0)
        self._any_pos = False
        self.buffered_peak = 0

    def _close_streaming(self):
        """Drain the residual suffix and normalize. Pending keys are all
        >= the folded prefix, so draining them sorted makes the overall
        fold order ``sorted(reported)`` — identical for every arrival
        order and for a mid-fold snapshot restore."""
        if self._fold_count == 0 and not self.model_dict:
            raise ValueError("aggregate on an empty round: no reports")
        # recomputed (not just self._any_pos): restored snapshots and
        # tests inject pending reports directly into model_dict
        uniform = self._fold_count == 0 and \
            not any(w > 0 for w in self.sample_num_dict.values())
        for i in sorted(self.model_dict):
            # uniform fallback (every reporter had an empty shard):
            # weight 1.0 — ``x * 1.0`` is bitwise ``x``, so the fallback
            # is the SAME fold with unit weights, not a separate path
            self._fold_in(i, weight=1.0 if uniform else None)
        out = self._fold_finish(self._fold_acc,
                                jnp.asarray(self._fold_total))
        self._reset_round()
        return out

    # -- legacy buffered close (custom aggregate_fn) -------------------------
    def _close(self, idxs):
        stacked = pt.tree_stack([self.model_dict[i] for i in idxs])
        weights = np.asarray([self.sample_num_dict[i] for i in idxs],
                             np.float32)
        if weights.sum() <= 0.0:
            # every reporter had an empty shard (possible under partial
            # closes): uniform mix instead of a 0/0 NaN model
            weights = np.ones_like(weights)
        out = self._aggregate(stacked, jnp.asarray(weights))
        self.model_dict.clear()
        self.sample_num_dict.clear()
        self.flag_client_model_uploaded = [False] * self.worker_num
        return out

    def aggregate(self):
        if self._streaming:
            return self._close_streaming()
        return self._close(range(self.worker_num))

    def reported_set(self) -> set:
        """Workers whose report is in hand for the open round — folded
        prefix plus pending buffer (the old ``set(model_dict)``)."""
        return set(range(self._fold_next)) | set(self.model_dict)

    def has_reported(self, worker_idx: int) -> bool:
        return worker_idx < self._fold_next or worker_idx in self.model_dict

    def received_count(self) -> int:
        """Updates in hand for the open round (quorum checks)."""
        return self._fold_count + len(self.model_dict)

    def aggregate_available(self):
        """Weighted mean over whichever workers reported this round, then
        reset — the straggler-tolerant close (quorum rounds). Equal to
        :meth:`aggregate` when everyone reported."""
        if self._streaming:
            return self._close_streaming()
        return self._close(sorted(self.model_dict))

    def client_sampling(self, round_idx: int, client_num_in_total: int,
                        client_num_per_round: int) -> np.ndarray:
        if self.sampler is not None:
            return self.sampler(round_idx, client_num_in_total,
                                client_num_per_round)
        return sample_clients(round_idx, client_num_in_total,
                              client_num_per_round)


class FedAvgServerManager(ServerManager):
    """Round-based cross-silo server.

    Fault tolerance (opt-in via ``round_deadline_s``): the all-received
    barrier is taken against the LIVE silo set (a per-silo
    ``SiloLivenessTable`` beaten by every inbound silo message); when the
    per-round deadline passes with at least
    ``ceil(min_quorum_frac * live)`` reports in, the round closes with a
    weighted PARTIAL aggregate and the non-reporting silos are EVICTED
    from the live set (their pending EF residual mass is dropped — the
    documented quorum-discard loss class). An evicted or restarted silo
    sends JOIN and is re-admitted with a full-precision resync of the
    silo mirror, so the downlink compression chain stays coherent.
    Without ``round_deadline_s`` the behavior is the original strict
    all-of-``worker_num`` barrier, unchanged.
    """

    def __init__(self, rank: int, size: int, com_manager,
                 aggregator: FedAvgAggregator, comm_round: int,
                 client_num_in_total: int, global_model,
                 on_round_done=None, checkpoint_mgr=None,
                 resume: bool = False, compression=None,
                 round_deadline_s: Optional[float] = None,
                 min_quorum_frac: float = 0.5,
                 server_ckpt=None, pace=None, join_admission=None,
                 max_deadline_extensions: Optional[int] = 25,
                 device_gate=None, wan=None):
        super().__init__(rank, size, com_manager)
        #: the mutex every device-touching section holds. Default: the
        #: process-wide _DEVICE_LOCK (single-tenant, byte-identical
        #: legacy behavior). The federation scheduler passes a per-job
        #: JobDeviceGate (sched/interleave.py) so tenants take
        #: fair-share turns on the one chip.
        self._device_lock = (device_gate if device_gate is not None
                             else _DEVICE_LOCK)
        self.aggregator = aggregator
        self.comm_round = comm_round
        self.client_num_in_total = client_num_in_total
        self.global_model = global_model
        self.round_idx = 0
        self.on_round_done = on_round_done
        self.worker_num = size - 1
        self.checkpoint_mgr = checkpoint_mgr
        # -- fault tolerance (liveness / deadline / eviction / rejoin) ------
        if not 0.0 < min_quorum_frac <= 1.0:
            raise ValueError(f"min_quorum_frac must be in (0, 1], got "
                             f"{min_quorum_frac}")
        self.round_deadline_s = round_deadline_s
        self.min_quorum_frac = min_quorum_frac
        #: deadline-evicted straggler semantics ON (False = the strict
        #: all-received barrier; the quorum subclass reuses the timer
        #: plumbing but keeps its own absolute-quorum policy)
        self._evict_on_deadline = bool(round_deadline_s
                                       and round_deadline_s > 0)
        self.liveness = SiloLivenessTable(range(self.worker_num))
        #: per-round {round, reported, live, partial} records (FT mode)
        self.live_history: List[Dict] = []
        self.ft_counters: Dict[str, int] = defaultdict(int)
        self._timer: Optional[threading.Timer] = None
        #: worker -> round of its last JOIN resync: a silo retrying JOIN on
        #: its heartbeat cadence gets ONE full-model resync per round, not
        #: one per tick (full-precision frames are the expensive ones)
        self._resynced_round: Dict[int, int] = {}
        # -- elastic control plane (fedml_tpu/control/) ---------------------
        #: durable round-schedule snapshots + the round/cohort ledger; a
        #: restarted server restores the newest snapshot in send_init_msg
        self._server_ckpt = server_ckpt
        #: adaptive deadline/quorum steering (None = the static flags,
        #: byte-identical legacy behavior)
        self._pace = pace
        #: JOIN token bucket (None = admit every JOIN, legacy behavior)
        self._join_admission = join_admission
        # -- WAN world model (fedml_tpu/wan/) -------------------------------
        #: population dynamics driving this schedule (None = off, the
        #: byte-identical legacy path): availability-restricted cohort
        #: sampling, the trace-gated rejoin path, and per-round churn
        #: telemetry. Deliberately NOT in the checkpoint manifest — the
        #: world is a pure function of (seed, round), so a restored
        #: server rebuilds the identical dynamics from its flags.
        self._wan = wan
        if wan is not None:
            self.aggregator.sampler = wan.sample_cohort
        #: worker -> (round, deferral count): the WAN rejoin gate's
        #: anti-starvation ledger (transient telemetry, deliberately not
        #: checkpointed — a restored server resets the counts and the
        #: valve re-arms; see WanWorld.max_join_deferrals_per_round)
        self._wan_join_deferrals: Dict[int, tuple] = {}
        #: workers whose JOIN was WAN-deferred, awaiting their device's
        #: trace to flip online: admitted in a batch at the next round
        #: boundary (:meth:`_wan_admit_pending`) so the rejoin ROUND is
        #: a pure function of the trace, not of the race between the
        #: JOIN retry cadence and the other silos' replies. Transient —
        #: a restored server loses it and the silos' retries rebuild it.
        self._wan_pending_joins: set = set()
        #: below-quorum deadline-extension budget per round (None =
        #: the pre-control-plane forever-extend behavior)
        self._max_extensions = max_deadline_extensions
        self._extensions_this_round = 0
        #: control-plane counters (checkpoints/restores/adjustments/
        #: throttles) — rolled into RoundTimer as ``cp_*``
        self.cp_counters: Dict[str, int] = defaultdict(int)
        #: the cohort broadcast for the OPEN round (ledger payload)
        self._round_cohort: Optional[List[int]] = None
        #: monotonic timestamp of the open round's broadcast — the origin
        #: every reply's report latency is measured from (ephemeral)
        self._bcast_at: Optional[float] = None
        #: observability bundle (fedml_tpu/obs) — bound by the launcher
        #: alongside round_timer; None = flight recorder off (default)
        self.obs = None
        #: serving publish hook (fedml_tpu/serve) — bound by the
        #: launcher when a serving tier is attached. Called with every
        #: broadcast's payload (full tree or compression-mirror delta —
        #: the rollout decodes deltas with the silos' own chain rule)
        #: and once more with the final model at FINISH. None (default)
        #: = no serving, byte-identical legacy behavior; the hook is a
        #: pure observer and must never raise into the round loop.
        self.publish_model = None
        #: cumulative transport bytes already credited into the round
        #: timer (pure-observer accounting, NOT schedule state: a
        #: restored server starts a fresh endpoint whose counters reset,
        #: so these deliberately stay out of the checkpoint manifest)
        self._wire_credited_up = 0
        self._wire_credited_down = 0
        #: serialization version token for the global model: bumped on
        #: every reassignment (aggregation, restore) so the incremental
        #: snapshot serializer and the capture cache below know when the
        #: cached bytes are still the model's bytes. Pure derived
        #: accounting — deliberately NOT in the checkpoint manifest (a
        #: restored server starts a fresh serializer cache anyway)
        self._model_version = 0
        #: (model version, captured state-dict) pair: mid-round snapshots
        #: (deadline extensions) re-capture the UNCHANGED global model —
        #: the cache skips that D2H + tree copy entirely
        self._gm_capture_cache = None
        #: terminal latch: set (with a FINISH sweep) when the schedule
        #: cannot make progress; launch_federation re-raises it
        self.scheduling_error: Optional[Exception] = None
        self._control_restored = False
        self._restore_lock = threading.Lock()
        # -- downlink compression state (comm/policy.py) --------------------
        self._policy = resolve_compression(compression)
        self._bcast_seq = -1
        #: the model state every silo holds: advanced by exactly what each
        #: broadcast decodes to, so with downlink compression it trails the
        #: exact global by the not-yet-sent delta mass (implicit error
        #: feedback — the gap rides in the next round's delta)
        self._mirror = None
        self._mirror_fp = None
        #: worker -> (held seq, held structure fp) from its last reply
        self._worker_base: Dict[int, tuple] = {}
        if checkpoint_mgr is not None and resume:
            # resume = restart the protocol at the checkpointed round: the
            # init broadcast carries (restored model, restored round), and
            # since sampling + client RNG derive from the round index the
            # continuation is bit-identical to an uninterrupted run
            restored = checkpoint_mgr.restore_latest(self._checkpoint_state())
            if restored:
                state, meta = restored
                self._load_state(state)
                self.round_idx = meta["round_idx"]

    # subclasses (FedOpt) extend the round-state tuple with server opt state
    def _checkpoint_state(self):
        return {"variables": self.global_model}

    def _load_state(self, state) -> None:
        self.global_model = state["variables"]

    # -- elastic control plane: full round-schedule snapshot/restore --------
    # (fedml_tpu/control/checkpoint.py; field manifest in
    # control/manifest.py, enforced by lint rule FT009)
    def _capture_extra(self, state: Dict) -> None:
        """Subclass hook: add flavor-specific round state (FedOpt's
        server optimizer, quorum's partial-round log) to the snapshot."""

    def _restore_extra(self, state: Dict) -> None:
        """Subclass hook: restore what :meth:`_capture_extra` added."""

    def _capture_control_state(self) -> Dict:
        """The FULL round-schedule state as an msgpack-serializable dict:
        everything a restarted server needs to resume mid-schedule.
        ``round_idx`` doubles as the sampling cursor — cohorts and client
        RNG keys are pure functions of (seed, round), so no separate RNG
        state exists to save."""
        from flax import serialization as fser
        agg = self.aggregator
        with self._device_lock:  # D2H transfers are device dispatches
            cache = self._gm_capture_cache
            if cache is not None and cache[0] == self._model_version:
                gm = cache[1]
            else:
                gm = fser.to_state_dict(_to_numpy(self.global_model))
                self._gm_capture_cache = (self._model_version, gm)
            # the streaming aggregator's pending buffer holds only the
            # not-yet-folded reports; the folded prefix rides in agg_fold
            pending = {str(w): fser.to_state_dict(_to_numpy(m))
                       for w, m in agg.model_dict.items()}
            fold_acc = (fser.to_state_dict(_to_numpy(agg._fold_acc))
                        if agg._fold_acc is not None else None)
        state = {
            "round_idx": int(self.round_idx),
            "comm_round": int(self.comm_round),
            "worker_num": int(self.worker_num),
            "bcast_seq": int(self._bcast_seq),
            "evict_on_deadline": bool(self._evict_on_deadline),
            "global_model": gm,
            "mirror": (fser.to_state_dict(self._mirror)
                       if self._mirror is not None else None),
            "mirror_fp": self._mirror_fp,
            "worker_base": {str(w): [int(s), str(fp)]
                            for w, (s, fp) in self._worker_base.items()},
            "live": sorted(int(w) for w in self.liveness.live_workers()),
            "evictions": int(self.liveness.evictions),
            "rejoins": int(self.liveness.rejoins),
            "latency_window": self.liveness.report_latencies.values(),
            "pending_models": pending,
            "pending_weights": {str(w): float(v)
                                for w, v in agg.sample_num_dict.items()},
            # mid-fold state: the running weighted sum, the contiguous
            # prefix bound, and the f32 weight total (exact through
            # float64 — f32 -> f64 -> f32 roundtrips bit-identically),
            # so a restored server resumes the fold where it stopped and
            # closes bit-identical to the unkilled reference
            "agg_fold": {
                "next": int(agg._fold_next),
                "count": int(agg._fold_count),
                "total": float(agg._fold_total),
                "any_pos": bool(agg._any_pos),
                "acc": fold_acc,
            },
            "uploaded_flags": [bool(f)
                               for f in agg.flag_client_model_uploaded],
            "live_history": self.live_history,
            "ft_counters": {k: int(v) for k, v in self.ft_counters.items()},
            "cp_counters": {k: int(v) for k, v in self.cp_counters.items()},
            "resynced_round": {str(k): int(v)
                               for k, v in self._resynced_round.items()},
            "round_deadline_s": (float(self.round_deadline_s)
                                 if self.round_deadline_s else None),
            "min_quorum_frac": float(self.min_quorum_frac),
            "extensions_this_round": int(self._extensions_this_round),
            "round_cohort": ([int(i) for i in self._round_cohort]
                             if self._round_cohort is not None else None),
            "pace": (self._pace.state() if self._pace is not None
                     else None),
        }
        self._capture_extra(state)
        return state

    def _restore_control_state(self, state: Dict) -> None:
        if int(state["worker_num"]) != self.worker_num \
                or int(state["comm_round"]) != self.comm_round:
            raise ValueError(
                f"server snapshot is for a {state['worker_num']}-silo/"
                f"{state['comm_round']}-round schedule; this launch is "
                f"{self.worker_num}-silo/{self.comm_round}-round — "
                "refusing a silently wrong resume (point "
                "--server_checkpoint_dir at a fresh directory)")
        self.round_idx = int(state["round_idx"])
        self._bcast_seq = int(state["bcast_seq"])
        self._evict_on_deadline = bool(state["evict_on_deadline"])
        self.global_model = state["global_model"]
        self._mirror = state["mirror"]
        self._mirror_fp = state["mirror_fp"]
        # worker_base is snapshotted for forensics but NOT restored:
        # whether each silo still holds the base it reported pre-kill is
        # value-level staleness the structural fingerprint cannot see, so
        # the first post-restore broadcast rebases FULL precision (one
        # full frame per failover) — the same coherence rule the JOIN
        # resync uses
        self._worker_base = {}
        live = {int(w) for w in state["live"]}
        for w in range(self.worker_num):
            if w not in live:
                self.liveness.evict(w)
        self.liveness.evictions = int(state["evictions"])
        self.liveness.rejoins = int(state["rejoins"])
        self.liveness.report_latencies.load(
            state.get("latency_window") or ())
        agg = self.aggregator
        agg.model_dict = {int(w): m
                          for w, m in state["pending_models"].items()}
        agg.sample_num_dict = {int(w): float(v)
                               for w, v in state["pending_weights"].items()}
        agg.flag_client_model_uploaded = [
            bool(f) for f in state["uploaded_flags"]]
        fold = state.get("agg_fold")
        if fold is not None:
            agg._fold_next = int(fold["next"])
            agg._fold_count = int(fold["count"])
            agg._fold_total = np.float32(fold["total"])
            agg._any_pos = bool(fold["any_pos"])
            # like pending models, the acc restores as a plain dict of
            # numpy arrays — bit-identical leaves, so resuming the fold
            # continues the canonical reduction exactly
            agg._fold_acc = fold["acc"]
        else:
            # pre-fold snapshot format: every report is pending; the
            # close drain refolds them in sorted order, which the fold
            # contract makes equal to the streaming result
            agg._fold_acc = None
            agg._fold_next = 0
            agg._fold_count = 0
            agg._fold_total = np.float32(0.0)
            agg._any_pos = any(w > 0
                               for w in agg.sample_num_dict.values())
        agg.buffered_peak = len(agg.model_dict)
        self.live_history = list(state["live_history"] or [])
        self.ft_counters.update(
            {k: int(v) for k, v in (state["ft_counters"] or {}).items()})
        self.cp_counters.update(
            {k: int(v) for k, v in (state["cp_counters"] or {}).items()})
        self._resynced_round = {
            int(k): int(v)
            for k, v in (state["resynced_round"] or {}).items()}
        rd = state["round_deadline_s"]
        self.round_deadline_s = float(rd) if rd is not None else None
        self.min_quorum_frac = float(state["min_quorum_frac"])
        self._extensions_this_round = int(state["extensions_this_round"])
        rc = state["round_cohort"]
        self._round_cohort = ([int(i) for i in rc]
                              if rc is not None else None)
        if self._pace is not None:
            self._pace.load_state(state.get("pace"))
        # the restored model is a new object: invalidate the capture
        # cache and bump the serialization token so the next snapshot
        # re-serializes it instead of reusing pre-restore bytes
        self._gm_capture_cache = None
        self._model_version += 1
        self._restore_extra(state)

    def _save_control_snapshot(self) -> None:
        """Durably snapshot the control state (no-op without a
        checkpointer). A failed save warns loudly but never kills the
        round loop — the federation keeps training, unprotected.

        With the async writer this is an O(capture) hand-off: the round
        thread pays the host copy only (``cp_capture_ms``); the
        serialize+fsync+publish cost (``cp_flush_ms``) rides the writer
        thread (the last COMPLETED flush is reported — a gauge, not an
        in-flight probe). In ``--checkpoint_sync`` mode both phases run
        inline here, which is exactly what the ``round_overheads`` bench
        measures against."""
        if self._server_ckpt is None:
            return
        try:
            t0 = time.perf_counter()
            state = self._capture_control_state()
            # version tokens for the incremental serializer: the model's
            # bytes change only at aggregation/restore; the mirror's
            # only when a broadcast advances it
            versions = {"global_model": int(self._model_version),
                        "mirror": int(self._bcast_seq)}
            t1 = time.perf_counter()
            self._server_ckpt.save(state, versions=versions)
            t2 = time.perf_counter()
            self.cp_counters["checkpoints"] += 1
            tm = getattr(self, "round_timer", None)
            if tm is not None:
                tm.gauge("cp_capture_ms", (t1 - t0) * 1e3)
                stats_fn = getattr(self._server_ckpt, "stats", None)
                if stats_fn is not None:  # async: writer-thread flush
                    tm.gauge("cp_flush_ms", stats_fn()["last_flush_ms"])
                else:  # sync: the save() above ran the flush inline
                    tm.gauge("cp_flush_ms", (t2 - t1) * 1e3)
        except Exception:
            logging.warning(
                "server control snapshot failed at round %d — the "
                "schedule continues WITHOUT failover protection",
                self.round_idx, exc_info=True)

    def _fail_schedule(self, reason: str) -> None:
        """Terminal scheduling failure: checkpoint the final state,
        FINISH every silo, latch the error for the launcher to raise."""
        from fedml_tpu.control import SchedulingStallError
        self.scheduling_error = SchedulingStallError(reason)
        logging.error("%s", self.scheduling_error)
        self._save_control_snapshot()
        self._finish_federation()

    def _aggregate_round(self, partial: bool = False):
        """Close the round: default is the plain sample-weighted average
        (over every reporter when ``partial`` — the weighted
        straggler-tolerant close); FedOpt overrides with a persistent
        server-optimizer step."""
        return (self.aggregator.aggregate_available() if partial
                else self.aggregator.aggregate())

    def _maybe_restore_control_state(self) -> None:
        """One-shot failover restore. Deliberately NOT in ``__init__``:
        subclass constructors (quorum, FedOpt) finish installing their
        own round-state fields after ``super().__init__`` and the restore
        must win over every construction-time default. Called from the
        top of both :meth:`run` (before the receive loop drains queued
        JOINs/heartbeats from an already-waiting fleet) and
        :meth:`send_init_msg` — whichever the launcher reaches first."""
        if self._server_ckpt is None:
            return
        with self._restore_lock:
            if self._control_restored:
                return
            snap = self._server_ckpt.load_latest()
            if snap is not None:
                self._restore_control_state(snap)
                self.cp_counters["restores"] += 1
                logging.warning(
                    "server control plane RESTORED from %s at round %d "
                    "(live=%s, %d pending replies) — resuming the "
                    "schedule mid-flight",
                    self._server_ckpt.directory, self.round_idx,
                    sorted(self.liveness.live_workers()),
                    self.aggregator.received_count())
            # latch AFTER success: if the restore refused (format or
            # schedule mismatch), the racing other entry point (run vs
            # send_init_msg) must retry and re-raise the refusal loudly
            # on ITS thread instead of silently proceeding from round 0
            self._control_restored = True

    def run(self) -> None:
        self._maybe_restore_control_state()
        super().run()

    def send_init_msg(self) -> None:
        self._maybe_restore_control_state()
        if self.round_idx >= self.comm_round:
            # resumed from a checkpoint of an already-finished run
            self._finish_federation()
            return
        idxs = self.aggregator.client_sampling(
            self.round_idx, self.client_num_in_total, self.worker_num)
        # first broadcast of a (possibly resumed) run: the mirror is unset,
        # so _encode_broadcast sends full precision and (re)bases everyone
        self._broadcast_model(MSG_TYPE_S2C_INIT_CONFIG, idxs)
        self._arm_deadline()

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            MSG_TYPE_C2S_SEND_MODEL,
            self.handle_message_receive_model_from_client)
        self.register_message_receive_handler(
            MSG_TYPE_ROUND_TIMEOUT, self.handle_round_timeout)
        self.register_message_receive_handler(
            MSG_TYPE_C2S_HEARTBEAT, self.handle_message_heartbeat)
        self.register_message_receive_handler(
            MSG_TYPE_C2S_JOIN, self.handle_message_join)

    def receive_message(self, msg_type: int, msg: Message) -> None:
        # liveness piggybacks on EVERY inbound silo message — a silo
        # mid-local-train proves life with its reply, idle silos with the
        # periodic heartbeat
        sender = msg.get_sender_id()
        if sender != self.rank:
            self.liveness.beat(sender - 1)
        super().receive_message(msg_type, msg)

    # -- deadline timer (single-threaded state machine preserved) -----------
    def _arm_deadline(self) -> None:
        """Post a self-addressed TIMEOUT tick ``round_deadline_s`` from
        now (no-op without a deadline). The timer thread never touches
        protocol state — the tick rides the normal receive loop."""
        if not self.round_deadline_s:
            return
        self._cancel_deadline()
        round_idx = self.round_idx

        def fire():
            tick = Message(MSG_TYPE_ROUND_TIMEOUT, self.rank, self.rank)
            tick.add(MSG_ARG_KEY_ROUND, round_idx)
            try:
                self.send_message(tick)
            except OSError as exc:  # backend already shut down
                logging.debug("round-%d deadline tick not delivered (%r)",
                              round_idx, exc)

        self._timer = threading.Timer(self.round_deadline_s, fire)
        self._timer.daemon = True
        self._timer.start()

    def _cancel_deadline(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def finish(self) -> None:
        self._cancel_deadline()
        super().finish()

    def _finish_federation(self) -> None:
        """FINISH every silo (evicted ones included — a dead peer's send
        failure is logged, not fatal: the federation is done either way)
        and stop the server loop."""
        if self.publish_model is not None:
            # the LAST aggregate is never broadcast (the schedule ends) —
            # publish it full so the endpoint serves the final model
            try:
                with self._device_lock:
                    final = _to_numpy(self.global_model)
                self.publish_model(self.round_idx, final)
            except Exception:
                logging.warning("final serving publish failed",
                                exc_info=True)
        for worker in range(1, self.size):
            try:
                self.send_message(
                    Message(MSG_TYPE_S2C_FINISH, self.rank, worker))
            except OSError as exc:
                logging.warning("FINISH to silo %d failed (%r) — peer "
                                "already gone", worker, exc)
        self.finish()
        # close barrier: the async writer publishes its pending snapshot
        # and the ledger flush-on-close fsyncs before the launcher (or
        # the extension-exhaustion error path) lets the process die. The
        # synchronous checkpointer's close is the same ledger flush.
        if self._server_ckpt is not None:
            try:
                self._server_ckpt.close()
            except Exception:
                logging.warning("checkpoint close barrier failed",
                                exc_info=True)
            # fold the run's durability counters into the timer AFTER
            # the close barrier (flush-on-close fsyncs included) so the
            # overheads bench reads fsyncs-per-run without reaching
            # into the now-closed checkpointer
            tm = getattr(self, "round_timer", None)
            if tm is not None:
                raw = getattr(self._server_ckpt, "inner",
                              self._server_ckpt)
                tm.count("cp_fsync_total",
                         int(getattr(raw, "fsync_count", 0)))
                tm.count("cp_ledger_fsyncs",
                         int(getattr(raw, "ledger_fsync_count", 0)))

    # -- downlink compression (comm/policy.py, comm/compression.py) ---------
    def _silos_in_sync(self) -> bool:
        """True iff at least one silo has confirmed a base and every
        reported (seq, fingerprint) matches the mirror exactly. An fp
        mismatch (version skew, a silo rebuilt with different shapes) is
        loud; a seq mismatch is value-level staleness — a broadcast that
        left the server but never reached the silo (dropped link) would
        leave its base VALUES behind while the structural fp still
        matches, so both degrade to a full-precision rebase: a shared
        compressed broadcast is only decodable when every silo holds the
        SAME mirror. In the all-received server every fresh reply
        reports the current seq, so steady-state compression is
        unaffected; a quorum straggler costs one full broadcast and
        re-syncs on its next reply."""
        if not self._worker_base:
            return False
        for worker, (seq, fp) in self._worker_base.items():
            if fp != self._mirror_fp:
                logging.warning(
                    "silo %d reports base fingerprint %s but the mirror is "
                    "%s — falling back to a full-precision broadcast",
                    worker + 1, fp, self._mirror_fp)
                return False
            if seq != self._bcast_seq:
                logging.debug(
                    "silo %d last confirmed broadcast seq %d (current %d) "
                    "— full-precision rebase", worker + 1, seq,
                    self._bcast_seq)
                return False
        return True

    def _encode_broadcast(self):
        """Encode the global model for this round's broadcast.

        Full precision the first time (INIT, incl. after resume — fresh
        silos hold nothing) and whenever :meth:`_silos_in_sync` fails;
        otherwise a compressed delta against the mirror. The mirror then
        advances by exactly what the silos will decode, so downlink
        compression error (top-k truncation, int8 rounding) feeds back
        implicitly: un-sent mass stays in the next (global - mirror) gap.
        """
        from fedml_tpu.comm.compression import (compress_for_policy,
                                                decompress,
                                                tree_fingerprint)
        pol = self._policy
        # the sync check compares silo reports against the seq they
        # could have seen — BEFORE this broadcast takes the next one
        in_sync = (pol.downlink_enabled and self._mirror is not None
                   and self._silos_in_sync())
        self._bcast_seq += 1
        with self._device_lock:  # D2H transfer is a device dispatch
            full = _to_numpy(self.global_model)
        if not in_sync:
            self._mirror = full
            self._mirror_fp = tree_fingerprint(full)
            return full
        t0 = time.perf_counter()
        with self._device_lock:  # delta compression is device compute
            key = jax.random.fold_in(jax.random.key(1733), self._bcast_seq)
            payload, _ = compress_for_policy(full, self._mirror, None, key,
                                             pol)
            self._mirror = _to_numpy(decompress(payload, self._mirror))
        tm = getattr(self, "round_timer", None)
        if tm is not None:
            tm.gauge("codec_encode_ms", (time.perf_counter() - t0) * 1e3)
        return payload

    def _broadcast_model(self, msg_type: int, idxs) -> None:
        """One shared payload (full or mirror-delta) to every silo.

        FT mode broadcasts to the LIVE set only (evicted silos come back
        through JOIN + resync, never a shared compressed delta they have
        no base for), and a send that exhausts its transport retries
        evicts the peer instead of killing the server loop."""
        payload = self._encode_broadcast()
        if self.publish_model is not None:
            # serving rollout feed: the broadcast payload doubles as the
            # checkpoint delta (full on INIT/fallback, mirror delta in
            # steady state) — published BEFORE the sends so the endpoint
            # swaps round r in while round r trains
            try:
                self.publish_model(self.round_idx, payload)
            except Exception:
                logging.warning("serving publish for round %d failed — "
                                "training continues unaffected",
                                self.round_idx, exc_info=True)
        live = self.liveness.live_workers()
        # ledger payload + the latency origin every reply is measured from
        self._round_cohort = [int(idxs[w - 1]) for w in range(1, self.size)]
        self._bcast_at = time.monotonic()
        # flight-recorder round boundary: snapshot the counter state so
        # _close_round's end_round attributes deltas to THIS round, and
        # open any anomaly-armed one-shot profile window (pure observer)
        tm = getattr(self, "round_timer", None)
        if tm is not None:
            tm.begin_round(self.round_idx)
        if self.obs is not None:
            self.obs.round_begin(self.round_idx)
        # ONE encode for the whole fan-out: every per-peer frame splices
        # the cached header+buffers and contributes only its envelope
        # keys. A fresh wrapper per broadcast is the cache invalidation —
        # round r+1's payload can never reuse round r's frames.
        shared = SharedPayload(payload)
        msgs = []
        for worker in range(1, self.size):
            if self._evict_on_deadline and (worker - 1) not in live:
                continue
            msg = Message(msg_type, self.rank, worker)
            msg.add(MSG_ARG_KEY_MODEL_PARAMS, shared)
            msg.add(MSG_ARG_KEY_CLIENT_INDEX, int(idxs[worker - 1]))
            msg.add(MSG_ARG_KEY_ROUND, self.round_idx)
            msg.add(MSG_ARG_KEY_BCAST_SEQ, self._bcast_seq)
            msgs.append(msg)  # ft: allow[FT008] one envelope per live silo, dropped at loop exit — bounded by silo count, not population
        bcast = getattr(self.com_manager, "broadcast", None)
        t0 = time.monotonic()
        if bcast is not None:
            # overlapped fan-out: enqueue on per-peer writer threads and
            # return; a peer whose queue overflows or whose retries
            # exhaust is evicted from the writer thread via on_error.
            # Without FT mode there is no eviction path, so on_error
            # stays None and the first failure propagates (sequentially,
            # matching the legacy loop).
            stats = bcast(msgs, on_error=(self._on_broadcast_send_error
                                          if self._evict_on_deadline
                                          else None))
        else:
            # backend without a broadcast API (duck-typed stubs): the
            # legacy sequential loop, same eviction semantics
            stats = {"max_queue_depth": 0}
            for msg in msgs:
                try:
                    self.send_message(msg)
                except OSError as exc:
                    if not self._evict_on_deadline:
                        raise
                    self._on_broadcast_send_error(msg.get_receiver_id(),
                                                  exc)
        if tm is not None:
            tm.gauge("bcast_fanout_ms", (time.monotonic() - t0) * 1e3)
            tm.gauge("send_queue_depth", stats["max_queue_depth"])

    def _on_broadcast_send_error(self, worker_rank: int, exc) -> None:
        """Per-peer broadcast failure -> eviction. MAY run on a comm
        writer thread (overlapped fan-out): evict() is internally locked,
        and the _worker_base pop is a GIL-atomic dict op; a silo that
        slips past an in-flight round's cohort is swept by the deadline
        path, which re-checks liveness."""
        if self.liveness.evict(worker_rank - 1):
            self._worker_base.pop(worker_rank - 1, None)
            logging.warning(
                "broadcast to silo %d failed after transport "
                "retries (%r) — EVICTED from the live set; it "
                "re-admits via JOIN", worker_rank, exc)

    def _note_worker_base(self, msg: Message) -> None:
        """Record which model version/structure the silo reports holding
        (compressed-reply decode base + the downlink fallback trigger)."""
        params = msg.get_params()
        if MSG_ARG_KEY_BASE_FP in params:
            self._worker_base[msg.get_sender_id() - 1] = (
                int(params.get(MSG_ARG_KEY_BASE_SEQ, -1)),
                params[MSG_ARG_KEY_BASE_FP])

    def _decode_model_payload(self, payload):
        """Compressed replies are rebuilt against the MIRROR — the model
        state the silos actually hold (equal to the round's broadcast;
        with downlink compression that trails the exact global model).
        Full-precision replies pass through."""
        from fedml_tpu.comm.compression import decompress, is_compressed
        if not is_compressed(payload):
            return payload
        base = self._mirror if self._mirror is not None else self.global_model
        return decompress(payload, base)

    def handle_message_receive_model_from_client(self, msg: Message) -> None:
        worker = msg.get_sender_id() - 1
        self._note_worker_base(msg)
        if self._evict_on_deadline:
            r = msg.get_params().get(MSG_ARG_KEY_ROUND, self.round_idx)
            if r != self.round_idx:
                # a straggler's reply for an already-closed round: its
                # update is stale against the advanced global — discard
                # (the silo stays live; it got/gets the next broadcast)
                self.ft_counters["stale_replies"] += 1
                return
            if self.liveness.admit(worker):
                # a current-round reply from an evicted silo IS proof of
                # life and a usable contribution — re-admit
                logging.info("silo %d re-admitted on a live round-%d "
                             "reply", worker + 1, r)
        # per-silo flight row: the server-measured report latency plus
        # whatever compact digest the silo piggybacked — the
        # cross-process half of the merged round timeline
        obs_row = None
        if self.obs is not None:
            obs_row = {"kind": "silo", "round": int(self.round_idx),
                       "silo_rank": int(worker + 1), "event": "reply"}
            digest = msg.get_params().get(MSG_ARG_KEY_OBS_DIGEST)
            if digest is not None:
                obs_row["digest"] = digest
        if self._bcast_at is not None:
            latency = time.monotonic() - self._bcast_at
            if self._resynced_round.get(worker) == self.round_idx:
                # churn-poisoning guard: a rejoin-resync reply's
                # broadcast->reply latency measures the OUTAGE plus the
                # resync detour, not the silo's report pace — a flap
                # burst's worth of them would inflate the steered
                # deadline (p90 x margin) for a full quantile-window
                # width. Excluded from the steering evidence, counted;
                # the flight row below still records the raw latency.
                self.cp_counters["resync_latency_skips"] += 1
            else:
                # the report-latency distribution pace steering feeds on
                self.liveness.observe_report_latency(worker, latency)
            if obs_row is not None:
                obs_row["report_latency_s"] = round(latency, 6)
        if obs_row is not None:
            self.obs.recorder.append(obs_row)
        try:
            with self._device_lock:  # delta decompression is device compute
                payload = self._decode_model_payload(
                    msg.get(MSG_ARG_KEY_MODEL_PARAMS))
        except Exception:
            if not self._evict_on_deadline:
                raise
            # corrupted frame (the payload-level guards — structure
            # fingerprint, top-k index bounds — refused to rebuild):
            # drop the reply, poison the silo's reported base so the next
            # broadcast falls back to FULL precision via _silos_in_sync,
            # and let the deadline close the round without this reply
            self.ft_counters["corrupt_frames"] += 1
            self._worker_base[worker] = (-2, "corrupt-frame")
            logging.warning(
                "silo %d round-%d reply failed to decode — dropping the "
                "reply and forcing a full-precision rebase", worker + 1,
                self.round_idx, exc_info=True)
            return
        t0 = time.monotonic()
        with self._device_lock:  # the streaming fold is device compute
            self.aggregator.add_local_trained_result(
                worker, payload, msg.get(MSG_ARG_KEY_NUM_SAMPLES))
        tm = getattr(self, "round_timer", None)
        if tm is not None:
            # slowest incremental fold this run; close-drain is gauged
            # into the same metric by _close_round
            tm.gauge("agg_fold_ms", (time.monotonic() - t0) * 1e3)
        if self._evict_on_deadline:
            live = self.liveness.live_workers()
            reported = self.aggregator.reported_set()
            if live <= reported:
                self._close_round(partial=len(reported) < self.worker_num)
            return
        if self.aggregator.check_whether_all_receive():
            self._close_round()

    def _credit_wire_bytes(self) -> None:
        """Credit the transport endpoint's CUMULATIVE byte counters into
        the round timer as deltas since the last credit. Called at every
        round close (per-round wire accounting for the flight deck) and
        once more by the launcher after FINISH (the remainder), so the
        run totals stay exactly the endpoint's totals."""
        tm = getattr(self, "round_timer", None)
        if tm is None:
            return
        sent = int(getattr(self.com_manager, "bytes_sent", 0))
        recv = int(getattr(self.com_manager, "bytes_received", 0))
        d_down, self._wire_credited_down = (sent - self._wire_credited_down,
                                            sent)
        d_up, self._wire_credited_up = (recv - self._wire_credited_up,
                                        recv)
        if d_down:
            tm.count("comm_bytes_down", d_down)
        if d_up:
            tm.count("comm_bytes_up", d_up)

    def _close_round(self, partial: bool = False) -> None:
        """Aggregate (full or weighted-partial), advance, broadcast the
        next round or FINISH. Shared by the strict barrier, the
        deadline-eviction close, and the quorum subclass."""
        # NOTE: in single-process actor mode the device lock below also
        # waits for any straggler local_train already ON the shared device
        # — a deadline can fire at t but the close lands when the device
        # frees up. That is shared-chip physics (one dispatch queue), not
        # a protocol property; multi-process deployments (one device per
        # silo) close at the deadline proper.
        self._cancel_deadline()
        reported = sorted(self.aggregator.reported_set())
        live_n = (len(self.liveness.live_workers())
                  if self._evict_on_deadline else self.worker_num)
        if self._evict_on_deadline:
            self.live_history.append({
                "round": self.round_idx,
                "reported": reported,
                "live": sorted(self.liveness.live_workers()),
                "partial": bool(partial)})
            if partial:
                self.ft_counters["partial_rounds"] += 1
        buffered_peak = self.aggregator.buffered_peak
        t0 = time.monotonic()
        with self._device_lock:
            self.global_model = self._aggregate_round(partial=partial)
        # aggregation produced a new model: its serialized bytes changed
        self._model_version += 1
        tm = getattr(self, "round_timer", None)
        if tm is not None:
            # the close is just the residual-suffix drain + normalize
            # under the streaming fold — the latency the old buffered
            # stack-reduce paid here is what fanout_agg measures
            tm.gauge("agg_fold_ms", (time.monotonic() - t0) * 1e3)
            tm.gauge("agg_buffered_peak", buffered_peak)
        if self.on_round_done is not None:
            # outside the lock: eval re-locks internally, sink I/O doesn't
            self.on_round_done(self.round_idx, self.global_model)
        # flight-recorder round close: the snapshot-delta record carries
        # the SAME cohort/reported/partial row the ledger will get, so
        # the merge tool can cross-check the two; the measured duration
        # feeds the slow-round anomaly detector. Wire bytes are credited
        # as deltas-since-last-close FIRST, so the record's counter
        # delta is this round's real wire traffic (obs/perf.py derives
        # wire_bytes_per_sec from exactly this).
        self._credit_wire_bytes()
        tm = getattr(self, "round_timer", None)
        # availability extras ride the flight record only (never the
        # ledger): rejoin/throttle trajectories and the deadline this
        # round actually ran under feed the `obs report` availability
        # section; wan_* adds the population-scale churn estimates
        extra = {
            "cohort": self._round_cohort,
            "reported": [int(w) for w in reported],
            "live": sorted(int(w)
                           for w in self.liveness.live_workers()),
            "partial": bool(partial),
            "evictions": int(self.liveness.evictions),
            "rejoins": int(self.liveness.rejoins),
            "joins_throttled": int(self.cp_counters["joins_throttled"]),
            "deadline_s": (float(self.round_deadline_s)
                           if self.round_deadline_s else None),
        }
        if self._wan is not None and tm is not None:
            # drain the world's sampling counters into THIS round's
            # delta, then fold the population-scale churn estimate
            # (mass JOIN wave vs the shadow admission bucket — all
            # deterministic functions of (trace seed, round))
            for k, v in self._wan.drain_counters().items():
                tm.count(k, v)
            joins, leaves, throttled = self._wan.mass_churn(self.round_idx)
            if joins:
                tm.count("wan_mass_joins", joins)
            if leaves:
                tm.count("wan_mass_leaves", leaves)
            if throttled:
                tm.count("wan_mass_join_throttled", throttled)
            frac = self._wan.available_frac(self.round_idx)
            if frac is not None:
                tm.gauge("wan_available_frac", frac)
                extra["wan_available_frac"] = round(frac, 4)
        round_rec = None
        if tm is not None:
            round_rec = tm.end_round(self.round_idx, extra=extra)
        if self.obs is not None:
            # the record pass feeds the perf accountant (obs/perf.py):
            # the server derives wire bytes/s + memory watermarks per
            # round (MFU stays silo-side — the server only aggregates)
            self.obs.round_end(
                self.round_idx,
                round_rec["duration_s"] if round_rec else None,
                record=round_rec)
            # group-commit telemetry: flight fsync batches since the
            # last close (credited after end_round, so the counter rolls
            # into the NEXT round's delta — totals stay exact)
            pop_fb = getattr(getattr(self.obs, "recorder", None),
                             "pop_fsync_batches", None)
            if pop_fb is not None and tm is not None:
                batches = pop_fb()
                if batches:
                    tm.count("obs_fsync_batches", batches)
        deadline_used = self.round_deadline_s
        self.round_idx += 1
        if self.checkpoint_mgr is not None:
            self.checkpoint_mgr.save(self.round_idx,
                                     self._checkpoint_state())
        # -- pace steering: derive the NEXT round's deadline + quorum
        #    target from the observed report-latency distribution and
        #    recent participation (control/pace.py; off = static flags)
        if self._pace is not None and self.round_deadline_s:
            self._pace.observe_round(len(reported), max(1, live_n))
            new_d = self._pace.next_deadline(
                self.liveness.report_latencies)
            new_q = self._pace.next_quorum_frac()
            if (new_d != self.round_deadline_s
                    or new_q != self.min_quorum_frac):
                self.cp_counters["deadline_adjustments"] += 1
                tm = getattr(self, "round_timer", None)
                if tm is not None:
                    tm.gauge("cp_steered_deadline_s", new_d)
                logging.info(
                    "pace steering: round %d deadline %.3fs -> %.3fs, "
                    "quorum frac %.3f -> %.3f (p90 report latency %s)",
                    self.round_idx, deadline_used or 0.0, new_d,
                    self.min_quorum_frac, new_q,
                    self.liveness.report_latencies.quantile(0.9))
            self.round_deadline_s = new_d
            self.min_quorum_frac = new_q
        # the NEW round enters with a full extension budget — reset
        # BEFORE the boundary snapshot, or a restored server would start
        # the next round already charged for the closed round's
        # extensions and could hit the cap spuriously under exactly the
        # degraded-fleet conditions failover exists for
        self._extensions_this_round = 0
        # -- durable round boundary: ledger line first, snapshot second
        #    (a crash between the two re-closes this round after restore
        #    and re-appends — readers dedup by round keeping the last)
        if self._server_ckpt is not None:
            self._server_ckpt.append_ledger({
                "round": self.round_idx - 1,
                "cohort": self._round_cohort,
                "reported": reported,
                "partial": bool(partial),
                "deadline_s": deadline_used})
            self._save_control_snapshot()
            # async-writer backpressure telemetry: snapshots skipped by
            # the depth-1 newest-wins slot since the last close
            pop = getattr(self._server_ckpt, "pop_coalesced", None)
            if pop is not None:
                coalesced = pop()
                if coalesced:
                    tm = getattr(self, "round_timer", None)
                    if tm is not None:
                        tm.count("cp_writer_queue_coalesced", coalesced)
        if self.round_idx == self.comm_round:
            self._finish_federation()
            return
        self._wan_admit_pending()
        idxs = self.aggregator.client_sampling(
            self.round_idx, self.client_num_in_total, self.worker_num)
        self._broadcast_model(MSG_TYPE_S2C_SYNC_MODEL, idxs)
        self._arm_deadline()

    def _wan_admit_pending(self) -> None:
        """Round-boundary rejoin batching (WAN mode): silos whose JOIN
        was deferred while their device's trace was offline are
        re-admitted at the first round boundary where the trace flips
        online — so the rejoin ROUND is a pure function of the trace
        seed (the ledger-replay property), not of the race between the
        JOIN retry cadence and the other silos' replies. The admitted
        silo rides the regular next broadcast; its reported base is
        poisoned so that broadcast falls back to FULL precision — the
        same one-full-frame-per-rejoin coherence rule the direct JOIN
        resync path uses."""
        if self._wan is None or not self._wan_pending_joins:
            return
        for worker in sorted(self._wan_pending_joins):
            if not self._wan.silo_online(worker + 1, self.round_idx):
                continue
            # ft: allow[FT009] transient WAN rejoin bookkeeping (see _wan_pending_joins)
            self._wan_pending_joins.discard(worker)
            self.liveness.admit(worker)
            self._worker_base[worker] = (-3, "wan-rejoin")
            # the first reply after an outage measures the outage, not
            # the silo's pace — same steering exclusion as a resync
            # ft: allow[FT008] keyed by SILO index (worker_num entries, tens) — the per-silo resync ledger, not per-client state
            self._resynced_round[worker] = self.round_idx
            logging.info(
                "silo %d re-admitted at round %d (WAN trace back online; "
                "deferred JOIN batch) — next broadcast full-rebases it",
                worker + 1, self.round_idx)

    # -- fault-tolerance handlers (deadline / heartbeat / rejoin) -----------
    def handle_round_timeout(self, msg: Message) -> None:
        """Deadline policy: close with a weighted partial aggregate once
        ≥ ceil(min_quorum_frac · live) reports are in, EVICTING the
        non-reporting live silos; below quorum, extend the deadline (a
        premature close with almost no mass would poison the global
        model). The quorum subclass overrides with its absolute-count
        policy."""
        if msg.get(MSG_ARG_KEY_ROUND) != self.round_idx:
            return  # timer from an already-closed round
        if not self._evict_on_deadline:
            return
        live = self.liveness.live_workers()
        reported = self.aggregator.reported_set()
        if self._wan is not None:
            # the trace IS the availability oracle: a live silo whose
            # device is offline at this round can never report, so it
            # must not sit in the quorum DENOMINATOR — a diurnal cliff
            # under a steered-up quorum would otherwise extend straight
            # into the stall cap (observed: 3 of 4 silos drop at the
            # trough while steering holds quorum at p25 of the healthy
            # past). Evict the known-dark non-reporters now; they
            # rejoin through the trace-gated JOIN path like any other
            # eviction. The WAN layer degrades schedules, it never
            # deadlocks them.
            for w in sorted(live - reported):
                if not self._wan.silo_online(w + 1, self.round_idx) \
                        and self.liveness.evict(w):
                    self._worker_base.pop(w, None)
                    logging.warning(
                        "silo %d is trace-offline at the round-%d "
                        "deadline — evicted from the quorum denominator "
                        "(WAN availability oracle)", w + 1, self.round_idx)
            live = self.liveness.live_workers()
        need = max(1, math.ceil(self.min_quorum_frac * max(1, len(live))))
        if self._pace is not None and len(live) > 1:
            # steering's no-deadlock invariant lives HERE, not in the
            # fraction: ceil(0.9 * n) is n for every n <= 10, so a
            # steered fraction alone would still demand EVERY live silo
            # on small (i.e. typical cross-silo) fleets and one silently
            # hung silo — which never triggers a send error, so it is
            # only evicted at a quorum-met close — would stall the
            # schedule into the extension cap. With steering active the
            # effective requirement is capped at live-1; the static-flag
            # path keeps exact legacy semantics (an explicit
            # --min_quorum_frac 1.0 means what it says).
            need = min(need, len(live) - 1)
        if len(reported) < need:
            if self._note_deadline_extension():
                self._fail_schedule(
                    f"round {self.round_idx} is still below quorum "
                    f"({len(reported)}/{len(live)} reports, need {need}) "
                    f"after {self._extensions_this_round - 1} deadline "
                    f"extensions (--max_deadline_extensions="
                    f"{self._max_extensions}) — the federation cannot "
                    "make progress; final state checkpointed")
                return
            if self.obs is not None:
                # a quorum extension is exactly the "round is not
                # closing" signal the flight recorder exists for: record
                # it and arm a one-shot profile of the next round
                self.obs.note_anomaly(
                    "deadline_extension", self.round_idx,
                    {"reported": len(reported), "live": len(live),
                     "need": int(need),
                     "extensions": int(self._extensions_this_round)})
            logging.warning(
                "round %d deadline passed with %d/%d reports (quorum %d) "
                "— extending the deadline (%d/%s extensions used)",
                self.round_idx, len(reported), len(live), need,
                self._extensions_this_round,
                self._max_extensions
                if self._max_extensions is not None else "inf")
            # mid-round durability: the partials in hand survive a kill
            # during a long extension stretch
            self._save_control_snapshot()
            self._arm_deadline()
            return
        for w in sorted(live - reported):
            if self.liveness.evict(w):
                self._worker_base.pop(w, None)
                logging.warning(
                    "silo %d missed the %.1fs round-%d deadline — "
                    "EVICTED from the live set (its pending "
                    "error-feedback residual mass is dropped: the same "
                    "loss class as the quorum server's stale-reply "
                    "discard; it re-admits via JOIN with a full resync)",
                    w + 1, self.round_deadline_s, self.round_idx)
        self._close_round(partial=True)

    def _note_deadline_extension(self) -> bool:
        """Count one below-quorum deadline extension; True when the
        per-round budget (``--max_deadline_extensions``) is exhausted —
        the caller must fail the schedule loudly instead of extending
        forever (the pre-control-plane behavior, kept via ``None``)."""
        self._extensions_this_round += 1
        self.ft_counters["deadline_extensions"] += 1
        return (self._max_extensions is not None
                and self._extensions_this_round > self._max_extensions)

    def handle_message_heartbeat(self, msg: Message) -> None:
        # the beat itself landed in receive_message; the handler only
        # keeps the count observable
        self.ft_counters["heartbeats"] += 1
        if self.obs is not None:
            digest = msg.get_params().get(MSG_ARG_KEY_OBS_DIGEST)
            if digest is not None:
                # idle-silo digests keep the per-silo timeline moving
                # between replies (an evicted silo still shows up)
                self.obs.recorder.append(
                    {"kind": "silo", "round": int(self.round_idx),
                     "silo_rank": int(msg.get_sender_id()),
                     "event": "heartbeat", "digest": digest})

    def handle_message_join(self, msg: Message) -> None:
        """Re-admit a restarted/evicted silo: mark live, forget its stale
        base report, and resync it with the FULL-precision silo mirror —
        the model every in-sync silo currently holds — so the shared
        downlink compression chain stays coherent (the rejoined silo
        decodes the next mirror delta like everyone else)."""
        worker = msg.get_sender_id() - 1
        done = msg.get_params().get(MSG_ARG_KEY_ROUNDS_COMPLETED, None)
        if self.liveness.is_live(worker) \
                and self.aggregator.has_reported(worker):
            # a live silo that already reported this round is just waiting
            # out the deadline with us — it is not lost, so no resync
            # (which would only trigger a redundant retrain)
            return
        # WAN rejoin gate (fedml_tpu/wan): the silo's device is still
        # offline in the availability trace — its JOIN is real protocol
        # traffic, but the DEVICE it speaks for has not come back yet.
        # Checked before admission so a deferred JOIN never burns a
        # token. Anchoring rejoin to the trace (instead of to wall-clock
        # luck) is also what makes a churn run's ledger replayable.
        wan_offline = (self._wan is not None
                       and not self._wan.silo_online(worker + 1,
                                                     self.round_idx))
        if wan_offline:
            # remember the request: the round-boundary batch admit
            # (_wan_admit_pending) re-admits this silo at the FIRST
            # round its device's trace is online again — deterministic
            # rejoin rounds, the ledger-replay property
            # ft: allow[FT009] transient WAN rejoin bookkeeping — a restored server loses it and the silos' JOIN retries rebuild it; not schedule state
            self._wan_pending_joins.add(worker)
            # anti-starvation valve: the virtual clock advances only at
            # round closes — if every live silo went dark, the round
            # extends forever at a frozen trace and every JOIN would be
            # deferred forever. Cap the deferrals-per-round and admit
            # past the cap: the WAN layer degrades schedules, it never
            # deadlocks them.
            r, n = self._wan_join_deferrals.get(worker, (-1, 0))
            n = n + 1 if r == self.round_idx else 1
            # ft: allow[FT009] transient WAN anti-starvation counter — resets harmlessly on failover (the valve re-arms), so it stays out of the snapshot manifest by design
            self._wan_join_deferrals[worker] = (self.round_idx, n)
            if n > self._wan.max_join_deferrals_per_round:
                logging.warning(
                    "silo %d JOIN deferred %d times inside round %d with "
                    "the trace frozen — admitting anyway (WAN "
                    "anti-starvation valve)", worker + 1, n - 1,
                    self.round_idx)
                # the force must reach the silo's OWN agent too (shared
                # world): a server-side admit alone would resync a silo
                # whose agent still drops every broadcast against the
                # frozen trace — the stall would persist
                self._wan.force_online(worker + 1)
                # ft: allow[FT009] transient WAN rejoin bookkeeping (see above)
                self._wan_pending_joins.discard(worker)
                wan_offline = False
        # admission control: a mass rejoin after a partition heals must
        # not stampede the full-precision resync path — throttled JOINs
        # get a BACKPRESSURE reply and the silo defers its next attempt
        # (its heartbeats keep beating the liveness table meanwhile)
        if wan_offline or (self._join_admission is not None
                           and not self._join_admission.try_acquire()):
            if wan_offline:
                tm = getattr(self, "round_timer", None)
                if tm is not None:
                    tm.count("wan_join_deferred")
                retry = float(self._wan.join_retry_s)
            else:
                self.cp_counters["joins_throttled"] += 1
                retry = float(self._join_admission.retry_after_s())
            out = Message(MSG_TYPE_S2C_JOIN_BACKPRESSURE, self.rank,
                          worker + 1)
            out.add(MSG_ARG_KEY_RETRY_AFTER, retry)
            try:
                self.send_message(out)
            except OSError as exc:
                logging.debug("backpressure reply to silo %d failed: %r",
                              worker + 1, exc)
            logging.info("silo %d JOIN %s — backpressure sent", worker + 1,
                         "deferred (device offline in the WAN trace)"
                         if wan_offline
                         else "throttled (admission token bucket empty)")
            return
        self.liveness.admit(worker)
        # ft: allow[FT009] transient WAN rejoin bookkeeping (see _wan_pending_joins)
        self._wan_pending_joins.discard(worker)
        self._worker_base.pop(worker, None)
        if not self._evict_on_deadline:
            # strict-barrier server: JOIN is proof of life only (a resync
            # reply could double-feed the all-received barrier)
            return
        if self.round_idx >= self.comm_round:
            return  # schedule done; _finish_federation already ran/runs
        if self._resynced_round.get(worker) == self.round_idx:
            return  # already resynced this round; its reply is in flight
        self._resynced_round[worker] = self.round_idx
        self.ft_counters["join_resyncs"] += 1
        logging.info(
            "silo %d JOIN (rounds_completed=%s) — re-admitted with a "
            "full-precision mirror resync at round %d", worker + 1, done,
            self.round_idx)
        if self._mirror is not None:
            payload = self._mirror
        else:
            with self._device_lock:  # D2H transfer is a device dispatch
                payload = _to_numpy(self.global_model)
        if self._wan is not None:
            # a REDRAW of this round's already-counted cohort — same
            # pure draw, telemetry-silent (the broadcast's draw owns the
            # per-round sampling counters; see sample_cohort(record=))
            idxs = self._wan.sample_cohort(
                self.round_idx, self.client_num_in_total,
                self.worker_num, record=False)
        else:
            idxs = self.aggregator.client_sampling(
                self.round_idx, self.client_num_in_total, self.worker_num)
        out = Message(MSG_TYPE_S2C_SYNC_MODEL, self.rank, worker + 1)
        out.add(MSG_ARG_KEY_MODEL_PARAMS, payload)
        out.add(MSG_ARG_KEY_CLIENT_INDEX, int(idxs[worker]))
        out.add(MSG_ARG_KEY_ROUND, self.round_idx)
        out.add(MSG_ARG_KEY_BCAST_SEQ, self._bcast_seq)
        try:
            self.send_message(out)
        except OSError as exc:
            if self.liveness.evict(worker):
                logging.warning("resync to rejoining silo %d failed "
                                "(%r) — evicted again", worker + 1, exc)


class FedOptServerManager(FedAvgServerManager):
    """Cross-silo FedOpt: the round closes with a persistent server
    optimizer on the pseudo-gradient instead of installing the average
    (reference fedml_api/distributed/fedopt/FedOptAggregator.py:70-123 —
    avg, ``w_old − w_avg`` into the optimizer, step). Client silos are
    unchanged; only the server's close step differs, so the same
    FedAvgClientManager processes run against either server."""

    def __init__(self, *args, server_optimizer: str = "adam",
                 server_lr: float = 1e-3, server_momentum: float = 0.0,
                 **kw):
        from fedml_tpu.algorithms.fedopt import get_server_optimizer

        global_model = args[6] if len(args) > 6 else kw["global_model"]
        opt_kw = {}
        if server_optimizer == "sgd" and server_momentum:
            opt_kw["momentum"] = server_momentum
        self._server_tx = get_server_optimizer(server_optimizer, server_lr,
                                               **opt_kw)
        self.server_opt_state = self._server_tx.init(global_model["params"])
        server_tx = self._server_tx

        def opt_step(old_params, avg_params, opt_state):
            pseudo_grad = pt.tree_sub(old_params, avg_params)
            updates, opt_state = server_tx.update(pseudo_grad, opt_state,
                                                  old_params)
            import optax
            return optax.apply_updates(old_params, updates), opt_state

        self._opt_step = jax.jit(opt_step)
        # super() last: checkpoint resume may overwrite the fresh opt state
        # through the _load_state hook below
        super().__init__(*args, **kw)

    def _checkpoint_state(self):
        return {"variables": self.global_model,
                "server_opt": self.server_opt_state}

    def _load_state(self, state) -> None:
        self.global_model = state["variables"]
        self.server_opt_state = state["server_opt"]

    def _capture_extra(self, state) -> None:
        from flax import serialization as fser
        state["server_opt"] = fser.to_state_dict(
            jax.tree.map(np.asarray, self.server_opt_state))

    def _restore_extra(self, state) -> None:
        from flax import serialization as fser
        # the freshly-initialized opt state is the structure template, so
        # optax's NamedTuple pytree round-trips through the msgpack dict
        self.server_opt_state = fser.from_state_dict(
            self.server_opt_state, state["server_opt"])

    def _aggregate_round(self, partial: bool = False):
        avg = (self.aggregator.aggregate_available() if partial
               else self.aggregator.aggregate())
        new_params, self.server_opt_state = self._opt_step(
            self.global_model["params"], avg["params"],
            self.server_opt_state)
        # BN/other collections keep the plain average
        return {**avg, "params": new_params}


class FedAvgClientManager(ClientManager):
    """A silo: receives the global model, re-points at its sampled client's
    shard (client virtualization — reference FedAVGTrainer.update_dataset),
    runs the jitted local program, ships (params, n_i) back."""

    def __init__(self, rank: int, size: int, com_manager,
                 dataset: FederatedDataset, module, task: str,
                 train_cfg: TrainConfig, seed: int = 0,
                 compress: bool = False, compression=None,
                 state_dir: Optional[str] = None, resume: bool = False,
                 state_sync: bool = False,
                 prefetch_depth: int = 2,
                 heartbeat_s: float = 0.0,
                 rejoin_idle_s: Optional[float] = None,
                 join_on_start: bool = False,
                 obs=None, device_gate=None, wan_agent=None):
        super().__init__(rank, size, com_manager)
        self.dataset = dataset
        #: WAN world agent (fedml_tpu/wan): when set, this silo embodies
        #: a churning, heterogeneous device — trace-offline rounds drop
        #: the reply and silence heartbeats (the server deadline-evicts
        #: us through the real path), online rounds sleep the embodied
        #: client's profiled report delay before replying. None
        #: (default) = the byte-identical legacy silo.
        self._wan_agent = wan_agent
        #: device mutex (see FedAvgServerManager): the process-wide
        #: _DEVICE_LOCK by default, a per-job fair-share gate under the
        #: federation scheduler
        self._device_lock = (device_gate if device_gate is not None
                             else _DEVICE_LOCK)
        #: observability bundle (fedml_tpu/obs): when set, this silo
        #: writes its own flight log AND piggybacks a compact counter
        #: digest on replies/heartbeats. None (default) = the legacy
        #: byte-identical wire format.
        self._obs = obs
        # -- fault tolerance ------------------------------------------------
        #: periodic proof of life (0 = off, the legacy behavior); the
        #: server ALSO counts every reply as a beat, so the periodic
        #: message only matters while this silo is idle
        self.heartbeat_s = float(heartbeat_s or 0.0)
        #: no server traffic for this long -> assume evicted/forgotten and
        #: send JOIN (the rejoin protocol's client half); default 3 beats
        self.rejoin_idle_s = (rejoin_idle_s if rejoin_idle_s is not None
                              else 3.0 * self.heartbeat_s)
        #: a RESTARTED silo announces itself instead of waiting for a
        #: broadcast that will never come (it is not in the live set)
        self.join_on_start = bool(join_on_start)
        self.rounds_completed = 0
        self._last_s2c = time.monotonic()
        #: JOIN deferral set by a server BACKPRESSURE reply (admission
        #: control) — heartbeats continue, JOIN escalation waits this out
        self._join_backoff_until = 0.0
        #: True while a broadcast handler (local training) is running —
        #: the heartbeat thread must not mistake a long local_train for
        #: an eviction and escalate to JOIN mid-round
        self._busy = False
        #: guards the receive-thread/heartbeat-thread shared flags
        #: (_busy, _last_s2c, _join_backoff_until, rounds_completed) —
        #: a leaf lock, never held across a send or device dispatch
        self._hb_lock = threading.Lock()
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        from fedml_tpu.trainer.functional import validate_accum_steps
        validate_accum_steps(train_cfg, dataset.train_data_local_num_dict)
        self._local_train = _shared_local_train(module, task, train_cfg)
        self._train_cfg = train_cfg
        self._n_pad = dataset.padded_len(train_cfg.batch_size)
        self._bsz = train_cfg.batch_size
        self._base_key = jax.random.key(seed)
        # -- wire compression (comm/policy.py) ------------------------------
        self._policy = resolve_compression(compression, compress=compress)
        self.compress = self._policy.enabled  # legacy introspection
        #: last applied global model (numpy) — the uplink delta base AND
        #: the downlink decode base (the server's mirror of this silo)
        self._held = None
        self._held_seq = -1
        #: uplink error-feedback residual (flat f32, quantize_tree layout):
        #: the mass top-k did NOT send, added to the next round's delta so
        #: the biased compressor still converges (EF-SGD). Checkpointed
        #: per silo under ``state_dir`` so resume keeps the EF trajectory.
        self._residual = None
        self._resume_residual = bool(resume)
        self._state_ckpt = None
        if state_dir and self._policy.uplink_topk:
            from fedml_tpu.state.residuals import SiloResidualStore
            # async write-back by default: the residual flush rides a
            # writer thread off the reply critical path (--checkpoint_sync
            # forces the old inline semantics federation-wide)
            self._state_ckpt = SiloResidualStore(
                state_dir, async_writeback=not state_sync)
        # async round pipeline (parallel/prefetch.py): the server's
        # client_sampling is the deterministic shared stream
        # (core/sampling.sample_clients), so this silo can predict which
        # client it will be handed NEXT round and pack that shard while
        # the current local_train holds the device. Keys are
        # ``(round_idx, client_idx)``: a mispredicting server
        # (async/quorum reassignments) misses on the key and the inline
        # produce then packs the ACTUAL client — one pack per round
        # either way, exactly the serial cost. Host-numpy only — the
        # device lock is never touched off the receive thread; closed on
        # the server's FINISH so no speculated shard outlives the run.
        from fedml_tpu.parallel.prefetch import (RoundPrefetcher,
                                                 resolve_prefetch_depth)
        depth = resolve_prefetch_depth(prefetch_depth)
        self._prefetch = (RoundPrefetcher(self._pack_client, depth,
                                          next_key=self._predict_next,
                                          name=f"silo{rank}-prefetch")
                          if depth > 0 else None)

    def _pack_client(self, key):
        """Pack one client's padded shard for ``key = (round_idx,
        client_idx)`` (numpy; no device). ``client_idx`` None is the
        degenerate silo-outnumbers-pool prediction — nothing to pack."""
        _, client_idx = key
        ds = self.dataset
        if client_idx is None:
            return ds, None
        x, y, mask = ds.pack_clients([client_idx], self._bsz,
                                     n_pad=self._n_pad)
        return ds, (x[0], y[0], mask[0])

    def _predict_next(self, key):
        """Successor key: next round's sampled client for this silo under
        the server's deterministic stream (FedAVGAggregator.py:89-97).
        Under a WAN world the server samples availability-restricted
        cohorts instead — the SAME pure function of the round index, so
        speculation stays exact (telemetry-silent: the server owns the
        sampling counters)."""
        r = key[0] + 1
        if self._wan_agent is not None:
            idxs = self._wan_agent.world.sample_cohort(
                r, self.dataset.client_num, self.size - 1, record=False)
        else:
            idxs = sample_clients(r, self.dataset.client_num,
                                  self.size - 1)
        if self.rank - 1 >= len(idxs):
            return (r, None)
        return (r, int(idxs[self.rank - 1]))

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            MSG_TYPE_S2C_INIT_CONFIG, self.handle_message_init)
        self.register_message_receive_handler(
            MSG_TYPE_S2C_SYNC_MODEL, self.handle_message_init)
        self.register_message_receive_handler(
            MSG_TYPE_S2C_FINISH, self._handle_finish)
        self.register_message_receive_handler(
            MSG_TYPE_S2C_JOIN_BACKPRESSURE, self._handle_join_backpressure)

    def _handle_join_backpressure(self, msg: Message) -> None:
        """The server throttled our JOIN (admission control): defer the
        next JOIN attempt by the advertised retry window. Deliberately
        does NOT refresh ``_last_s2c`` — we are still evicted, the idle
        clock must keep running so the JOIN retries after the backoff."""
        retry = float(msg.get_params().get(
            MSG_ARG_KEY_RETRY_AFTER, max(1.0, self.heartbeat_s)))
        with self._hb_lock:
            self._join_backoff_until = time.monotonic() + retry
        logging.info("silo %d: JOIN backpressured — retrying in %.2fs",
                     self.rank, retry)

    def run(self) -> None:
        self.register_message_receive_handlers()
        if self.join_on_start:
            self._send_join()
        if self.heartbeat_s > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name=f"silo{self.rank}-heartbeat")
            self._hb_thread.start()
        try:
            self.com_manager.handle_receive_message()
        finally:
            self._hb_stop.set()

    def _obs_digest(self) -> Dict:
        """The compact counter digest piggybacked on replies/heartbeats
        when observability is on: cumulative wire bytes, transport
        retries, rounds completed, prefetch and state-cache hit counts,
        plus this endpoint incarnation's stream epoch (the same identity
        the reliable transport stamps frames with) — everything the
        server needs for its per-silo flight rows, a few dozen bytes."""
        from fedml_tpu.obs import endpoint_epoch
        com = self.com_manager
        with self._hb_lock:
            done = self.rounds_completed
        counters = dict(com.all_counters() if hasattr(com, "all_counters")
                        else getattr(com, "counters", {}))
        digest = {"rounds_completed": int(done),
                  "epoch": endpoint_epoch(com) or 0,
                  "bytes_up": int(getattr(com, "bytes_sent", 0)),
                  "bytes_down": int(getattr(com, "bytes_received", 0)),
                  "retries": int(counters.get("retries", 0)),
                  "dedup_drops": int(counters.get("dedup_drops", 0))}
        if self._prefetch is not None:
            st = self._prefetch.stats()
            digest["prefetch_hits"] = int(st.get("hits", 0))
            digest["prefetch_misses"] = int(st.get("misses", 0))
        store = getattr(self.dataset, "store", None)
        if store is not None and hasattr(store, "stats"):
            st = store.stats()
            digest["state_cache_hits"] = int(
                st.get("state_cache_hits", 0))
            digest["state_cache_misses"] = int(
                st.get("state_cache_misses", 0))
        return digest

    def _send_join(self) -> None:
        msg = Message(MSG_TYPE_C2S_JOIN, self.rank, 0)
        with self._hb_lock:
            done = self.rounds_completed
        msg.add(MSG_ARG_KEY_ROUNDS_COMPLETED, done)
        try:
            self.send_message(msg)
        except OSError as exc:
            # the server itself may be down: the next heartbeat tick
            # retries the JOIN (the transport already retried the send)
            logging.warning("silo %d: JOIN not delivered (%r) — will "
                            "retry on the heartbeat cadence", self.rank,
                            exc)

    def _heartbeat_loop(self) -> None:
        """Periodic beat while idle; escalates to JOIN when the server has
        been silent past ``rejoin_idle_s`` (we were evicted, or the
        server restarted and forgot us)."""
        while not self._hb_stop.wait(self.heartbeat_s):
            if self._wan_agent is not None \
                    and not self._wan_agent.online_now():
                # the embodied device is dark: no beats (the server's
                # deadline eviction is the real removal path), no JOIN
                # escalation (rejoin waits for the trace to flip back)
                continue
            with self._hb_lock:  # snapshot the receive-thread flags
                idle = time.monotonic() - self._last_s2c
                busy = self._busy
                backoff_until = self._join_backoff_until
            if (not busy
                    and idle > max(self.rejoin_idle_s, self.heartbeat_s)  # ft: allow[FT015] eviction detection + JOIN backoff are wall-clock contracts: server silence and the advertised retry window are real seconds
                    and time.monotonic() >= backoff_until):
                self._send_join()
                continue
            try:
                beat = Message(MSG_TYPE_C2S_HEARTBEAT, self.rank, 0)
                if self._obs is not None:
                    beat.add(MSG_ARG_KEY_OBS_DIGEST, self._obs_digest())
                self.send_message(beat)
            except OSError as exc:
                logging.debug("silo %d heartbeat failed: %r", self.rank,
                              exc)

    def _handle_finish(self, msg: Message) -> None:
        # nothing follows FINISH: release speculated shards + the worker
        # thread, then shut the protocol down. The residual store's close
        # is the write-back durability barrier — every async save() this
        # run requested is on disk before the protocol exits.
        self._hb_stop.set()
        if self._prefetch is not None:
            self._prefetch.close()
        if self._state_ckpt is not None:
            try:
                self._state_ckpt.close()
            except Exception:
                logging.exception("silo %d: residual store close failed",
                                  self.rank)
        self.finish()

    def _apply_broadcast(self, msg: Message):
        """Decode this round's global model: full payloads install
        directly; compressed downlink deltas rebuild against the held
        model (the structural fingerprint guard inside ``decompress``
        raises loudly on skew). Returns the numpy model tree."""
        from fedml_tpu.comm.compression import decompress, is_compressed
        variables = msg.get(MSG_ARG_KEY_MODEL_PARAMS)
        if is_compressed(variables):
            if self._held is None:
                raise RuntimeError(
                    "silo received a compressed broadcast before any "
                    "full-precision model — the server must send INIT "
                    "full (transport reordering or a protocol bug)")
            with self._device_lock:  # delta rebuild is device compute
                variables = _to_numpy(decompress(variables, self._held))
        self._held = variables
        seq = msg.get_params().get(MSG_ARG_KEY_BCAST_SEQ)
        if seq is not None:
            self._held_seq = int(seq)
        return variables

    def _uplink_residual(self, round_idx: int, variables):
        """EF residual entering this round. On resume it is restored once
        from the silo's state checkpoint at the server's resumed round;
        absent state falls back to zeros (convergence-safe: EF merely
        re-loses mass that was pending, it never corrupts)."""
        if self._resume_residual:
            self._resume_residual = False
            if self._state_ckpt is not None:
                d = sum(int(np.prod(np.shape(l)))
                        for l in jax.tree.leaves(variables))
                restored = self._state_ckpt.load(round_idx, d)
                if restored is not None:
                    self._residual = restored
                else:
                    logging.info(
                        "silo%d: no residual checkpoint for round %d — "
                        "starting error feedback from zero", self.rank,
                        round_idx)
        return self._residual

    def _save_residual(self, completed_round: int) -> None:
        # same round keying as the server's model checkpoint (saved under
        # rounds-completed), so restore-at-resumed-round lines both up
        if self._state_ckpt is not None and self._residual is not None:
            self._state_ckpt.save(completed_round,
                                  np.asarray(self._residual))

    def handle_message_init(self, msg: Message) -> None:
        # busy-flag the whole handler: local_train can legitimately run
        # far longer than rejoin_idle_s, and the heartbeat thread must
        # not read that as "the server forgot us" and JOIN mid-round
        with self._hb_lock:
            self._last_s2c = time.monotonic()  # server traffic: alive
            self._busy = True
        try:
            self._train_and_reply(msg)
        finally:
            with self._hb_lock:
                self._busy = False
                self._last_s2c = time.monotonic()

    def _wan_payload_bytes(self) -> float:
        """Rough model frame size for the WAN bandwidth model: the held
        model's f32 bytes (0 before the first broadcast lands)."""
        if self._held is None:
            return 0.0
        return 4.0 * sum(int(np.prod(np.shape(leaf)))
                         for leaf in jax.tree.leaves(self._held))

    def _train_and_reply(self, msg: Message) -> None:
        t0 = time.perf_counter()
        client_idx = msg.get(MSG_ARG_KEY_CLIENT_INDEX)
        round_idx = msg.get(MSG_ARG_KEY_ROUND)
        wan_delay = 0.0
        if self._wan_agent is not None:
            # decided BEFORE the broadcast applies: an offline device
            # never received the frame, so its held model goes stale and
            # the server's next broadcast to it full-rebases (the same
            # coherence rule every other loss path uses)
            nbytes = self._wan_payload_bytes()
            drop, wan_delay = self._wan_agent.on_round(
                round_idx, int(client_idx), up_bytes=nbytes,
                down_bytes=nbytes)
            if drop:
                logging.info(
                    "silo %d: device offline in the WAN trace at round "
                    "%s — dropping the broadcast (no training, no "
                    "reply)", self.rank, round_idx)
                return
        variables = self._apply_broadcast(msg)
        packed = None
        if self._prefetch is not None:
            # keyed on the ACTUAL (round, client): a mispredicted slot
            # simply misses and this same get() packs the right shard
            # inline — never two packs for one round
            (ds, payload), _ = self._prefetch.get(
                (round_idx, int(client_idx)))
            if ds is self.dataset:
                packed = payload
        if packed is None:  # swapped dataset (or degenerate None slot)
            x, y, mask = self.dataset.pack_clients([client_idx], self._bsz,
                                                   n_pad=self._n_pad)
            packed = (x[0], y[0], mask[0])
        xb, yb, maskb = packed
        reply = Message(MSG_TYPE_C2S_SEND_MODEL, self.rank, 0)
        # the scale is a pure function of round_idx (identical for every
        # silo this round), computed OUTSIDE the device lock with the
        # SHARED f32 formula (round_lr_scale) so every driver path scales
        # by the bit-identical factor
        scale = round_lr_scale(self._train_cfg, round_idx)
        with self._device_lock:
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, round_idx), client_idx)
            if scale is None:
                new_vars, _ = self._local_train(
                    variables, jnp.asarray(xb), jnp.asarray(yb),
                    jnp.asarray(maskb), key)
            else:
                new_vars, _ = self._local_train(
                    variables, jnp.asarray(xb), jnp.asarray(yb),
                    jnp.asarray(maskb), key, lr_scale=scale)
            if self._policy.enabled:
                from fedml_tpu.comm.compression import compress_for_policy
                ckey = jax.random.fold_in(jax.random.fold_in(
                    jax.random.key(977), round_idx), self.rank)
                residual = (self._uplink_residual(round_idx, variables)
                            if self._policy.uplink_topk else None)
                payload, new_residual = compress_for_policy(
                    new_vars, variables, residual, ckey, self._policy)
                if self._policy.uplink_topk:
                    # committed as-if-delivered. If a QUORUM server later
                    # discards this reply as stale, the sent top-k mass is
                    # lost to the EF loop — strictly less than the
                    # uncompressed quorum protocol loses (it discards the
                    # ENTIRE stale update), so the EF-convergence claim is
                    # scoped to rounds whose replies are accepted
                    self._residual = new_residual
                reply.add(MSG_ARG_KEY_MODEL_PARAMS, payload)
            else:
                reply.add(MSG_ARG_KEY_MODEL_PARAMS, _to_numpy(new_vars))
        if self._policy.uplink_topk:
            self._save_residual(round_idx + 1)  # file I/O outside the lock
        n_i = float(self.dataset.train_data_local_num_dict[int(client_idx)])
        reply.add(MSG_ARG_KEY_NUM_SAMPLES, n_i)
        # round/version tag: lets straggler-tolerant servers detect stale
        # replies (fedavg_async.py) — the plain server ignores it
        reply.add(MSG_ARG_KEY_ROUND, round_idx)
        # held-base report: drives the server's downlink decision and its
        # automatic full-precision fallback on structure mismatch
        from fedml_tpu.comm.compression import tree_fingerprint
        reply.add(MSG_ARG_KEY_BASE_SEQ, self._held_seq)
        reply.add(MSG_ARG_KEY_BASE_FP, tree_fingerprint(variables))
        if self._obs is not None:
            # piggyback the counter digest for the server's per-silo row
            # and record this silo's own view of the round (its flight
            # log is what the merge tool aligns with the server's) —
            # BEFORE the send, so a mid-failover round still documents
            # the local train that happened
            reply.add(MSG_ARG_KEY_OBS_DIGEST, self._obs_digest())
            self._obs.recorder.append(
                {"kind": "round", "round": int(round_idx),
                 "client_idx": int(client_idx),
                 "train_s": round(time.perf_counter() - t0, 6)})
        if wan_delay > 0:
            # injected WAN report latency (the embodied client's compute
            # + bandwidth profile) — outside the device lock, on this
            # silo's own receive thread: a straggler straggles alone.
            # The _busy flag is still up (handle_message_init), so the
            # heartbeat thread cannot mistake the sleep for an eviction.
            time.sleep(wan_delay)
        try:
            self.send_message(reply)
        except OSError as exc:
            # the server may be mid-failover: dropping the reply is safe
            # (the restarted server re-broadcasts the round and this silo
            # retrains it), dying here is not — the receive loop must
            # survive to hear the restarted server
            logging.warning(
                "silo %d: round-%d reply not delivered (%r) — server "
                "down? a restarted server re-drives the round", self.rank,
                round_idx, exc)
            return
        with self._hb_lock:
            self.rounds_completed += 1


def run_fedavg_cross_silo(dataset: FederatedDataset, module,
                          task: str = "classification",
                          worker_num: int = 2, comm_round: int = 2,
                          train_cfg: Optional[TrainConfig] = None,
                          backend: str = "INPROC",
                          addresses=None, wire_codec: bool = True,
                          compress: bool = False, compression=None,
                          token=None,
                          checkpoint_dir: Optional[str] = None,
                          resume: bool = False,
                          server_optimizer: Optional[str] = None,
                          server_lr: float = 1e-3,
                          server_momentum: float = 0.0,
                          seed: int = 0,
                          join_timeout_s: float = 600.0,
                          round_record_hook=None,
                          timer=None,
                          prefetch_depth: int = 2,
                          round_deadline_s: Optional[float] = None,
                          min_quorum_frac: float = 0.5,
                          heartbeat_s: float = 0.0,
                          fault_plan=None,
                          server_checkpoint_dir: Optional[str] = None,
                          checkpoint_sync: bool = False,
                          pace_steering: bool = False,
                          join_rate_limit: float = 0.0,
                          max_deadline_extensions: Optional[int] = 25,
                          obs_dir: Optional[str] = None,
                          job_id: Optional[str] = None,
                          comm_factory=None,
                          device_gate=None,
                          serve_port: Optional[int] = None,
                          serve_staleness_rounds: int = 2,
                          serving=None,
                          wan_trace=None,
                          wan_profiles=None,
                          wan_round_s: float = 60.0,
                          wan=None):
    """Launch server + ``worker_num`` client actors (threads; one per silo)
    and run the full protocol. Returns (final global model, round history).

    ``compression`` selects the wire policy (comm/policy.py:
    none | delta_int8 | topk_ef | topk_ef_int8, a name or a
    CompressionPolicy); the legacy boolean ``compress`` maps to
    delta_int8. ``timer`` (a RoundTimer) receives the wire accounting
    (``comm_bytes_up``/``comm_bytes_down`` from actual encoded frames)
    plus the fault-tolerance counters (retries, evictions, rejoins, ...).

    Fault tolerance: ``round_deadline_s`` turns on deadline rounds —
    the server closes with a weighted partial aggregate once the deadline
    passes with ≥ ``min_quorum_frac`` of LIVE silos reported, evicting
    the non-reporters; evicted/restarted silos rejoin via JOIN + a
    full-precision mirror resync. ``heartbeat_s`` makes idle silos beat
    (and auto-JOIN after ~3 silent beats). ``fault_plan`` (DSL/JSON, see
    comm/faults.py) wraps every endpoint in the seeded chaos harness.

    Elastic control plane (fedml_tpu/control/):
    ``server_checkpoint_dir`` snapshots the server's full round-schedule
    state at round boundaries and deadline closes (a killed-and-restarted
    server resumes mid-schedule and appends to the round/cohort ledger);
    snapshots are written ASYNCHRONOUSLY by default (a dedicated writer
    thread with newest-wins coalescing and group-committed ledger fsyncs
    — restore may land a few rounds back and replay forward to the same
    ledger); ``checkpoint_sync`` forces the legacy inline
    snapshot-at-every-boundary durability;
    ``pace_steering`` derives each round's deadline (p90·margin, clamped)
    and quorum target from the observed report-latency distribution,
    using the static flags as base/floor; ``join_rate_limit`` (joins/sec)
    token-buckets JOIN floods with BACKPRESSURE replies;
    ``max_deadline_extensions`` caps the below-quorum extension loop —
    exhausting it raises a loud SchedulingStallError after checkpointing
    the final state. All defaults off/inert -> byte-identical legacy
    behavior.

    Observability (fedml_tpu/obs): ``obs_dir`` turns on the federation
    flight recorder — per-round snapshot-delta timelines + per-silo
    digest rows in ``flight_rank<r>.jsonl`` next to the control-plane
    ledger, anomaly-armed one-shot profiling under ``obs_dir/profiles``.
    Pure observer: trajectories are bit-exact vs ``obs_dir=None``.

    WAN realism (fedml_tpu/wan): ``wan_trace``/``wan_profiles``/
    ``wan_round_s`` (or a prebuilt ``wan`` WanWorld) drive the schedule
    through seeded diurnal churn and heterogeneous stragglers — cohorts
    sample only trace-available clients, trace-offline silos get
    deadline-evicted and rejoin through a trace-gated JOIN path, and
    profiled report delays feed the pace steerer. Pure function of the
    trace seed: one seed replays a bit-identical ledger. Unset = off,
    byte-identical legacy behavior (README "WAN-realistic federation").

    Serving (fedml_tpu/serve): ``serve_port`` attaches a serving tier —
    each broadcast's model hot-swaps into a jitted, batch-coalescing
    TCP/JSON inference endpoint on that port (0 = ephemeral) that
    serves round r while r+1 trains, staleness-bounded by
    ``serve_staleness_rounds``; ``serving`` hands in a prebuilt
    ``ServingTier`` instead (the caller owns its lifecycle). Also a
    pure observer — trajectories are bit-exact with serving on or off.

    The reference's equivalent is `mpirun -np worker_num+1 main_fedavg.py`
    (FedAvgAPI.py:20-67 rank dispatch); here ranks are threads over the
    selected backend, so the same protocol code also drives TCP/GRPC
    processes for true multi-host runs.
    """
    checkpoint_mgr = None
    if checkpoint_dir:
        from fedml_tpu.utils.checkpoint import CheckpointManager
        checkpoint_mgr = CheckpointManager(checkpoint_dir)
    # WAN world model (fedml_tpu/wan): population dynamics driving this
    # schedule — availability-restricted sampling, trace-gated rejoin,
    # per-silo churn/straggler agents. A prebuilt world (``wan=``) wins;
    # otherwise specs build one. The shadow mass-JOIN bucket runs at the
    # same rate as the real admission controller, so the population wave
    # is measured against the configured policy.
    if wan is None:
        from fedml_tpu.wan import build_wan_world
        wan = build_wan_world(wan_trace, wan_profiles, wan_round_s,
                              population=dataset.client_num,
                              mass_join_rate=join_rate_limit)
    elif wan.population is None:
        wan.population = dataset.client_num
    # resolve ONCE and hand the instance to both sides, so the server's
    # downlink and the silos' uplink can never disagree about the policy
    policy = resolve_compression(compression, compress=compress)
    from fedml_tpu.control import build_control_plane
    control = build_control_plane(
        server_checkpoint_dir=server_checkpoint_dir,
        pace_steering=pace_steering, join_rate_limit=join_rate_limit,
        round_deadline_s=round_deadline_s,
        min_quorum_frac=min_quorum_frac,
        max_deadline_extensions=max_deadline_extensions,
        checkpoint_sync=checkpoint_sync)

    def server_factory(size, server_com, aggregator, global_model,
                       on_round_done):
        common = dict(on_round_done=on_round_done,
                      checkpoint_mgr=checkpoint_mgr, resume=resume,
                      compression=policy,
                      round_deadline_s=round_deadline_s,
                      min_quorum_frac=min_quorum_frac,
                      device_gate=device_gate, wan=wan, **control)
        if server_optimizer:
            return FedOptServerManager(
                0, size, server_com, aggregator, comm_round,
                dataset.client_num, global_model,
                server_optimizer=server_optimizer, server_lr=server_lr,
                server_momentum=server_momentum, **common)
        return FedAvgServerManager(0, size, server_com, aggregator,
                                   comm_round, dataset.client_num,
                                   global_model, **common)

    if job_id is None and (checkpoint_dir or server_checkpoint_dir):
        # launch_federation keys the derived default job id on
        # client_state_dir only; a run that persists via
        # server_checkpoint_dir alone must ALSO rejoin its own flight
        # timeline on crash-resume instead of forking a phantom job
        from fedml_tpu.obs import default_job_id
        job_id = default_job_id(
            "fed", stable_key=(checkpoint_dir or server_checkpoint_dir))
    model, history, _ = launch_federation(
        dataset, module, task, worker_num, train_cfg, server_factory,
        backend=backend, addresses=addresses, wire_codec=wire_codec,
        compression=policy, token=token, seed=seed,
        client_state_dir=checkpoint_dir, resume=resume,
        state_sync=checkpoint_sync,
        join_timeout_s=join_timeout_s, round_record_hook=round_record_hook,
        timer=timer, prefetch_depth=prefetch_depth,
        heartbeat_s=heartbeat_s, fault_plan=fault_plan,
        obs_dir=obs_dir, job_id=job_id,
        comm_factory=comm_factory, device_gate=device_gate,
        serve_port=serve_port,
        serve_staleness_rounds=serve_staleness_rounds, serving=serving,
        wan=wan)
    return model, history


def launch_federation(dataset: FederatedDataset, module, task: str,
                      worker_num: int, train_cfg: Optional[TrainConfig],
                      server_factory, backend: str = "INPROC",
                      addresses=None, wire_codec: bool = True,
                      compress: bool = False, compression=None,
                      token=None, seed: int = 0,
                      client_state_dir: Optional[str] = None,
                      resume: bool = False,
                      state_sync: bool = False,
                      join_timeout_s: float = 600.0,
                      raise_on_timeout: bool = False,
                      round_record_hook=None,
                      timer=None,
                      prefetch_depth: int = 2,
                      heartbeat_s: float = 0.0,
                      fault_plan=None,
                      obs_dir: Optional[str] = None,
                      job_id: Optional[str] = None,
                      comm_factory=None,
                      device_gate=None,
                      serve_port: Optional[int] = None,
                      serve_staleness_rounds: int = 2,
                      serving=None,
                      wan=None):
    """Shared federation scaffolding for every server flavor (sync,
    FedOpt, quorum, FedAsync): init the global model, build the
    per-round eval hook, wire comm managers + client silos, run the
    protocol threads, bounded-join. ``server_factory(size, server_com,
    aggregator, global_model, on_round_done)`` returns the server
    manager (callers that want a non-``none`` downlink construct their
    server with the same resolved policy). Returns ``(final global
    model, history, server)`` — the server carries ``round_timer`` with
    the wire byte accounting.

    Multi-job tenancy hooks (fedml_tpu/sched): ``comm_factory(rank)``
    supplies each rank's endpoint instead of ``create_comm_manager``
    (the scheduler hands per-job virtual channels over one shared
    fabric); ``device_gate`` replaces the process-wide device lock with
    a per-job fair-share gate. Both ``None`` (the default) is the
    byte-identical single-tenant path."""
    train_cfg = train_cfg or TrainConfig()
    policy = resolve_compression(compression, compress=compress)
    size = worker_num + 1
    gate = device_gate if device_gate is not None else _DEVICE_LOCK
    if comm_factory is not None:
        # the factory's endpoints are prebuilt elsewhere (the scheduler's
        # shared fabric): transport knobs only create_comm_manager
        # consumes would be silently dropped here — refuse, so a caller
        # expecting chaos injection or wire auth cannot run without them
        dropped = [name for name, unset in (
            ("fault_plan", fault_plan is None),
            ("token", token is None),
            ("addresses", addresses is None),
            ("wire_codec", wire_codec)) if not unset]
        if dropped:
            raise ValueError(
                f"comm_factory supplies prebuilt endpoints: {dropped} "
                "would be silently ignored — apply transport knobs where "
                "the endpoints are built (e.g. SharedFabric(wire_codec=, "
                "token=, fault_plan=))")
        router, plan = None, None
    else:
        router = (InProcRouter()
                  if backend.upper() in ("INPROC", "MPI") else None)
        # parse ONCE: one seeded plan instance shared by every endpoint,
        # so per-rank RNG streams come from the same seed (comm/faults.py)
        from fedml_tpu.comm.faults import parse_fault_plan
        plan = parse_fault_plan(fault_plan)

    sample_x = dataset.train_data_global[0][:1]
    with gate:  # model init is a device dispatch (tenants contend)
        global_model = module.init(jax.random.key(seed),
                                   jnp.asarray(sample_x), train=False)
    history: List[Dict] = []
    eval_fn = jax.jit(make_eval(module, task))

    def on_round_done(round_idx, model):
        xt, yt = dataset.test_data_global
        if not len(xt):
            return
        with gate:  # only the eval is device compute
            stats = eval_fn(model, jnp.asarray(xt), jnp.asarray(yt),
                            jnp.ones(len(xt), jnp.float32))
            acc = float(stats["correct_sum"]) / max(1.0,
                                                    float(stats["count"]))
            loss = float(stats["loss_sum"]) / max(1.0,
                                                  float(stats["count"]))
        # history/log/sink I/O happen OUTSIDE the lock: a slow sink (file
        # I/O, wandb HTTP) must not stall every silo's local_train
        rec = {"round": round_idx, "test_acc": acc, "test_loss": loss}
        history.append(rec)
        logging.info("cross-silo round %d: %s", round_idx, rec)
        if round_record_hook is not None:
            # stream to the caller's sink AS ROUNDS LAND — a 100-round
            # chip protocol is otherwise indistinguishable from a hang
            # until the final join (observed, round 5). Never let a sink
            # error kill the server receive loop.
            try:
                round_record_hook(rec)
            except Exception:
                logging.warning("round_record_hook failed for round %d",
                                round_idx, exc_info=True)

    aggregator = FedAvgAggregator(worker_num)
    if comm_factory is not None:
        server_com = comm_factory(0)
    else:
        server_com = create_comm_manager(backend, 0, size, router=router,
                                         addresses=addresses,
                                         wire_codec=wire_codec, token=token,
                                         fault_plan=plan)
    server = server_factory(size, server_com, aggregator, global_model,
                            on_round_done)
    from fedml_tpu.utils.tracing import RoundTimer
    server.round_timer = timer if timer is not None else RoundTimer()
    # observability (fedml_tpu/obs): one flight recorder per process
    # role — the server gets the anomaly detector + one-shot profiler,
    # each silo records its own log and piggybacks digests. obs_dir
    # None (default) keeps the wire format byte-identical.
    from fedml_tpu.obs import (build_observability, default_job_id,
                               endpoint_epoch)
    # collision-safe default: two unconfigured jobs sharing an obs dir
    # must never interleave under one literal id (computed ONCE per
    # launch so every rank of this run carries the same id). Keyed on
    # the run's durable namespace when it has one, so a crash-resumed
    # leg rejoins its own flight timeline instead of forking a phantom
    # second job.
    job = job_id or default_job_id("fed", stable_key=client_state_dir)
    obs_server = build_observability(obs_dir, job_id=job, rank=0,
                                     role="server")
    if obs_server is not None:
        obs_server.recorder.set_epoch(endpoint_epoch(server_com))
        obs_server.bind_timer(server.round_timer)
        server.obs = obs_server
    # serving tier (fedml_tpu/serve): a prebuilt tier (caller-owned) or
    # one constructed here from serve_port (0 = ephemeral). Either way
    # the server's broadcast/finish publishes feed the rollout, the tier
    # shares THIS launch's device gate (fair-share co-tenant under the
    # scheduler) and lands its metrics on the same round timer + flight
    # log as everything else.
    tier, own_tier = serving, False
    if tier is None and serve_port is not None:
        from fedml_tpu.serve import build_serving
        tier = build_serving(
            module, task, sample_x,
            staleness_rounds=serve_staleness_rounds,
            checkpointer=getattr(server, "_server_ckpt", None),
            device_gate=gate, timer=server.round_timer, obs=obs_server,
            port=serve_port)
        own_tier = True
    if tier is not None:
        server.publish_model = tier.publish_hook
    clients = []
    client_coms = []
    try:
        for rank in range(1, size):
            if comm_factory is not None:
                com = comm_factory(rank)
            else:
                com = create_comm_manager(backend, rank, size,
                                          router=router,
                                          addresses=addresses,
                                          wire_codec=wire_codec,
                                          token=token, fault_plan=plan)
            # ft: allow[FT008] one endpoint per SILO at launch — bounded by worker_num (tens), not the client population
            client_coms.append(com)
            silo_obs = build_observability(obs_dir, job_id=job, rank=rank,
                                           role="silo")
            if silo_obs is not None:
                silo_obs.recorder.set_epoch(endpoint_epoch(com))
            # ft: allow[FT008] one manager per SILO at launch — silo count is the federation's process count, not its population
            clients.append(FedAvgClientManager(
                rank, size, com, dataset, module, task, train_cfg,
                seed=seed,
                compression=policy,
                state_dir=(os.path.join(client_state_dir, f"silo_{rank}")
                           if client_state_dir else None),
                resume=resume, state_sync=state_sync,
                prefetch_depth=prefetch_depth,
                heartbeat_s=heartbeat_s, obs=silo_obs,
                device_gate=device_gate,
                wan_agent=(wan.agent(rank) if wan is not None else None)))
    except BaseException:
        # a silo endpoint/manager that fails to construct (port already
        # bound, bad address, state-dir OSError) raises BEFORE the main
        # run block's finally exists — the serving front's listening
        # socket and the obs recorder must not outlive the failed
        # launch (an in-process relaunch would hit EADDRINUSE)
        if own_tier:
            tier.close()
        if obs_server is not None:
            obs_server.close()
        raise

    # Warm the two heavyweight programs ON THE MAIN THREAD before any
    # actor thread starts: one local_train at the padded shape and one
    # eval at the global test shape. Every silo then only EXECUTES inside
    # the protocol (the programs are shared via _shared_local_train /
    # eval_fn closure), so round 0 costs worker_num executions instead of
    # worker_num serialized ~40 s compiles on receive threads.
    try:
        import time as _time
        n_pad = dataset.padded_len(train_cfg.batch_size)
        wx, wy, wmask = dataset.pack_clients([0], train_cfg.batch_size,
                                             n_pad=n_pad)
        t0 = _time.time()
        logging.info("cross-silo warmup: local_train compile (n_pad=%d)...",
                     n_pad)
        warm_kw = {}
        if train_cfg.lr_decay_round != 1.0:
            # silos will call with lr_scale (a different traced signature)
            # — warm THAT program, not the constant-lr one
            warm_kw["lr_scale"] = round_lr_scale(train_cfg, 0)
        # mirror the ACTOR call exactly: silos receive the model as
        # wire-decoded NUMPY arrays (uncommitted), not the init's
        # device-committed tree — jit caches on input shardings, so a
        # committed-tree warmup can leave the actors' uncommitted-input
        # program cold (a second round-0 compile) and the key as fold_in
        # output, as in
        # handle_message_init
        warm_key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), 0), 0)
        # under the scheduler this launch's warmup races OTHER tenants'
        # live rounds on the shared chip — hold the (per-job) gate for
        # the executions; solo launches see an uncontended lock
        with gate:
            warm_vars, _ = _shared_local_train(module, task, train_cfg)(
                _to_numpy(global_model), jnp.asarray(wx[0]),
                jnp.asarray(wy[0]), jnp.asarray(wmask[0]), warm_key,
                **warm_kw)
            jax.block_until_ready(warm_vars)
        del warm_vars
        logging.info("cross-silo warmup: local_train ready in %.1fs; "
                     "eval compile...", _time.time() - t0)
        t0 = _time.time()
        xt, yt = dataset.test_data_global
        if len(xt):
            with gate:
                warm_stats = eval_fn(global_model, jnp.asarray(xt),
                                     jnp.asarray(yt),
                                     jnp.ones(len(xt), jnp.float32))
                jax.block_until_ready(warm_stats)
        logging.info("cross-silo warmup: eval ready in %.1fs (test n=%d)",
                     _time.time() - t0, len(xt))
    except Exception:  # warmup is an optimization, never a launch blocker
        logging.warning("cross-silo warmup compile failed; silos will "
                        "compile lazily on their receive threads",
                        exc_info=True)

    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    server_thread = threading.Thread(target=server.run, daemon=True)
    try:
        for t in threads:
            t.start()
        server_thread.start()
        server.send_init_msg()
        server_thread.join(timeout=join_timeout_s)
        if server_thread.is_alive():
            if raise_on_timeout:
                raise RuntimeError(
                    f"federation did not finish within "
                    f"{join_timeout_s:.0f}s "
                    "(dead worker or quorum never reached?)")
            # non-raising path: an empty/partial history otherwise looks
            # like a silent success — say loudly what happened (observed:
            # a slow XLA:CPU compile pushing the protocol past the join
            # budget)
            logging.error(
                "federation still running after join_timeout_s=%.0f — "
                "returning partial history (%d records); raise the "
                "timeout for slow-compile hosts", join_timeout_s,
                len(history))
        for t in threads:
            t.join(timeout=60)
    finally:
        # EVERY exit (incl. the join-timeout raise above and the stall
        # re-raise below) releases the serving front's listening socket
        # + worker threads and stops any open obs profile window — a
        # raised launch must not leave a port bound for the process
        # lifetime (an in-process relaunch would hit EADDRINUSE)
        if own_tier:
            # flushes the final SLO record into the flight log, then
            # stops the front + swap worker + coalescer;
            # caller-provided tiers stay open (the caller is still
            # serving / inspecting them)
            tier.close()
        if obs_server is not None:
            obs_server.close()
    # wire accounting from the server's transport endpoint: every uplink
    # reply lands in bytes_received, every broadcast in bytes_sent —
    # ACTUAL encoded frame lengths, not array-size estimates. (Quorum's
    # self-addressed TIMEOUT ticks ride the same endpoint; they are tens
    # of bytes against multi-KB..MB model frames.) Backends without a
    # wire (inproc with wire_codec=False) report 0. Round-based servers
    # credit per-round deltas at every close (_credit_wire_bytes — the
    # flight deck's per-round wire rates); this final credit picks up
    # only the remainder (FINISH sweep, last replies), so the run total
    # equals the endpoint total either way.
    if hasattr(server, "_credit_wire_bytes"):
        server._credit_wire_bytes()
    else:
        server.round_timer.count("comm_bytes_down",
                                 int(getattr(server_com, "bytes_sent", 0)))
        server.round_timer.count("comm_bytes_up",
                                 int(getattr(server_com,
                                             "bytes_received", 0)))
    # fault-tolerance roll-up: transport counters (retries, dedup drops,
    # injected faults) summed over EVERY endpoint, protocol counters
    # (evictions, rejoins, corrupt frames, partial closes) from the
    # server. Counted even when zero so the keys are always present.
    transport = defaultdict(int)
    for com in [server_com, *client_coms]:
        counters = (com.all_counters() if hasattr(com, "all_counters")
                    else getattr(com, "counters", {}))
        for k, v in dict(counters).items():
            transport[k] += int(v)
    tmr = server.round_timer
    tmr.count("ft_retries", transport["retries"])
    tmr.count("ft_dedup_drops", transport["dedup_drops"])
    tmr.count("ft_conn_errors", transport["conn_errors"])
    tmr.count("ft_faults_injected", transport["faults_injected"])
    liveness = getattr(server, "liveness", None)
    tmr.count("ft_evictions",
              int(getattr(liveness, "evictions", 0)))
    tmr.count("ft_rejoins", int(getattr(liveness, "rejoins", 0)))
    ftc = getattr(server, "ft_counters", {})
    for key in ("partial_rounds", "stale_replies", "corrupt_frames",
                "join_resyncs", "heartbeats", "deadline_extensions"):
        tmr.count(f"ft_{key}", int(ftc.get(key, 0)))
    # control-plane roll-up (checkpoint/restore/steering/admission) —
    # counted even when zero so the cp_* keys are always present, like
    # the ft_* family
    cpc = getattr(server, "cp_counters", {})
    for key in ("checkpoints", "restores", "deadline_adjustments",
                "joins_throttled", "resync_latency_skips"):
        tmr.count(f"cp_{key}", int(cpc.get(key, 0)))
    # WAN-world roll-up (fedml_tpu/wan): the server drains the world's
    # sampling counters at every round close; this picks up the
    # remainder plus every silo agent's offline-drop / injected-delay
    # totals. Keys only exist when a world ran — wan off leaves the
    # timer byte-identical.
    if wan is not None:
        for k, v in wan.drain_counters().items():
            tmr.count(k, int(v))
        for c in clients:
            agent = getattr(c, "_wan_agent", None)
            if agent is not None:
                for k, v in agent.counters.items():
                    tmr.count(k, int(v))
    if getattr(server, "_pace", None) is not None \
            and getattr(server, "round_deadline_s", None):
        tmr.gauge("cp_steered_deadline_s", float(server.round_deadline_s))
    err = getattr(server, "scheduling_error", None)
    if err is not None:
        # the server already checkpointed final state and FINISHed the
        # silos; surface the stall as the loud failure it is
        raise err
    return server.global_model, history, server
