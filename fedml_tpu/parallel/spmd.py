"""SPMD federated rounds over a device mesh — the distributed backend.

This file is the TPU-native answer to the reference's entire distributed
stack: the MPI rank dispatch (FedAvgAPI.py:20-67), the Server/Client manager
message loops (FedAvgServerManager.py:43-93, FedAvgClientManager.py), and the
all-received barrier (FedAVGAggregator.py:50-56). On a mesh there are no
messages and no barrier code: each device trains its shard of the sampled
clients, "send model to server" is a weighted ``psum`` over the ``clients``
ICI axis, and "sync model to client" is the replication of the psum result.
One jitted program per round; the barrier is implicit in SPMD.

Scaling model (how this maps to hardware):
- clients axis -> all chips of a slice (ICI). client_num_per_round is padded
  to a multiple of the mesh size with zero-weight slots.
- hierarchical FL -> 2-D mesh ('group', 'clients'): psum over 'clients' is
  the edge aggregation, psum over 'group' the cloud aggregation
  (reference hierarchical_fl/trainer.py re-expressed as two collectives).
- multi-host: the same program under ``jax.distributed.initialize`` — XLA
  routes the psum over ICI within a slice and DCN across slices; nothing in
  this file changes.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import (FedAvgAPI, _normalized,
                                         make_vmapped_clients)
from fedml_tpu.core.sampling import (eval_subsample, round_keys,
                                     sample_clients)
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.trainer.functional import (TrainConfig, make_eval,
                                          make_local_train, round_lr_scale)


def build_mesh(axis_sizes: Dict[str, int],
               devices: Optional[list] = None) -> Mesh:
    """Build a named mesh, e.g. {'clients': 8} or {'group': 2, 'clients': 4}."""
    shape = tuple(axis_sizes.values())
    names = tuple(axis_sizes.keys())
    # Auto axis types: arrays don't get mesh-committed shardings-in-types
    # (Explicit mode pins inputs to one mesh and breaks multi-mesh programs)
    types = tuple(jax.sharding.AxisType.Auto for _ in names)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), names, axis_types=types)


def _pvary(tree, axes: Tuple[str, ...]):
    """Mark a replicated pytree as device-varying inside shard_map.

    Without this, ``jax.grad`` w.r.t. the replicated global params inside the
    shard_map body transposes the broadcast into an implicit ``psum`` — every
    client would receive the SUM of all clients' gradients instead of its own
    (caught by the sim==distributed parity test)."""
    return jax.tree.map(lambda v: jax.lax.pcast(v, axes, to="varying"), tree)


@jax.named_scope("fedml.aggregate")
def _weighted_psum_mean(stacked, weights, axes: Tuple[str, ...]):
    """sum_i w_i * leaf_i over the local client axis, psum over mesh axes,
    divide by the global weight total — the FedAvg aggregation rule
    (FedAVGAggregator.py:58-87) as two collectives. The local contraction
    runs at HIGHEST precision: the TPU's default multiplies f32 operands
    in one bf16 pass, which would round the new global model to ~3 digits
    every round."""
    wsum = jax.tree.map(
        lambda s: jnp.tensordot(weights.astype(s.dtype), s, axes=1,
                                precision=jax.lax.Precision.HIGHEST),
        stacked)
    wsum = jax.lax.psum(wsum, axes)
    wtot = jax.lax.psum(jnp.sum(weights), axes)
    return jax.tree.map(lambda s: s / wtot.astype(s.dtype), wsum)


def _make_shard_round(module, task: str, cfg: TrainConfig,
                      axes: Tuple[str, ...]):
    """One chip's half of a flat mesh round, for a ``shard_map`` body: its
    shard of the cohort through the shared vmapped clients
    (``algorithms.fedavg.make_vmapped_clients`` - no ``tier_clients`` yet:
    a size-ordered cohort sharded contiguously would put every long client
    on chip 0), the FedAvg mean and the stat totals each a ``psum`` over
    ``axes``."""
    clients = make_vmapped_clients(make_local_train(module, task, cfg))

    def shard_round(variables, x, y, mask, keys, weights, lr_scale):
        stacked, stats = clients(variables, x, y, mask, keys, lr_scale)
        new_vars = _weighted_psum_mean(stacked, weights, axes)
        totals = jax.tree.map(
            lambda s: jax.lax.psum(jnp.sum(s, axis=0), axes), stats)
        return new_vars, totals

    return shard_round


def make_spmd_round(module, task: str, cfg: TrainConfig, mesh: Mesh,
                    axis: str = "clients", donate: bool = False,
                    check_vma: bool = True):
    """Compile one FedAvg round over ``mesh[axis]``.

    Inputs are client-major: x [P, n_pad, ...], y, mask, keys, weights with
    P = clients_per_round (a multiple of the axis size; each device trains
    P/axis_size clients via vmap). Returns (replicated new variables,
    psum-reduced train stats).

    ``donate=True`` lets XLA reuse the incoming variables' HBM for the new
    model (the driver overwrites its reference each round); leave False when
    the caller reuses the same variables across calls (parity tests).
    """
    shard_round = _make_shard_round(module, task, cfg, (axis,))
    decayed = cfg.lr_decay_round != 1.0

    def body(variables, x, y, mask, keys, weights, *maybe_r):
        variables = _pvary(variables, (axis,))
        # replicated round index -> decay**r scale, broadcast to the
        # vmapped clients (same f32 power as the sim driver's round_fn,
        # so sim==mesh parity holds under the schedule too); None traces
        # the identical constant-LR program
        scale = round_lr_scale(cfg, maybe_r[0]) if decayed else None
        return shard_round(variables, x, y, mask, keys, weights, scale)

    sharded = P(axis)
    in_specs = (P(), sharded, sharded, sharded, sharded, sharded)
    if decayed:  # extra replicated round-index operand
        in_specs = in_specs + (P(),)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=check_vma,
    ), donate_argnums=(0,) if donate else ())


def make_spmd_multiround(module, task: str, cfg: TrainConfig, mesh: Mesh,
                         rounds: int, axis: str = "clients",
                         donate: bool = True, check_vma: bool = True):
    """R full-participation FedAvg rounds as ONE jitted shard_map program:
    ``lax.scan`` over round indices with the weighted ``psum`` aggregation
    inside the scan body — on a slice the host is touched once per R
    rounds instead of once per round (the mesh analogue of
    algorithms.fedavg.FusedRounds; SURVEY §7 "keep the entire round
    on-device"). Per-round/per-client keys are derived in-scan by the same
    fold_in chain the host loop uses, so the trajectory equals R calls of
    ``make_spmd_round`` with FedAvgAPI-style keys.

    Returns ``fn(variables, x, y, mask, client_ids, weights, base_key,
    r0) -> (new_variables, stats[R])`` with x/y/mask/weights client-major
    as in make_spmd_round and ``client_ids`` the uint32 global client ids
    of the local slots (used only for key derivation).
    """
    shard_round = _make_shard_round(module, task, cfg, (axis,))

    def body(variables, x, y, mask, client_ids, weights, base_key, r0):
        # client_ids/x/y/mask/weights are sharded inputs — already
        # device-varying; only the replicated variables need the pcast
        variables = _pvary(variables, (axis,))

        def one_round(vars_r, r):
            _, keys, _ = round_keys(base_key, r, client_ids)
            new_vars, totals = shard_round(vars_r, x, y, mask, keys,
                                           weights, round_lr_scale(cfg, r))
            # re-vary: the psum result is replicated-typed, the next scan
            # step consumes it as the (device-varying) client input again
            return _pvary(new_vars, (axis,)), totals

        new_vars, stats = jax.lax.scan(
            one_round, variables,
            r0 + jnp.arange(rounds, dtype=jnp.uint32))
        # the carry is device-varying-typed but value-identical on every
        # device (each step ends in the same psum); one pmean clears the
        # type for the replicated output at zero numeric cost
        new_vars = jax.tree.map(lambda v: jax.lax.pmean(v, axis), new_vars)
        return new_vars, stats

    sharded = P(axis)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), sharded, sharded, sharded, sharded, sharded, P(),
                  P()),
        out_specs=(P(), P()),
        check_vma=check_vma,
    ), donate_argnums=(0,) if donate else ())


def make_spmd_block_multiround(module, task: str, cfg: TrainConfig,
                               mesh: Mesh, axis: str = "clients",
                               donate: bool = True,
                               check_vma: bool = True):
    """R SAMPLED-cohort FedAvg rounds as ONE jitted shard_map program.

    The mesh analogue of ``algorithms.fedavg.FusedRounds`` block mode: the
    host draws the R cohorts up front with the reference sampling stream
    (FedAVGAggregator.py:89-97 np.random contract), packs them as one
    ``[R, P, n_pad, ...]`` block (P = cohort size padded to a mesh
    multiple), and this program scans the R rounds with the weighted
    ``psum`` aggregation inside the scan body — composing cohort-bucket
    packing with multi-round fusion on the slice, which
    ``make_spmd_multiround`` (full participation, federation-resident)
    cannot do for sampled regimes.

    Returns ``fn(variables, xs, ys, masks, idsR, weightsR, base_key, r0)
    -> (new_variables, stats[R])`` with the block arrays ``[R, P, ...]``
    sharded over ``axis`` on dim 1 and ``idsR`` the uint32 global client
    ids per round (key derivation via the shared fold_in chain,
    core/sampling.round_keys — trajectory parity with R ``run_round``
    calls is exact).
    """
    shard_round = _make_shard_round(module, task, cfg, (axis,))

    def body(variables, xs, ys, masks, idsR, weightsR, base_key, r0):
        variables = _pvary(variables, (axis,))

        def one_round(vars_r, inp):
            r, x, y, mask, ids, weights = inp
            _, keys, _ = round_keys(base_key, r, ids)
            new_vars, totals = shard_round(vars_r, x, y, mask, keys,
                                           weights, round_lr_scale(cfg, r))
            return _pvary(new_vars, (axis,)), totals

        rs = r0 + jnp.arange(xs.shape[0], dtype=jnp.uint32)
        new_vars, stats = jax.lax.scan(one_round, variables,
                                       (rs, xs, ys, masks, idsR, weightsR))
        new_vars = jax.tree.map(lambda v: jax.lax.pmean(v, axis), new_vars)
        return new_vars, stats

    blocked = P(None, axis)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), blocked, blocked, blocked, blocked, blocked, P(),
                  P()),
        out_specs=(P(), P()),
        check_vma=check_vma,
    ), donate_argnums=(0,) if donate else ())


def make_sharded_eval(module, task: str, mesh: Mesh, axis="clients",
                      check_vma: bool = True):
    """Evaluation sharded over the mesh: each device scores its slice of
    the eval union, stat sums meet in one psum. The multi-chip analogue of
    the reference's rank-0 test_on_server_for_all_clients
    (FedAVGAggregator.py:109) — no device ever holds the whole eval set."""
    ev = make_eval(module, task)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def body(variables, x, y, mask):
        stats = ev(variables, x, y, mask)  # this shard's sums
        return jax.tree.map(lambda s: jax.lax.psum(s, axes), stats)

    sharded = P(axes)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), sharded, sharded, sharded),
        out_specs=P(), check_vma=check_vma))


def make_hierarchical_spmd_round(module, task: str, cfg: TrainConfig,
                                 mesh: Mesh, group_comm_round: int = 1,
                                 donate: bool = False,
                                 check_vma: bool = True):
    """Two-tier FedAvg round on a ('group', 'clients') mesh: run
    ``group_comm_round`` edge rounds (train + psum over 'clients' within each
    group), then one cloud aggregation (psum over 'group') — the reference's
    hierarchical_fl group/global loop (hierarchical_fl/{trainer,group}.py) as
    nested collectives."""
    if cfg.lr_decay_round != 1.0:
        raise NotImplementedError(
            "lr_decay_round is not defined for the 2-tier round (ambiguous "
            "round index); use the flat FedAvg drivers for the schedule")
    clients = make_vmapped_clients(make_local_train(module, task, cfg))

    def body(variables, x, y, mask, keys, weights):
        # carry type: group-varying; per-client variation is introduced at the
        # consumption point each edge round so the carry type stays stable
        variables = _pvary(variables, ("group",))

        def scan_body(vars_g, rkeys):
            local_vars = _pvary(vars_g, ("clients",))
            stacked, stats = clients(local_vars, x, y, mask, rkeys)
            agg = _weighted_psum_mean(stacked, weights, ("clients",))
            return agg, stats

        # fresh per-client keys per edge round
        all_keys = jax.vmap(
            lambda r: jax.vmap(
                lambda k: jax.random.fold_in(k, r))(keys))(
                    jnp.arange(group_comm_round, dtype=jnp.uint32))
        vars_g, stats_per_round = jax.lax.scan(scan_body, variables, all_keys)
        stats = jax.tree.map(lambda s: s[-1], stats_per_round)
        # cloud tier: weight each group model by its group sample count
        gw = jax.lax.psum(jnp.sum(weights), "clients")
        gsum = jax.tree.map(lambda s: s * gw.astype(s.dtype), vars_g)
        gsum = jax.lax.psum(gsum, "group")
        gtot = jax.lax.psum(gw, "group")
        new_vars = jax.tree.map(lambda s: s / gtot.astype(s.dtype), gsum)
        totals = jax.tree.map(
            lambda s: jax.lax.psum(jnp.sum(s, axis=0), ("group", "clients")),
            stats)
        return new_vars, totals

    sharded = P(("group", "clients"))
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), sharded, sharded, sharded, sharded, sharded),
        out_specs=(P(), P()),
        check_vma=check_vma,
    ), donate_argnums=(0,) if donate else ())


@dataclasses.dataclass(frozen=True)
class DistributedFedAvgConfig:
    comm_round: int = 10
    client_num_per_round: int = 8
    frequency_of_the_test: int = 5
    seed: int = 0
    # padding policy, mirroring FedAvgConfig.pack: "cohort" (pow-2 bucket of
    # the sampled cohort's max — mesh-padded duplicate slots never raise the
    # max) or "global" (dataset-wide static shape)
    pack: str = "cohort"
    # seeded test-union eval subsample, same stream as
    # FedAvgConfig.eval_test_subsample so histories stay comparable
    eval_test_subsample: Optional[int] = None
    # async round pipeline (parallel/prefetch.py): host pack + sharded
    # device_put of round r+1 (or the next fused block window) runs on a
    # background thread while round r's dispatch executes; at most this
    # many cohorts stay in flight (2 = double buffering, 0 = serial;
    # $FEDML_TPU_PREFETCH overrides). Trajectories are bit-identical to
    # the serial path — the prefetcher runs the exact same pack for the
    # exact round index. Engages only for partial participation (full
    # participation keeps the resident _pack_cache cohort).
    prefetch_depth: int = 2
    # federation flight recorder (fedml_tpu/obs) — mirrors
    # FedAvgConfig.obs_dir/job_id; None = off, pure observer when on
    obs_dir: Optional[str] = None
    job_id: Optional[str] = None
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    # model parallelism INSIDE each client slot: shard the model over a
    # second mesh axis — "tp" (Megatron, transformer models) or "fsdp"
    # (ZeRO-3, any model) with mp_size devices per client
    model_parallel: Optional[str] = None
    mp_size: int = 1
    # named data x fsdp x tp mesh (parallel/mesh.py): e.g.
    # {"data": 4, "fsdp": 2}. Supersedes model_parallel/mp_size — ONE
    # mesh carries the federation axis AND the canonical SpecLayout
    # parameter layout, so fused block scans and fsdp/tp rounds compose
    # instead of living on disjoint 1-D meshes. Mutually exclusive with
    # model_parallel.
    mesh_shape: Optional[Dict[str, int]] = None


class DistributedFedAvgAPI(FedAvgAPI):
    """Distributed FedAvg driver (parity: FedML_FedAvg_distributed,
    FedAvgAPI.py:20) — outer loop on the host, round on the mesh.

    It is ``FedAvgAPI``'s round driver placed on a mesh: the host half of a
    round is inherited, and this class overrides only what a mesh changes.
    Sampled-client shards are placed with
    ``NamedSharding(mesh, P('clients'))`` so each device receives only its
    clients' data (the client-virtualization gather, FedAVGTrainer.py:25-30),
    the cohort is padded to a multiple of the mesh, the round and eval
    programs are the mesh's, and evaluation is the sharded test union.
    """

    _job_prefix = "spmd"
    _prefetch_slots = ("_prefetch", "_block_prefetch")
    # FusedRounds scans the single-device round; the mesh's fused path is
    # run_rounds_fused / train_fused below
    _fused_driver_cls = None

    def __init__(self, dataset: FederatedDataset, module,
                 task: str = "classification", mesh: Optional[Mesh] = None,
                 config: Optional[DistributedFedAvgConfig] = None):
        config = config or DistributedFedAvgConfig()
        mp = config.model_parallel
        mesh_shape = getattr(config, "mesh_shape", None)
        if mp and mp not in ("tp", "fsdp"):
            raise ValueError(f"unknown model_parallel: {mp!r}")
        if mp and mesh_shape:
            raise ValueError(
                "mesh_shape supersedes model_parallel — declare the mp "
                "axis on the named mesh instead, e.g. "
                "mesh_shape={'data': n, 'tp': k}")
        if (mp or mesh_shape) and config.train.lr_decay_round != 1.0:
            raise NotImplementedError(
                "lr_decay_round is not threaded through the model-parallel "
                "(gspmd) round; use the flat clients-axis mesh")
        if mesh is None and mp:
            devs = jax.devices()
            k = config.mp_size
            if len(devs) % k != 0:
                raise ValueError(
                    f"mp_size {k} must divide device count {len(devs)}")
            mesh = Mesh(np.asarray(devs).reshape(len(devs) // k, k),
                        ("clients", mp))
        # named data x fsdp x tp mesh (parallel/mesh.py): the canonical
        # SpecLayout drives both the round programs and parameter
        # placement; the federation axis is 'data' instead of 'clients'
        self._layout = None
        self._data_axis = "clients"
        if mesh_shape:
            from fedml_tpu.parallel.mesh import (DEFAULT_LAYOUT,
                                                 build_named_mesh)
            if mesh is None:
                mesh = build_named_mesh(dict(mesh_shape))
            self._layout = DEFAULT_LAYOUT
            self._data_axis = DEFAULT_LAYOUT.data_axis
            if self._data_axis not in mesh.axis_names:
                raise ValueError(
                    f"named federation mesh needs a {self._data_axis!r} "
                    f"axis; got axes {mesh.axis_names}")
        self.mesh = mesh or build_mesh({"clients": len(jax.devices())})
        if mp and mp not in self.mesh.axis_names:
            raise ValueError(
                f"model_parallel={mp!r} needs a mesh axis named {mp!r}; "
                f"got axes {self.mesh.axis_names}")
        # round/eval slots pad to the FEDERATION axis ('clients', or
        # 'data' on the named mesh — == all devices when 1-D)
        self.n_dev = int(self.mesh.shape[self._data_axis])
        self._data_sharding = NamedSharding(self.mesh, P(self._data_axis))
        # fused-block prefetcher (parallel/prefetch.py), built lazily like
        # the base's cohort one
        self._block_prefetch = None
        super().__init__(dataset, module, task=task, config=config)

    # -- what a mesh changes of the round driver ---------------------------
    def _build_programs(self, aggregate_hook) -> None:
        """The mesh's round and eval programs, and the fresh model
        committed to the layout they were built for."""
        module, task, cfg = self.module, self.task, self.config
        mp = cfg.model_parallel
        self._tier_clients = None  # no tiers on a mesh yet (ROADMAP S3)
        if self._layout is not None:
            from fedml_tpu.parallel.mesh import (make_mesh_eval,
                                                 make_mesh_federated_round)
            self._round_fn, self._shard_params = make_mesh_federated_round(
                module, task, cfg.train, self.mesh, self._layout,
                donate=True)
            self._eval_fn = make_mesh_eval(module, task, self.mesh,
                                           self._layout)
        elif mp:
            from fedml_tpu.parallel.gspmd_round import (
                make_gspmd_eval, make_sharded_federated_round)
            if mp == "tp":
                from fedml_tpu.parallel.tensor import tp_param_specs
                specs_fn = tp_param_specs()
            else:
                from fedml_tpu.parallel.fsdp import fsdp_param_specs
                specs_fn = fsdp_param_specs(int(self.mesh.shape["fsdp"]))
            self._round_fn, self._shard_params = \
                make_sharded_federated_round(module, task, cfg.train,
                                             self.mesh, specs_fn,
                                             donate=True)
            self._eval_fn = make_gspmd_eval(module, task, self.mesh,
                                            specs_fn)
        else:
            self._shard_params = None
            # flax nn.RNN creates its scan carry (zeros) inside the body,
            # which the varying-manual-axes checker rejects under
            # shard_map; recurrent models declare `flax_rnn_carry = True`
            # and run with the check off (correctness held by the
            # sim==mesh parity tests) — every other model keeps the guard
            self._check_vma = not getattr(module, "flax_rnn_carry", False)
            self._round_fn = make_spmd_round(module, task, cfg.train,
                                             self.mesh, donate=True,
                                             check_vma=self._check_vma)
            self._eval_fn = make_sharded_eval(module, task, self.mesh,
                                              check_vma=self._check_vma)
        if self._shard_params is not None:  # place into the TP/FSDP layout
            self.variables = self._shard_params(self.variables)
        else:
            # commit the fresh init to the mesh the way every round's
            # output comes back (replicated): jit caches on input
            # sharding, so an uncommitted round-0 model would compile the
            # round once for round 0 and again for round 1 (the first
            # chip run paid 33.8 s, then 24.7 s)
            self.variables = jax.device_put(
                self.variables, NamedSharding(self.mesh, P()))

    def _round_devices(self) -> list:
        return list(self.mesh.devices.flat)

    def _put(self, a):
        """Each chip's piece of ``a`` straight onto that chip: the slots
        are contiguous by chip (``_pad_round``, the data axis of
        ``_data_sharding``), so a host array *is* its pieces, and each is
        one transfer from the host - not an upload to the first chip and
        a reshard chip to chip. A device array (the round's keys) is
        resharded as ever."""
        return jax.device_put(a, self._data_sharding)

    def _pad_round(self, idxs):
        """Pad the sampled-client list to a mesh-size multiple with
        zero-weight duplicate slots (masked out of the aggregation)."""
        idxs = np.asarray(idxs)
        P_round = len(idxs)
        rem = (-P_round) % self.n_dev
        if rem == 0:
            return idxs, np.ones(P_round, np.float32)
        padded = np.concatenate([idxs, np.repeat(idxs[-1:], rem)])
        alive = np.concatenate([np.ones(P_round), np.zeros(rem)])
        return padded, alive.astype(np.float32)

    def _round_inputs(self, x, y, mask, keys, weights, agg_key) -> tuple:
        # the psum mean draws nothing: the mesh programs take no key for it
        return x, y, mask, keys, weights

    def _round_operands(self, args: tuple, round_idx: int) -> tuple:
        # the decayed builder takes the replicated round index as its final
        # operand (make_spmd_round's conditional spec)
        if self.config.train.lr_decay_round != 1.0:
            return args + (jnp.uint32(round_idx),)
        return args

    def evaluate(self, round_idx: int) -> Dict:
        """Test metrics over the sharded test union (``_eval_global``)."""
        rec = {"round": round_idx}
        stats = self._eval_global()
        if stats is not None:
            rec.update(_normalized(stats, "test"))
        return rec

    def _eval_global(self):
        xt, yt = self.dataset.test_data_global
        if not len(xt):
            return None
        with self.timer.phase("eval"):
            if (self._eval_cache is None
                    or self._eval_cache[0] is not self.dataset):
                xt, yt = eval_subsample(xt, yt,
                                        self.config.eval_test_subsample,
                                        self.config.seed)
                n = len(xt)
                n_pad = ((n + self.n_dev - 1) // self.n_dev) * self.n_dev
                pad = n_pad - n
                x = np.pad(np.asarray(xt),
                           [(0, pad)] + [(0, 0)] * (xt.ndim - 1))
                y = np.pad(np.asarray(yt),
                           [(0, pad)] + [(0, 0)] * (yt.ndim - 1))
                m = np.concatenate([np.ones(n, np.float32),
                                    np.zeros(pad, np.float32)])
                # eval union: padded to a mesh multiple, sharded, resident
                self._eval_cache = (self.dataset, (self._put(x),
                                                   self._put(y),
                                                   self._put(m)))
            x, y, m = self._eval_cache[1]
            # every caller reads the sums as host floats next; waiting here
            # makes the span the evaluation and not its enqueue
            # ft: allow[FT003] eval-boundary sync, inside the eval phase
            return jax.block_until_ready(
                self._eval_fn(self.variables, x, y, m))

    def run_rounds_fused(self, r0: int, rounds: int, next_window=None):
        """Advance the model by ``rounds`` rounds in ONE device dispatch.

        Full participation (``client_num_per_round == client_num``): the
        federation is packed and uploaded once, resident across calls, and
        per-round keys derive in-scan (make_spmd_multiround). Sampled
        cohorts: the R cohorts are drawn host-side with the host loop's
        exact sampling stream, packed as one ``[R, P, n_pad, ...]`` block
        at the block's cohort bucket, and scanned in one dispatch
        (make_spmd_block_multiround) — both throughput levers at once,
        trajectory-identical to R ``run_round`` calls. Returns stacked
        per-round stats.

        ``next_window``: the caller's ACTUAL next ``(r0, rounds)`` window
        (``train_fused`` knows its whole chunk schedule up front), so the
        block prefetcher packs exactly that window behind this dispatch;
        the bare ``(r0 + rounds, rounds)`` guess would miss at every
        eval-boundary chunk-size change and waste whole-window speculative
        uploads. ``()`` means "nothing follows" (last window: speculate
        nothing); None keeps the uniform-window guess for direct callers."""
        cfg = self.config
        N = self.dataset.client_num
        if cfg.model_parallel:
            raise ValueError(
                "fused mesh rounds support the flat 'clients' mesh or a "
                "named mesh_shape mesh; legacy model_parallel does not "
                "compose with the fused scan")
        self.timer.begin_round(r0)  # one span and one record a block
        if self._layout is not None or cfg.client_num_per_round != N:
            # named mesh: the GSPMD block scan serves full AND sampled
            # participation (the resident full-federation fast path is a
            # shard_map program on the 'clients' axis only)
            stats = self._run_block_fused(r0, rounds,
                                          next_window=next_window)
        else:
            stats = self._run_resident_fused(r0, rounds)
        self.timer.end_round(r0, extra={"rounds": rounds})
        return stats

    def _run_resident_fused(self, r0: int, rounds: int):
        """Full participation on the flat mesh: the federation packed and
        uploaded once, per-round keys derived in-scan."""
        cfg = self.config
        N = self.dataset.client_num
        if (getattr(self, "_fused_data", None) is None
                or self._fused_data[0] is not self.dataset):
            padded, alive = self._pad_round(np.arange(N))
            x, y, mask = self.dataset.pack_clients(
                padded, cfg.train.batch_size, n_pad=self._n_pad)
            mask = mask * alive[:, None]
            weights = self.dataset.client_weights(padded) * alive
            put = self._put
            # keyed by dataset identity like _pack_cache/_eval_cache: a
            # mid-run dataset swap must invalidate the resident arrays
            self._fused_data = (self.dataset,
                                (put(x), put(y), put(mask),
                                 put(jnp.asarray(np.asarray(padded),
                                                 dtype=jnp.uint32)),
                                 put(weights)))
            self._fused_fns = {}
        if rounds not in self._fused_fns:
            self._fused_fns[rounds] = make_spmd_multiround(
                self.module, self.task, cfg.train, self.mesh, rounds,
                check_vma=getattr(self, "_check_vma", True))
        self.variables, stats = self._fused_fns[rounds](
            self.variables, *self._fused_data[1], self._base_key,
            jnp.uint32(r0))
        return stats

    def _pack_block(self, key):
        """Host side of one fused block window ``key = (r0, rounds)``:
        draw the R cohorts with the host sampling stream, pack them as one
        ``[R, P, n_pad, ...]`` batch, shard-upload. Thread-safe (the block
        prefetcher's ``produce``); the payload carries the dataset for the
        caller's identity check."""
        r0, rounds = key
        cfg = self.config
        bsz = cfg.train.batch_size
        ds = self.dataset
        with self.timer.phase("pack"):
            cohorts = [sample_clients(r, ds.client_num,
                                      cfg.client_num_per_round)
                       for r in range(r0, r0 + rounds)]
            padded_alive = [self._pad_round(np.asarray(c)) for c in cohorts]
            flat = np.concatenate([p for p, _ in padded_alive])
            alive = np.concatenate([a for _, a in padded_alive])
            n_pad = (max(ds.cohort_padded_len(c, bsz) for c in cohorts)
                     if cfg.pack == "cohort" else self._n_pad)
            x, y, mask = ds.pack_clients(flat, bsz, n_pad=n_pad)
            mask = mask * alive[:, None]
            weights = ds.client_weights(flat) * alive
            P_pad = len(padded_alive[0][0])  # cohort padded to the mesh
            lead = (rounds, P_pad)
        with self.timer.phase("upload"):
            put = lambda a: jax.device_put(
                jnp.asarray(a), NamedSharding(self.mesh,
                                              P(None, self._data_axis)))
            args = (put(x.reshape(lead + x.shape[1:])),
                    put(y.reshape(lead + y.shape[1:])),
                    put(mask.reshape(lead + mask.shape[1:])),
                    put(flat.astype(np.uint32).reshape(lead)),
                    put(weights.reshape(lead)))
        return ds, args

    def _block_prefetcher(self):
        """Fused-block-window prefetcher. Clamped to ONE window ahead
        regardless of prefetch_depth: each slot holds a whole R-round
        block, so depth 1 is already double buffering and deeper
        speculation would multiply HBM by block size."""
        from fedml_tpu.parallel.prefetch import (RoundPrefetcher,
                                                 bind_prefetcher,
                                                 resolve_prefetch_depth)
        depth = resolve_prefetch_depth(
            getattr(self.config, "prefetch_depth", 0))
        if depth <= 0:
            if self._block_prefetch is not None:
                # kill switch flipped mid-run: a block slot is a whole
                # [R, P, n_pad, ...] sharded window — free it
                self._block_prefetch[0].invalidate()
            return None
        self._block_prefetch = bind_prefetcher(
            self._block_prefetch, self.dataset,
            lambda: RoundPrefetcher(self._pack_block, depth=1,
                                    next_key=lambda k: (k[0] + k[1], k[1]),
                                    name="mesh-block-prefetch"))
        return self._block_prefetch[0]

    def _run_block_fused(self, r0: int, rounds: int, next_window=None):
        """Sampled-cohort fused block on the mesh: host-drawn cohorts,
        one [R, P, n_pad, ...] sharded upload, one scan dispatch. With
        prefetching on, the NEXT window's pack + upload runs behind this
        window's scan (the caller's real schedule when supplied, see
        run_rounds_fused)."""
        pf = self._block_prefetcher()
        if pf is not None:
            from fedml_tpu.parallel.prefetch import consume
            upcoming = (None if next_window is None
                        else ([tuple(next_window)] if next_window else []))
            _, args = consume(pf, (r0, rounds), self.timer,
                              self.dataset, self._pack_block,
                              upcoming=upcoming)
        else:
            _, args = self._pack_block((r0, rounds))
        if getattr(self, "_block_fn", None) is None:
            # one jitted program; jit's own shape-keyed trace cache
            # specializes per (R, P_pad, n_pad) block shape
            if self._layout is not None:
                from fedml_tpu.parallel.mesh import make_mesh_block_multiround
                self._block_fn = make_mesh_block_multiround(
                    self.module, self.task, self.config.train, self.mesh,
                    self._layout, donate=True)
            else:
                self._block_fn = make_spmd_block_multiround(
                    self.module, self.task, self.config.train, self.mesh,
                    check_vma=getattr(self, "_check_vma", True))
        with self.timer.phase("dispatch"):
            self.variables, stats = self._block_fn(
                self.variables, *args, self._base_key, jnp.uint32(r0))
        return stats

    def train_fused(self, max_rounds_per_dispatch: Optional[int] = None
                    ) -> Dict:
        """The round loop with fused dispatches: one device call per eval
        interval (capped at ``max_rounds_per_dispatch``), eval after rounds
        0, freq, 2*freq, ..., and the last round — the same cadence as
        ``train()``, so fused and host histories line up (the mesh analogue
        of FusedRounds.train)."""
        cfg = self.config
        if self._obs is not None:
            # same caveat as FedAvgAPI.fused_rounds: fused scans have no
            # per-round host boundary to record
            logging.warning(
                "observability is on but train_fused dispatches whole "
                "round blocks — one flight record a block, not a round, "
                "for fused spans; use train() for per-round timelines")
        if cfg.comm_round <= 0:
            return self.history[-1] if self.history else {}
        freq = cfg.frequency_of_the_test
        evals = sorted({r for r in range(0, cfg.comm_round, freq)}
                       | {cfg.comm_round - 1})
        # the whole chunk schedule is known up front — computed here so
        # each dispatch can hand the block prefetcher its REAL successor
        # window (chunk sizes change at eval boundaries, which a uniform
        # stride guess would miss every time)
        windows, r = [], 0
        for e in evals:
            while r <= e:
                chunk = e + 1 - r
                if max_rounds_per_dispatch:
                    chunk = min(chunk, max_rounds_per_dispatch)
                windows.append((r, chunk, e))
                r += chunk
        wi = 0
        for e in evals:
            stats = None
            while wi < len(windows) and windows[wi][2] == e:
                w0, chunk, _ = windows[wi]
                nxt = (windows[wi + 1][:2] if wi + 1 < len(windows)
                       else ())
                stats = self.run_rounds_fused(w0, chunk, next_window=nxt)
                wi += 1
            with self.timer.phase("device_wait"):
                # ft: allow[FT003] eval-boundary sync, by design
                jax.block_until_ready(self.variables)
            rec = self.evaluate(e)
            rec["train_loss_local"] = (
                float(stats["loss_sum"][-1])
                / max(1.0, float(stats["count"][-1])))
            self.history.append(rec)
        return self.history[-1] if self.history else {}

    def train(self, checkpoint_mgr=None, resume: bool = False) -> Dict:
        """The base's round loop with optional round-level
        checkpoint/resume: client sampling and per-client RNG are (seed,
        round)-derived, so restarting from ``(round_idx, variables)`` is
        bit-identical to never stopping (utils/checkpoint.py)."""
        if checkpoint_mgr is None:
            return self._train_rounds(0)
        if self._obs is not None and getattr(self.config, "job_id",
                                             None) is None:
            # re-key the derived default id onto the run's durable
            # namespace BEFORE any record lands: a crash-resumed leg must
            # rejoin its own flight timeline, not fork a phantom second
            # job under a fresh nonce (obs.default_job_id stable_key)
            from fedml_tpu.obs import default_job_id
            self._obs.recorder.job_id = default_job_id(
                self._job_prefix, stable_key=checkpoint_mgr.directory)
        start = 0
        if resume:
            restored = checkpoint_mgr.restore_latest(
                {"variables": self.variables})
            if restored:
                state, meta = restored
                self.variables = state["variables"]
                start = meta["round_idx"]
        return self._train_rounds(start, lambda r: checkpoint_mgr.save(
            r + 1, {"variables": self.variables}))


# -- static-analysis hook (fedml_tpu.analysis layer 2) ----------------------
from fedml_tpu.analysis.registry import AuditSpec, hot_entry_point  # noqa: E402


@hot_entry_point("spmd.block_multiround")
def _audit_block_multiround() -> AuditSpec:
    """The fused mesh block (make_spmd_block_multiround) over two real
    [R, P, n_pad, ...] windows built by the driver's own _pack_block:
    consecutive windows of one run must share one lowering (pack="global"
    pins n_pad; P is the cohort padded to the mesh). Mesh size adapts to
    the backend (8 virtual CPU devices under CI, 1 on a bare host) —
    the audit checks the program, not the device count."""
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression

    n_dev = len(jax.devices())
    ds = make_blob_federated(client_num=max(4, n_dev), n_samples=240, seed=0)
    api = DistributedFedAvgAPI(
        ds, LogisticRegression(num_classes=ds.class_num),
        mesh=build_mesh({"clients": n_dev}),
        config=DistributedFedAvgConfig(
            comm_round=4, client_num_per_round=max(2, n_dev), pack="global",
            prefetch_depth=0,
            train=TrainConfig(epochs=1, batch_size=8)))
    fn = make_spmd_block_multiround(api.module, api.task, api.config.train,
                                    api.mesh,
                                    check_vma=getattr(api, "_check_vma",
                                                      True))

    def window(r0, rounds):
        _, args = api._pack_block((r0, rounds))
        return (api.variables, *args, api._base_key, jnp.uint32(r0))

    return AuditSpec(fn=fn, sweep=[window(0, 2), window(2, 2)],
                     max_lowerings=1, grad_path=True)


@hot_entry_point("spmd.sharded_eval")
def _audit_sharded_eval() -> AuditSpec:
    """The shard_map'd eval path (make_sharded_eval): per-device stat
    sums meeting in one psum over 'clients'. Registered so the
    collective-signature audit (FT105/FT106) pins the psum set of the
    sharded eval lowering — the mesh work inherits drift detection on
    its simplest collective program. The eval batch (24) divides every
    CI device count (1 and 8), so one lowering serves both."""
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression

    n_dev = len(jax.devices())
    mesh = build_mesh({"clients": n_dev})
    ds = make_blob_federated(client_num=4, n_samples=240, seed=0)
    module = LogisticRegression(num_classes=ds.class_num)
    xt, yt = ds.test_data_global
    n = (24 // n_dev) * n_dev or n_dev  # largest multiple of n_dev <= 24
    xt, yt = jnp.asarray(xt[:n]), jnp.asarray(yt[:n])
    mask = jnp.ones(len(xt), jnp.float32)
    variables = module.init(jax.random.key(0), xt[:1], train=False)
    fn = make_sharded_eval(module, "classification", mesh)
    # sweep point 2 mirrors the actor path: wire-decoded NUMPY arrays
    # (uncommitted) — a different caller that must share the jnp-typed
    # point's lowering key, like the cross-silo warmup contract
    np_args = (variables, np.asarray(xt), np.asarray(yt),
               np.ones(len(xt), np.float32))
    return AuditSpec(fn=fn,
                     sweep=[(variables, xt, yt, mask), np_args],
                     max_lowerings=1, grad_path=False)
