"""Named multi-axis SPMD federation mesh — data x fsdp x tp on ONE mesh.

Before this module the parallel layer had three disjoint 1-D/2-D meshes:
the fused round scan lived on a ``('clients',)`` shard_map mesh
(parallel/spmd.py), ZeRO sharding on an ``('fsdp',)`` mesh with its own
largest-axis rule (parallel/fsdp.py), and Megatron TP on a ``('tp',)``
mesh with its own name rules (parallel/tensor.py). They could not
compose: a federated round was either data-parallel OR model-sharded,
and every measured bench row ran one chip while the multichip story
was a dry run (``__graft_entry__.dryrun_multichip``).

This module promotes all of it to one canonical named mesh:

- ``data``  — sampled clients (the federation axis; what spmd.py calls
  ``clients``). The cross-client weighted FedAvg mean reduces over it.
- ``fsdp``  — ZeRO-3 parameter sharding: each leaf sharded on its
  largest divisible axis, small leaves replicated (the fsdp.py rule,
  imported — ONE copy).
- ``tp``    — Megatron tensor parallelism for the transformer's Dense
  kernels (column/row split sets imported from tensor.py — ONE copy).

:class:`SpecLayout` is the single canonical per-parameter PartitionSpec
assignment: name/shape rules that reduce exactly to ``fsdp_specs`` when
only ``fsdp`` is present and to ``transformer_tp_specs`` when only
``tp`` is present (pinned by tests/test_mesh_layout.py), and compose
both on a 3-D mesh. Divisibility is guarded per-dimension — a dim is
never oversharded past its size — and the replicated ``P()`` fallback
is explicit.

The round programs are pure GSPMD (``jax.jit`` + ``NamedSharding``,
like gspmd_round.py): shard_map's replicated-params contract cannot
express parameters that are *sharded* over ``fsdp``/``tp`` while the
client batch varies over ``data``, so XLA's SPMD partitioner inserts
the collectives the layout implies. ``make_mesh_block_multiround`` is
the fused sampled-round scan (the spmd.make_spmd_block_multiround
program shape) on the named mesh; its round body is literally the sim
driver's (``make_vmapped_body`` + ``pt.tree_weighted_mean`` + the
shared ``round_keys`` fold_in chain), so a ``{data: 1}`` mesh
reproduces the sim trajectory bit-exactly.

CLI (used by ci/run_fast.sh and bench.py):

    python -m fedml_tpu.parallel.mesh --smoke
    python -m fedml_tpu.parallel.mesh --bench-worker --workload \
        transformer_flash_s2048 --mesh data=8 --force-host
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.core.sampling import round_keys
from fedml_tpu.parallel.fsdp import leaf_fsdp_spec
from fedml_tpu.parallel.tensor import COLUMN_PARALLEL, ROW_PARALLEL
from fedml_tpu.trainer.functional import (TrainConfig, make_local_train,
                                          round_lr_scale)

#: canonical axis order — every named federation mesh declares its axes
#: in this order so mesh shapes print/compare stably
MESH_AXES = ("data", "fsdp", "tp")


def parse_mesh_shape(spec: str) -> Dict[str, int]:
    """``"data=4,fsdp=2"`` -> ``{"data": 4, "fsdp": 2}`` (canonical axis
    order, unknown axis names rejected loudly)."""
    shape: Dict[str, int] = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"mesh shape needs axis=size entries, got {part!r} "
                f"(e.g. 'data=4,fsdp=2')")
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in MESH_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r}; valid axes: {MESH_AXES}")
        n = int(size)
        if n < 1:
            raise ValueError(f"mesh axis {name}={n} must be >= 1")
        shape[name] = n
    if "data" not in shape:
        raise ValueError("mesh shape must include the 'data' axis")
    return {a: shape[a] for a in MESH_AXES if a in shape}


def build_named_mesh(shape: Dict[str, int],
                     devices: Optional[list] = None) -> Mesh:
    """Named federation mesh in canonical axis order. Unlike
    ``spmd.build_mesh`` the mesh may span a PREFIX of the local devices
    (a 2-device mesh on an 8-virtual-device CI host), so parity tests
    can build {1, 2, 4, 8}-device meshes side by side."""
    ordered = {a: int(shape[a]) for a in MESH_AXES if a in shape}
    unknown = set(shape) - set(ordered)
    if unknown:
        raise ValueError(
            f"unknown mesh axes {sorted(unknown)}; valid axes: {MESH_AXES}")
    n = int(np.prod(list(ordered.values()))) if ordered else 0
    if n < 1:
        raise ValueError(f"empty mesh shape: {shape!r}")
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh shape {ordered} needs {n} devices, have {len(devs)}")
    from fedml_tpu.parallel.spmd import build_mesh

    return build_mesh(ordered, devices=devs[:n])


def _path_names(path) -> list:
    return [getattr(p, "key", getattr(p, "name", "")) for p in path]


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """The canonical per-parameter PartitionSpec layout for a named
    data x fsdp x tp mesh — ONE rule set unifying the ad-hoc pair that
    grew on disjoint meshes:

    - transformer Dense kernels get the Megatron split (column-parallel
      ``Dense_0``/``Dense_2`` + logit head on ``tp`` dim 1, row-parallel
      ``Dense_1``/``Dense_3`` on ``tp`` dim 0 — the tensor.py sets,
      imported), with the OTHER kernel dim ZeRO-sharded over ``fsdp``
      when divisible;
    - every other leaf (conv kernels, embeddings, heads of non-TP
      models) follows the ZeRO largest-divisible-axis rule
      (fsdp.leaf_fsdp_spec, imported) over ``fsdp``;
    - leaves smaller than ``min_size`` elements (LayerNorm/GroupNorm
      scales, biases) replicate — gathering them costs more than
      storing them;
    - a dim is sharded only when the axis size divides it (never
      oversharded past its size); anything unmatched falls back to the
      explicit replicated ``P()``.

    Axis sizes are read from the mesh, so the same layout object serves
    any mesh shape: absent/size-1 axes simply drop out of the specs
    (a ``{data: 1}`` mesh yields all-replicated params — the sim
    program).
    """

    data_axis: str = "data"
    fsdp_axis: str = "fsdp"
    tp_axis: str = "tp"
    min_size: int = 1024

    def axis_size(self, mesh: Mesh, axis: str) -> int:
        return int(dict(mesh.shape).get(axis, 1))

    def param_spec(self, path, leaf, mesh: Mesh) -> P:
        names = _path_names(path)
        shape = tuple(getattr(leaf, "shape", ()))
        tp_n = self.axis_size(mesh, self.tp_axis)
        fsdp_n = self.axis_size(mesh, self.fsdp_axis)
        in_block = any(n.startswith("TransformerBlock") for n in names)
        module = next((n for n in reversed(names)
                       if n.startswith(("Dense", "Embed", "LayerNorm",
                                        "pos_embed"))), "")
        leaf_name = names[-1] if names else ""
        # -- Megatron split for transformer Dense leaves (tensor.py rule)
        if tp_n > 1 and module.startswith("Dense") \
                and (in_block or module == "Dense_0"):
            column = (module in COLUMN_PARALLEL if in_block
                      else True)  # top-level Dense_0: logit head (vocab)
            row = in_block and module in ROW_PARALLEL
            if leaf_name == "kernel" and len(shape) == 2 \
                    and (column or row):
                tp_dim = 1 if column else 0
                dims: list = [None, None]
                if shape[tp_dim] % tp_n == 0:
                    dims[tp_dim] = self.tp_axis
                other = 1 - tp_dim
                if (fsdp_n > 1 and shape[other] % fsdp_n == 0
                        and int(np.prod(shape)) >= self.min_size):
                    dims[other] = self.fsdp_axis
                return P(*dims)
            if leaf_name == "bias":
                # column-parallel bias rides the split output features;
                # row-parallel bias applies after the psum -> replicated
                if column and shape and shape[0] % tp_n == 0:
                    return P(self.tp_axis)
                return P()
        # -- everything else: the ZeRO largest-divisible-axis rule
        if fsdp_n > 1:
            return leaf_fsdp_spec(leaf, fsdp_n, axis=self.fsdp_axis,
                                  min_size=self.min_size)
        return P()

    def param_specs(self, variables, mesh: Mesh):
        """PartitionSpec tree mirroring ``variables`` — every leaf gets
        a spec (the replicated fallback is explicit, never missing)."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.param_spec(path, leaf, mesh),
            variables)

    def specs_fn(self, mesh: Mesh):
        """The layout as a ``param_specs_fn`` for gspmd_round factories."""
        return lambda tree: self.param_specs(tree, mesh)

    def data_spec(self) -> P:
        """Client-major round inputs ``[P, ...]``: sharded over data."""
        return P(self.data_axis)

    def block_spec(self) -> P:
        """Fused-block inputs ``[R, P, ...]``: round dim replicated,
        client dim sharded over data."""
        return P(None, self.data_axis)


#: the one default layout every mesh driver shares
DEFAULT_LAYOUT = SpecLayout()


def make_mesh_federated_round(module, task: str, cfg: TrainConfig,
                              mesh: Mesh,
                              layout: SpecLayout = DEFAULT_LAYOUT,
                              donate: bool = False):
    """One FedAvg round on the named mesh: sampled clients data-parallel
    over ``data`` while every client's model carries the canonical
    fsdp/tp layout. Delegates to the shared gspmd_round factory — the
    same round body as every other FedAvg path. Returns
    ``(round_fn, shard_params)``."""
    from fedml_tpu.parallel.gspmd_round import make_sharded_federated_round

    return make_sharded_federated_round(
        module, task, cfg, mesh, layout.specs_fn(mesh),
        clients_axis=layout.data_axis, donate=donate)


def make_mesh_eval(module, task: str, mesh: Mesh,
                   layout: SpecLayout = DEFAULT_LAYOUT):
    """Sharded eval on the named mesh: the eval union rides ``data``,
    params keep their layout (gspmd_round.make_gspmd_eval)."""
    from fedml_tpu.parallel.gspmd_round import make_gspmd_eval

    return make_gspmd_eval(module, task, mesh, layout.specs_fn(mesh),
                           clients_axis=layout.data_axis)


def _data_only(mesh: Mesh, layout: SpecLayout) -> bool:
    """True when no model axis actually shards (every non-data axis is
    absent or size 1) — params are replicated, so the shard_map program
    (explicit psum aggregation) is expressible."""
    return all(int(size) <= 1 for name, size in dict(mesh.shape).items()
               if name != layout.data_axis)


def make_mesh_block_multiround(module, task: str, cfg: TrainConfig,
                               mesh: Mesh,
                               layout: SpecLayout = DEFAULT_LAYOUT,
                               donate: bool = True,
                               variant: Optional[str] = None):
    """R sampled-cohort FedAvg rounds as ONE jitted scan on the named
    mesh — the spmd.make_spmd_block_multiround program promoted to
    data x fsdp x tp. Two lowerings serve the one driver signature,
    picked by what the mesh can express (``variant`` None = auto):

    - ``"shard_map"`` — the explicit-psum fused scan
      (spmd.make_spmd_block_multiround) with its client axis renamed to
      ``data``. Fastest per-device program, but shard_map's
      replicated-params contract cannot express fsdp/tp-sharded
      parameters; auto-picked for multi-device data-ONLY meshes.
    - ``"gspmd"`` — a jit scan whose body is the SIM driver's round
      verbatim (make_vmapped_body + pt.tree_weighted_mean + the shared
      round_keys fold_in chain) with the layout's NamedShardings; XLA's
      partitioner inserts the collectives the layout implies. Auto-
      picked for sharded layouts, and for ``{data: 1}`` where the
      sim-identical jaxpr makes the trajectory BIT-exact vs
      FedAvgAPI/FusedRounds (the parity contract); wider meshes agree
      within reduction-reordering tolerance
      (tests/test_mesh_layout.py).

    Returns ``fn(variables, xs, ys, masks, idsR, weightsR, base_key,
    r0) -> (new_variables, stats[R])`` with block arrays
    ``[R, P, n_pad, ...]`` sharded ``P(None, 'data')``.
    """
    if variant is None:
        variant = ("shard_map"
                   if (_data_only(mesh, layout)
                       and int(dict(mesh.shape)[layout.data_axis]) > 1)
                   else "gspmd")
    if variant == "shard_map":
        if not _data_only(mesh, layout):
            raise ValueError(
                "shard_map block variant needs a data-only mesh "
                f"(replicated params); got {dict(mesh.shape)}")
        from fedml_tpu.parallel.spmd import make_spmd_block_multiround

        return make_spmd_block_multiround(
            module, task, cfg, mesh, axis=layout.data_axis, donate=donate,
            check_vma=not getattr(module, "flax_rnn_carry", False))
    if variant != "gspmd":
        raise ValueError(f"unknown block variant: {variant!r}")
    from fedml_tpu.algorithms.fedavg import make_vmapped_body
    from fedml_tpu.core import pytree as pt
    from fedml_tpu.parallel.gspmd_round import _avals_key, tree_shardings

    body_v = make_vmapped_body(make_local_train(module, task, cfg))

    def body(variables, xs, ys, masks, idsR, weightsR, base_key, r0):
        def one_round(vars_r, inp):
            r, x, y, mask, ids, weights = inp
            _, keys, _ = round_keys(base_key, r, ids)
            stacked, totals = body_v(vars_r, x, y, mask, keys,
                                     round_lr_scale(cfg, r))
            return pt.tree_weighted_mean(stacked, weights), totals

        rs = r0 + jnp.arange(xs.shape[0], dtype=jnp.uint32)
        return jax.lax.scan(one_round, variables,
                            (rs, xs, ys, masks, idsR, weightsR))

    _jit = {}  # one compile per variables structure (gspmd_round rule)

    def jitted(variables, xs, ys, masks, idsR, weightsR, base_key, r0):
        key = _avals_key(variables)
        if key not in _jit:
            params = tree_shardings(mesh,
                                    layout.param_specs(variables, mesh))
            block = NamedSharding(mesh, layout.block_spec())
            rep = NamedSharding(mesh, P())
            _jit[key] = jax.jit(
                body,
                in_shardings=(params, block, block, block, block, block,
                              rep, rep),
                out_shardings=(params, None),
                donate_argnums=(0,) if donate else ())
        return _jit[key](variables, xs, ys, masks, idsR, weightsR,
                         base_key, r0)

    return jitted


# -- measured collective accounting ------------------------------------------

#: HLO collective op mnemonics whose output bytes we account (the
#: GSPMD partitioner emits these; jaxpr-level psums don't exist on the
#: jit path, so the compiled module is the measurement surface)
_HLO_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")

_HLO_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_HLO_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def _hlo_shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _HLO_SHAPE_RE.findall(text):
        size = _HLO_DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def collective_hlo_stats(compiled_text: str) -> Dict[str, Any]:
    """Measured collective footprint of ONE compiled (post-partitioner)
    HLO module: per-op instruction counts and output bytes. This is the
    program XLA actually runs — the honest wire figure for a GSPMD
    lowering, where no jaxpr-level collective exists to count."""
    ops: Dict[str, Dict[str, int]] = {}
    for line in compiled_text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("%") and " = " not in stripped:
            continue
        for op in _HLO_COLLECTIVE_OPS:
            # match the instruction opcode, not fused-computation names
            if f" {op}(" not in stripped and f" {op}-start(" \
                    not in stripped:
                continue
            _, _, rhs = stripped.partition(" = ")
            out_part = rhs.split(f" {op}", 1)[0]
            entry = ops.setdefault(op, {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += _hlo_shape_bytes(out_part)
            break
    return {"ops": ops,
            "total_bytes": sum(e["bytes"] for e in ops.values()),
            "total_count": sum(e["count"] for e in ops.values())}


def program_collective_stats(fn, *args) -> Dict[str, Any]:
    """Lower + compile ``fn(*args)`` and account its collectives.
    ``fn`` may be a jitted callable or a plain function (wrapped)."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        lower = jax.jit(fn).lower
    return collective_hlo_stats(lower(*args).compile().as_text())


# -- static-analysis hook (fedml_tpu.analysis layer 2) ----------------------
from fedml_tpu.analysis.registry import AuditSpec, hot_entry_point  # noqa: E402


def _audit_api(n_dev: int):
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig)

    ds = make_blob_federated(client_num=max(4, n_dev), n_samples=240,
                             seed=0)
    return DistributedFedAvgAPI(
        ds, LogisticRegression(num_classes=ds.class_num),
        config=DistributedFedAvgConfig(
            comm_round=4, client_num_per_round=max(2, n_dev),
            pack="global", prefetch_depth=0,
            mesh_shape={"data": n_dev},
            train=TrainConfig(epochs=1, batch_size=8)))


@hot_entry_point("mesh.block_multiround")
def _audit_mesh_block_multiround() -> AuditSpec:
    """The fused named-mesh block scan over two real windows built by
    the driver's own _pack_block — consecutive windows of one run must
    share one lowering. Pinned to the shard_map variant (the program
    multi-device data-only meshes run): its explicit psum set over
    'data' is the drift surface, and shard_map signatures are
    device-count-independent (spmd.block_multiround precedent). The
    gspmd variant's jaxpr-level signature is empty at every mesh size
    (partitioner-inserted collectives; fedavg.round_fn precedent) and
    is covered by mesh.federated_round."""
    api = _audit_api(len(jax.devices()))
    fn = make_mesh_block_multiround(api.module, api.task,
                                    api.config.train, api.mesh,
                                    api._layout, donate=False,
                                    variant="shard_map")

    def window(r0, rounds):
        _, args = api._pack_block((r0, rounds))
        return (api.variables, *args, api._base_key, jnp.uint32(r0))

    return AuditSpec(fn=fn, sweep=[window(0, 2), window(2, 2)],
                     max_lowerings=1, grad_path=True)


@hot_entry_point("mesh.federated_round")
def _audit_mesh_federated_round() -> AuditSpec:
    """The per-round named-mesh program (make_mesh_federated_round via
    the shared gspmd_round factory) over two rounds' real host inputs —
    every round of a run must hit the one compiled program."""
    api = _audit_api(len(jax.devices()))
    fn, _ = make_mesh_federated_round(api.module, api.task,
                                      api.config.train, api.mesh,
                                      api._layout, donate=False)

    def inputs(r):
        _, _, (xd, yd, maskd, keysd, wd) = api._pack_round(r)
        return (api.variables, xd, yd, maskd, keysd, wd)

    return AuditSpec(fn=fn, sweep=[inputs(0), inputs(1)],
                     max_lowerings=1, grad_path=True)


# -- CLI: ci smoke lane + bench scaling worker ------------------------------

def _measure_host_peak_flops(n: int = 768, iters: int = 4) -> float:
    """Measured f32 GEMM throughput of THIS host (whole host, not per
    virtual device — forced-host devices share the physical cores), as
    an honest denominator for CPU scaling rows where the documented
    per-chip peak table refuses to guess."""
    import time

    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    jax.block_until_ready(mm(a))
    t0 = time.perf_counter()
    out = a
    for _ in range(iters):
        out = mm(out)
    jax.block_until_ready(out)
    return 2.0 * n ** 3 * iters / (time.perf_counter() - t0)


def _bench_workload(workload: str, mesh_shape: Dict[str, int],
                    rounds_per_dispatch: int, timed_dispatches: int
                    ) -> Dict[str, Any]:
    """Measure fused federated rounds/sec for one workload at one mesh
    shape — the mesh_scaling bench worker body. Times the fused block
    program itself (the block is packed once and re-dispatched; the
    driver pipelines host packing behind dispatch, so program
    throughput is the scaling observable)."""
    import time

    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig)
    from fedml_tpu.utils import on_tpu
    from fedml_tpu.utils.flops import analytic_flops

    n_dev = int(np.prod(list(mesh_shape.values())))
    tpu = on_tpu()
    if workload == "transformer_flash_s2048":
        from fedml_tpu.data.synthetic import make_token_federated
        from fedml_tpu.models.transformer import TransformerLM

        # CPU smoke shapes (same policy as bench_transformer_flash):
        # the real S=2048 stage shape only on a chip backend
        if tpu:
            vocab, width, depth, heads, S = 1024, 256, 4, 4, 2048
            n_pad, bsz, clients = 4, 4, 8
        else:
            vocab, width, depth, heads, S = 256, 64, 2, 2, 256
            n_pad, bsz, clients = 2, 2, 8
        ds = make_token_federated(client_num=clients, vocab_size=vocab,
                                  seq_len=S,
                                  sequences_per_client=n_pad * bsz,
                                  seed=0)
        module = TransformerLM(vocab_size=vocab, width=width, depth=depth,
                               num_heads=heads, max_len=S)
        task = "nwp"
        shape_note = {"seq_len": S, "width": width, "depth": depth,
                      "num_heads": heads, "cpu_smoke_shape": not tpu}
    elif workload == "resnet18_gn":
        from fedml_tpu.data.base import FederatedDataset
        from fedml_tpu.models import create_model

        hw, chans, classes, clients = (24, 3, 100, 8) if tpu \
            else (12, 3, 10, 8)
        samples, bsz = (20, 20) if tpu else (2, 2)
        rng = np.random.RandomState(0)
        train_local = {
            c: (rng.rand(samples, hw, hw, chans).astype(np.float32),
                rng.randint(0, classes, samples).astype(np.int32))
            for c in range(clients)}
        ds = FederatedDataset.from_client_arrays(
            train_local, {c: None for c in range(clients)}, classes)
        module = create_model("resnet18_gn", output_dim=classes)
        task = "classification"
        shape_note = {"hw": hw, "classes": classes,
                      "cpu_smoke_shape": not tpu}
    else:
        raise ValueError(f"unknown mesh_scaling workload: {workload!r}")

    R = rounds_per_dispatch
    api = DistributedFedAvgAPI(
        ds, module, task=task,
        config=DistributedFedAvgConfig(
            comm_round=R * (timed_dispatches + 1),
            client_num_per_round=clients, pack="global",
            prefetch_depth=0, mesh_shape=dict(mesh_shape),
            train=TrainConfig(epochs=1, batch_size=bsz, lr=0.1)))
    fn = make_mesh_block_multiround(api.module, api.task,
                                    api.config.train, api.mesh,
                                    api._layout, donate=False)
    # mirror the auto-variant rule so the row documents the program it
    # measured (and so analytic flops scale correctly below)
    variant = ("shard_map" if (_data_only(api.mesh, api._layout)
                               and n_dev > 1) else "gspmd")
    _, args = api._pack_block((0, R))
    run = lambda r0: fn(api.variables, *args, api._base_key,
                        jnp.uint32(r0))
    v, stats = run(0)  # compile + warmup
    jax.block_until_ready(v)
    assert np.isfinite(float(np.sum(np.asarray(stats["loss_sum"]))))
    t0 = time.perf_counter()
    for i in range(timed_dispatches):
        v, _ = run(i * R)
        jax.block_until_ready(v)
    dt = time.perf_counter() - t0
    rps = R * timed_dispatches / dt

    flops_block = None
    try:
        flops_block = float(analytic_flops(
            fn, api.variables, *args, api._base_key, jnp.uint32(0)))
    except Exception:  # ft: allow[FT005] analytic-flops cross-check column: a probe miss drops the column, never the bench row
        pass
    # shard_map jaxprs carry PER-DEVICE shapes (the data axis is already
    # split at trace time), so the global round count scales by the data
    # shard count; gspmd jaxprs trace at global shapes (x1)
    flops_scale = (int(dict(api.mesh.shape)[api._layout.data_axis])
                   if variant == "shard_map" else 1)
    round_flops = (flops_block * flops_scale / R if flops_block
                   else None)

    coll = program_collective_stats(
        fn, api.variables, *args, api._base_key, jnp.uint32(0))
    param_bytes = int(sum(
        np.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree.leaves(api.variables)))

    from fedml_tpu.obs.perf import device_peak_flops
    per_dev_peak = device_peak_flops(api.mesh.devices.flat[0])
    achieved = rps * round_flops if round_flops else None
    row: Dict[str, Any] = {
        "workload": workload,
        "mesh": dict(mesh_shape),
        "mesh_devices": n_dev,
        "device_kind": str(api.mesh.devices.flat[0].device_kind),
        "program_variant": variant,
        "rounds_per_dispatch": R,
        "timed_rounds": R * timed_dispatches,
        "rounds_per_sec": round(rps, 3),
        "round_flops": round_flops,
        "achieved_flops_per_s": (round(achieved, 3) if achieved
                                 else None),
        "mfu": (float(f"{achieved / (per_dev_peak * n_dev):.6g}")
                if achieved and per_dev_peak else None),
        "param_bytes": param_bytes,
        "collective_bytes_per_round": coll["total_bytes"] // R,
        "collective_ops": coll["ops"],
        **shape_note,
    }
    if achieved and not per_dev_peak:
        # CPU host: the documented peak table never guesses, so measure
        # the host's own GEMM peak as a labeled denominator instead.
        # Whole-host figure — forced-host devices share the cores, so
        # the fleet peak does NOT scale with mesh size here.
        host_peak = _measure_host_peak_flops()
        row["measured_host_peak_flops"] = round(host_peak, 3)
        row["peak_source"] = "measured_host_gemm_f32"
        row["mfu_vs_measured_host_peak"] = float(
            f"{achieved / host_peak:.6g}")
    return row


def _run_smoke(out_dir: str) -> int:
    """ci/run_fast.sh mesh lane (<= 20 s on the CI host): a real
    2-device named-mesh federation with the flight recorder ON, the
    fused block program exercised, the mesh entry points' collective
    signatures checked against ci/collective_baseline.json, and the
    flight log rebuilt by ``obs merge --ledger`` at rc 0."""
    import json
    import os
    import shutil

    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig)

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    flight_dir = os.path.join(out_dir, "flight")
    n_data = 2 if len(jax.devices()) >= 2 else 1
    ds = make_blob_federated(client_num=6, n_samples=240, seed=0)
    api = DistributedFedAvgAPI(
        ds, LogisticRegression(num_classes=ds.class_num),
        config=DistributedFedAvgConfig(
            comm_round=5, client_num_per_round=4, pack="global",
            prefetch_depth=0, mesh_shape={"data": n_data},
            obs_dir=flight_dir, job_id="mesh-smoke",
            train=TrainConfig(epochs=1, batch_size=8)))
    # per-round leg: flight records + the schedule-trace ledger the
    # merge cross-checks (cohorts recorded the moment they are drawn,
    # the single-process analogue of the cross-silo server's ledger)
    ledger_path = os.path.join(out_dir, "ledger.jsonl")
    with open(ledger_path, "w") as ledger:
        for r in range(3):
            idxs, stats = api.run_round(r)
            assert np.isfinite(float(stats["loss_sum"]))
            ledger.write(json.dumps(
                {"round": r, "cohort": [int(i) for i in idxs]}) + "\n")
    # fused leg: one 2-round block window through the named-mesh scan
    stats = api.run_rounds_fused(3, 2)
    jax.block_until_ready(api.variables)
    assert np.isfinite(float(np.asarray(stats["loss_sum"])[-1]))
    if api._obs is not None:
        api._obs.close()

    # collective audit over the mesh entry points vs the CI baseline
    from fedml_tpu.analysis.jaxpr_audit import (check_collective_baseline,
                                                run_audit)
    mesh_entries = ("mesh.block_multiround", "mesh.federated_round")
    findings, reports = run_audit(only=mesh_entries)
    baseline = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "ci",
        "collective_baseline.json")
    # reports cover the mesh entries only, so every baseline finding is
    # about one of them (or the baseline file itself); baseline entries
    # for other programs come back as stale names, ignored here
    base_findings, _stale = check_collective_baseline(reports, baseline)
    findings += base_findings
    if findings:
        for f in findings:
            print(f"mesh smoke: {f.format_text()}")
        return 1

    # flight log merged with the ledger — rc 0 is the lane's contract
    from fedml_tpu.obs.__main__ import main as obs_main
    rc = obs_main(["merge", flight_dir, "--ledger", ledger_path,
                   "--output", os.path.join(out_dir, "merged.json")])
    if rc != 0:
        print(f"mesh smoke: obs merge --ledger exited {rc}")
        return 1
    print(f"mesh smoke ok: {n_data}-device data mesh, 3 host rounds + "
          f"one fused 2-round block, collective audit green, "
          f"merge rc 0 ({out_dir})")
    return 0


def _cli(argv=None) -> int:
    import argparse
    import json
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m fedml_tpu.parallel.mesh",
        description="named-mesh federation: CI smoke + scaling worker")
    parser.add_argument("--smoke", action="store_true",
                        help="ci/run_fast.sh mesh lane")
    parser.add_argument("--out", default="runs/mesh_smoke",
                        help="smoke artifact directory")
    parser.add_argument("--bench-worker", action="store_true",
                        help="measure one (workload, mesh) point and "
                             "print a JSON row (bench.py mesh_scaling)")
    parser.add_argument("--workload", default="transformer_flash_s2048")
    parser.add_argument("--mesh", default="data=1",
                        help="mesh shape, e.g. data=8 or data=4,fsdp=2")
    parser.add_argument("--rounds", type=int, default=4,
                        help="fused rounds per dispatch")
    parser.add_argument("--dispatches", type=int, default=2,
                        help="timed dispatches (after one warmup)")
    args = parser.parse_args(argv)
    from fedml_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    if args.bench_worker:
        row = _bench_workload(args.workload, parse_mesh_shape(args.mesh),
                              args.rounds, args.dispatches)
        print(json.dumps(row), file=sys.stdout, flush=True)
        return 0
    if args.smoke:
        return _run_smoke(args.out)
    parser.error("pick one of --smoke / --bench-worker")
    return 2


if __name__ == "__main__":
    raise SystemExit(_cli())
