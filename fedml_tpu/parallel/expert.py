"""Expert parallelism: a Switch-style MoE FFN sharded over an ``ep`` axis.

The reference has nothing in this class (its models top out at ResNet-56);
this is the framework's expert-parallel axis so federated LM training can
scale parameters past one chip's HBM. Design follows the standard TPU MoE
recipe (Switch Transformer):

- top-1 router over E experts, with a fixed per-expert ``capacity`` so every
  shape is static (overflow tokens fall through on the residual path);
- experts live sharded over the ``ep`` axis (each device owns E/N experts'
  FFN weights) — the parameter memory scales with the mesh;
- dispatch/return are each ONE ``all_to_all`` over ICI: tokens are binned
  into per-expert capacity buffers with a one-hot matmul (static shapes, no
  scatter), exchanged, FFN'd by the owning device, and exchanged back.

Everything is a pure function of per-shard arrays under ``shard_map``;
composes with the other axes (('clients', 'ep') gives each federated
client an expert-parallel sub-mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def init_moe_params(key, n_experts: int, width: int, hidden: int):
    """Stacked expert FFN params: w_up [E, w, h], w_dn [E, h, w], and the
    router [w, E]."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_up = 1.0 / np.sqrt(width)
    scale_dn = 1.0 / np.sqrt(hidden)
    return {
        "router": jax.random.normal(k1, (width, n_experts)) * scale_up,
        "w_up": jax.random.normal(k2, (n_experts, width, hidden)) * scale_up,
        "w_dn": jax.random.normal(k3, (n_experts, hidden, width)) * scale_dn,
    }


def _aux_loss(frac, mean_prob):
    """Switch load-balancing loss from its two statistics: E * Σ_e
    (token fraction to e) * (mean router prob of e)."""
    return frac.shape[-1] * jnp.sum(frac * mean_prob)


def _route_top1(x, router, n_experts: int, capacity: int):
    """Top-1 routing with capacity: returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] prob-weighted, (frac, mean_prob) aux statistics)."""
    T = x.shape[0]
    logits = x @ router                               # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)               # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], -1)[:, 0]

    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue (cumsum trick)
    pos = jnp.cumsum(onehot, axis=0) * onehot         # [T, E], 1-based
    pos = jnp.sum(pos, axis=-1) - 1.0                 # [T], 0-based
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32)        # [T, C]
    dispatch = (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]
    combine = dispatch * gate[:, None, None]

    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return dispatch, combine, (frac, mean_prob)


def moe_ffn_local(x, params, capacity: int):
    """Single-device MoE FFN (the oracle for the sharded version).
    x: [T, w] -> [T, w]."""
    n_experts = params["router"].shape[-1]
    dispatch, combine, (frac, mean_prob) = _route_top1(
        x, params["router"], n_experts, capacity)
    aux = _aux_loss(frac, mean_prob)
    # [E, C, w] expert input buffers via one-hot contraction (no scatter)
    buffers = jnp.einsum("tec,tw->ecw", dispatch, x)
    h = jax.nn.gelu(jnp.einsum("ecw,ewh->ech", buffers, params["w_up"]))
    out_buf = jnp.einsum("ech,ehw->ecw", h, params["w_dn"])
    out = jnp.einsum("tec,ecw->tw", combine, out_buf)
    return out, aux


def expert_parallel_ffn(x, params, n_experts: int, capacity: int,
                        n_shards: int, axis: str = "ep"):
    """The collective MoE FFN body (call inside shard_map): x [T_local, w]
    tokens sharded on the batch axis, params sharded with router replicated
    and w_up/w_dn [E_local, ...] on the same ``axis``; one all_to_all each
    way."""
    dispatch, combine, (frac, mean_prob) = _route_top1(
        x, params["router"], n_experts, capacity)
    # globalize the statistics BEFORE the product so the sharded aux
    # equals the single-device aux exactly (the loss is nonlinear)
    aux = _aux_loss(jax.lax.pmean(frac, axis),
                    jax.lax.pmean(mean_prob, axis))
    buffers = jnp.einsum("tec,tw->ecw", dispatch, x)  # [E, C, w]
    # exchange: every shard sends each expert-group its buffers;
    # arrives as [E_local, N*C, w] after re-gluing the shard axis
    buffers = buffers.reshape(n_shards, n_experts // n_shards,
                              capacity, x.shape[-1])
    recv = jax.lax.all_to_all(buffers, axis, split_axis=0,
                              concat_axis=0, tiled=False)
    # recv: [N, E_local, C, w] — N source shards' queues per local expert
    e_loc = n_experts // n_shards
    recv = recv.transpose(1, 0, 2, 3).reshape(
        e_loc, n_shards * capacity, x.shape[-1])
    h = jax.nn.gelu(jnp.einsum("ecw,ewh->ech", recv, params["w_up"]))
    out_buf = jnp.einsum("ech,ehw->ecw", h, params["w_dn"])
    # return trip: split back per source shard and all_to_all home
    out_buf = out_buf.reshape(e_loc, n_shards, capacity,
                              x.shape[-1]).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(out_buf, axis, split_axis=0,
                              concat_axis=0, tiled=False)
    back = back.reshape(n_experts, capacity, x.shape[-1])
    out = jnp.einsum("tec,ecw->tw", combine, back)
    return out, aux


def make_expert_parallel_ffn(mesh: Mesh, n_experts: int, capacity: int,
                             axis: str = "ep"):
    """Build ``ffn(x_local, params_sharded) -> (out_local, aux)`` to run
    under shard_map (see :func:`expert_parallel_ffn`)."""
    n_shards = mesh.shape[axis]
    if n_experts % n_shards:
        raise ValueError(f"n_experts={n_experts} must divide over "
                         f"{axis}={n_shards}")

    def ffn(x, params):
        return expert_parallel_ffn(x, params, n_experts, capacity, n_shards,
                                   axis)

    return ffn


def expert_sharded_params(params, mesh: Mesh, axis: str = "ep"):
    """Place MoE params: experts split over ``ep``, router replicated."""
    from jax.sharding import NamedSharding

    specs = {"router": P(), "w_up": P(axis, None, None),
             "w_dn": P(axis, None, None)}
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def make_moe_step(mesh: Mesh, n_experts: int, capacity: int,
                  axis: str = "ep"):
    """Jitted shard_map wrapper: x sharded on tokens, params on experts."""
    ffn = make_expert_parallel_ffn(mesh, n_experts, capacity, axis)
    pspecs = {"router": P(), "w_up": P(axis, None, None),
              "w_dn": P(axis, None, None)}
    return jax.jit(jax.shard_map(
        ffn, mesh=mesh, in_specs=(P(axis), pspecs),
        out_specs=(P(axis), P())))
