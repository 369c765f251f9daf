"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference's longest sequences are LSTM char/word LMs (nlp/rnn.py:5,41);
it has no sequence-axis machinery (SURVEY §5.7). This module is the
framework's long-context subsystem so the mesh design carries a real
``seq`` axis rather than merely not precluding one: transformer-class
models (e.g. on-device LMs for federated next-word prediction at long
context) shard the sequence across devices and attend globally without any
device ever holding the full [S, S] score matrix or the full K/V.

Two standard schemes, both as ``shard_map``-ready collectives:

* :func:`ring_attention` — K/V blocks rotate around the ``seq`` axis ring
  via ``ppermute`` while each device keeps its Q shard; softmax is
  accumulated online (flash-attention style running max/denominator), so
  memory is O(S_local) and the N-1 rotations overlap compute with ICI
  transfer. Causality is enforced with global position ids, so the result
  is exactly ``softmax(QK^T/sqrt(d) + mask) V`` for the full sequence.

* :func:`ulysses_attention` — ``all_to_all`` re-shards [seq-shard, all
  heads] -> [full seq, head-shard], runs ordinary local attention per head
  group, and transposes back. One collective each way; preferable when
  heads >= devices and ICI all-to-all bandwidth is plentiful.

Both are pure functions of per-shard arrays and compose with the
``clients`` axis: :func:`make_seq_federated_round` runs the FULL FedAvg
round on a ('clients', 'seq') mesh — every federated client trains over
ring-attended long sequences on its own sub-mesh, with per-step gradient
sync over ``seq`` — and matches the single-device round exactly
(tests/test_seq_federated.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30  # finite: keeps fully-masked rows NaN-free in the online max


def _block_attend(q, k, v, qpos, kpos, m, denom, acc, causal: bool,
                  scale: float):
    """One online-softmax update with a visiting K/V block.

    q: [B, Sq, H, D]   k,v: [B, Sk, H, D]   qpos: [Sq]   kpos: [Sk]
    m, denom: [B, H, Sq]   acc: [B, Sq, H, D]
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]           # [Sq, Sk]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))              # [B, H, Sq]
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                   # [B, H, Sq, Sk]
    denom_new = denom * corr + p.sum(axis=-1)
    acc_new = (acc * corr.transpose(0, 2, 1)[..., None]
               + jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return m_new, denom_new, acc_new


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "seq", causal: bool = False) -> jax.Array:
    """Exact global attention over a sequence sharded on ``axis_name``.

    Call inside ``shard_map`` (or ``pmap``) with q/k/v = this device's
    sequence shard, laid out [batch, seq_local, heads, head_dim]. Returns
    the attention output for the local Q shard. K/V travel the ring once
    (N-1 ``ppermute`` hops); each hop's matmul overlaps the next transfer.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype)).astype(jnp.float32)

    qpos = idx * s_local + jnp.arange(s_local)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # accumulators in f32 regardless of input dtype (bf16-safe softmax),
    # derived from q so they inherit its full device-varying set (seq axis
    # plus any outer axes like 'clients') — otherwise the fori_loop carry
    # type changes after the first iteration and tracing fails
    qf = q.astype(jnp.float32)
    zeros_bhs = qf[..., 0].transpose(0, 2, 1) * 0.0     # [B, H, Sq]
    m = zeros_bhs + _NEG_INF
    denom = zeros_bhs
    acc = qf * 0.0

    def body(t, carry):
        k_blk, v_blk, m, denom, acc = carry
        src = (idx - t) % n                 # whose K/V we hold at step t
        kpos = src * s_local + jnp.arange(s_local)

        def attend(ops):
            m, denom, acc = ops
            return _block_attend(qf, k_blk.astype(jnp.float32),
                                 v_blk.astype(jnp.float32),
                                 qpos, kpos, m, denom, acc, causal, scale)

        if causal:
            # skip blocks entirely in this Q shard's future — at N devices
            # that is ~half the ring's attention FLOPs
            visible = kpos[0] <= qpos[-1]
            m, denom, acc = jax.lax.cond(visible, attend,
                                         lambda ops: ops, (m, denom, acc))
        else:
            m, denom, acc = attend((m, denom, acc))
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, denom, acc

    k_blk, v_blk, m, denom, acc = jax.lax.fori_loop(
        0, n, body, (k, v, m, denom, acc))
    out = acc / jnp.maximum(denom, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "seq",
                      causal: bool = False, local_attn=None) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme).

    Input shards are [B, S_local, H, D] with H divisible by the axis size.
    ``all_to_all`` turns them into [B, S_full, H/N, D] (full sequence, a
    slice of heads), local attention runs exactly, and the inverse
    all-to-all restores the sequence sharding.

    ``local_attn``: the per-head-group attention over the re-sharded
    [B, S_full, H/N, D] arrays — any (q, k, v, causal=...) callable.
    None = the plain XLA oracle; pass the :mod:`fedml_tpu.ops.autotune`
    selection (see :func:`make_sequence_parallel_attention`) so the local
    step runs whichever of Pallas/XLA actually wins at this shape.
    """
    n = jax.lax.psum(1, axis_name)  # static under shard_map
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"{axis_name!r} axis size ({n}); use ring_attention otherwise")

    def seq2head(x):  # [B, S_loc, H, D] -> [B, S_full, H/N, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):  # inverse
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    out = (local_attn or reference_attention)(
        seq2head(q), seq2head(k), seq2head(v), causal=causal)
    return head2seq(out)


def reference_attention(q, k, v, causal: bool = False) -> jax.Array:
    """Unsharded oracle: plain softmax attention, [B, S, H, D]."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.asarray(d,
                                                                 jnp.float32))
    if causal:
        pos = jnp.arange(q.shape[1])
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_sequence_parallel_attention(
        mesh: Mesh, scheme: str = "ring", causal: bool = False,
        axis_name: str = "seq", local_attn="auto"):
    """Wrap the chosen scheme in shard_map over ``mesh``'s seq axis.

    Returns ``fn(q, k, v) -> out`` taking GLOBAL [B, S, H, D] arrays;
    sharding to [B, S/N, H, D] shards and back is handled by shard_map.

    ``local_attn`` is the attention that runs where the scheme attends
    locally: ulysses' per-head-group step, and the whole computation when
    the ``seq`` axis has size 1 (a degenerate ring is pure
    ppermute/fori_loop overhead around plain attention — the single-chip
    bench case — so it is short-circuited to the local attention).
    ``"auto"`` = the :mod:`fedml_tpu.ops.autotune` per-shape winner
    (tuned Pallas blocks vs XLA reference, decision cached on disk);
    None = the plain XLA oracle; or any (q, k, v, causal=...) callable.
    """
    if scheme not in ("ring", "ulysses"):
        raise ValueError(f"scheme must be ring|ulysses, got {scheme!r}")
    if local_attn == "auto":
        from fedml_tpu.ops.autotune import make_autotuned_attention
        local_attn = make_autotuned_attention()
    if int(mesh.shape[axis_name]) == 1:
        fn = functools.partial(local_attn or reference_attention,
                               causal=causal)
    elif scheme == "ring":
        fn = functools.partial(ring_attention, axis_name=axis_name,
                               causal=causal)
    else:
        fn = functools.partial(ulysses_attention, axis_name=axis_name,
                               causal=causal, local_attn=local_attn)
    spec = P(None, axis_name, None, None)

    def sharded(q, k, v):
        return fn(q, k, v)

    return jax.jit(jax.shard_map(sharded, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec))


class _SeqShardedLM:
    """Adapter giving the trainer's ``module.apply(variables, x, train=...)``
    contract for a TransformerLM whose sequence axis is sharded: positions
    are offset by this shard's location on the ``seq`` axis."""

    def __init__(self, lm, seq_axis: str = "seq"):
        self._lm = lm
        self._seq_axis = seq_axis

    def apply(self, variables, x, train: bool = False, **kw):
        offset = jax.lax.axis_index(self._seq_axis) * x.shape[-1]
        return self._lm.apply(variables, x, train=train, pos_offset=offset,
                              **kw)

    def init(self, *a, **kw):
        return self._lm.init(*a, **kw)


def make_seq_federated_round(lm, cfg, mesh: Mesh,
                             clients_axis: str = "clients",
                             seq_axis: str = "seq", task: str = "nwp"):
    """FedAvg round over a ('clients', 'seq') mesh: sampled clients are
    data-parallel on one axis while every client's long sequences are
    sharded over the other — federated long-context training. The LM must
    take an ``attn_fn`` spanning the seq axis (ring/ulysses above); the
    local trainer syncs loss terms and gradients over ``seq`` each step
    (trainer.functional.make_local_train ``grad_sync_axes``), so all of a
    client's shards take the identical optimizer step and the round equals
    its single-device counterpart exactly.

    Inputs: x, y [P, n_pad, S] (token ids, S = GLOBAL length), mask
    [P, n_pad], keys [P], weights [P]. Returns (replicated new variables,
    psum'd stats).

    Warm-up note (an earlier bench row read 577.8 tokens/s for this
    reason): the returned jit caches on input *sharding*. A first call made with
    the raw ``lm.init`` variables (uncommitted) compiles one program; its
    output comes back mesh-committed (out_specs P()), so the next call is
    a cache MISS and recompiles — ~seconds on CPU, more on a chip. That
    second compile was inside the bench's timed
    region (its TP twin pre-places params via ``shard_params``, so only
    this round hit it), mis-measuring the round by orders of magnitude.
    Warm BOTH signatures before timing: ``v, _ = fn(variables, *args);
    v, _ = fn(v, *args)`` — steady state is the second signature.
    """
    from fedml_tpu.parallel.spmd import (_pvary, _weighted_psum_mean)
    from fedml_tpu.trainer.functional import make_local_train

    if getattr(cfg, "lr_decay_round", 1.0) != 1.0:
        raise NotImplementedError(
            "lr_decay_round is not threaded through the sequence-parallel "
            "round; use the flat clients-axis drivers for the schedule")
    module = _SeqShardedLM(lm, seq_axis)
    local_train = make_local_train(module, task, cfg,
                                   grad_sync_axes=(seq_axis,))

    def body(variables, x, y, mask, keys, weights):
        variables = _pvary(variables, (clients_axis, seq_axis))
        weights = _pvary(weights, (seq_axis,))  # psum'able over both axes
        stacked, stats = jax.vmap(
            local_train, in_axes=(None, 0, 0, 0, 0))(variables, x, y, mask,
                                                     keys)
        # every seq shard holds the identical client model (grads psum'd per
        # step), so the weighted mean over BOTH axes equals the mean over
        # clients — and clears the device-varying type for the replicated
        # output (psum over seq divides out: n_seq cancels top and bottom)
        new_vars = _weighted_psum_mean(stacked, weights,
                                       (clients_axis, seq_axis))
        # stats were already psum'd over seq inside the loss; only the
        # client axis remains
        totals = jax.tree.map(
            lambda s: jax.lax.psum(jnp.sum(s, axis=0), clients_axis), stats)
        return new_vars, totals

    seq_data = P(clients_axis, None, seq_axis)
    flat = P(clients_axis)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), seq_data, seq_data, flat, flat, flat),
        out_specs=(P(), P()),
    ))
