"""Routed gated experts without drops, in XLA, for the experts one chip holds.

A sparse block has ``num_experts`` gated (SwiGLU) experts, ``top_k`` of them
a token. Under expert parallelism a chip holds ``experts_held = (first,
count)`` of them: it routes every token over *all* the experts with the
published router, normalises the weights over all ``top_k`` chosen, and adds
the part of the result its own experts give. What the absent experts would
add is left out; there is no exchange and nothing stands in for one.

Selection: ``p = sigmoid(s W_g)`` (``score="sigmoid"``, the default) or
``p = softmax(s W_g)`` over all the experts (``score="softmax"``), the
``top_k`` largest of ``p + bias`` (the bias only selects), weights ``p_e /
(sum of the chosen p + eps)`` (``eps`` 1e-6 unless the caller's model
publishes another). The router's product, the scores and the top-k run in
float32 at ``highest``
precision whatever the rest of the program uses, so that a selection differs
from a float32 reference's only where the layer's input already does.

No token is dropped at any load, and no shape depends on the routing. The
(token, choice) pairs that landed on a held expert are sorted by expert, so
each expert's rows are one segment, and the three products run segment by
segment in blocks of ``block`` rows: one loop over the blocks in use, each
block one expert's (gather its rows, ``[block, d] x [d, w]`` twice, the
gate, ``[block, w] x [w, d]``, each row times its pair's routing weight
added into the ``[tokens, d]`` output at its token). The loop's trip count
is the number of blocks the routing needs - ``sum_e ceil(n_e / block)``, at
most ``rows / block + count`` for the static worst case of ``tokens x
min(top_k, count)`` rows - so the work, and with it the device time,
follows the load.

The block follows the call's expected load per held expert, ``tokens x
top_k / num_experts`` (``num_experts`` the router's width): the smallest
power of two of at least 1.5 times it, no fewer than 64 rows and no more
than ``BLOCK`` or the call's pairs (``_block``). Every row-sized operation
of a block - the gathers, the products, the scatters - costs the block's
width whatever its load, while an expert's three matrices are read once a
block: so a block about as wide as an expert's load at a layer's peak (1.2
to 1.5 times the mean, read at a random initialisation) keeps one block an
expert and pads little. Qwen3-Next's 2,048 tokens x top-10 of 512 take
blocks of 64, kanana's top-6 of 128 blocks of 256 (at its uneven loads, one
held expert with 1,322 of 1,489 pairs, the layer ran 9 % faster than in
blocks of 512 on a TPU v5e), LFM2's 4,096 x top-4 of 32 the ceiling of 512.
It is a property of the call's static shapes, not a caller's choice.

The combine is that add, one row scatter a block: nothing of the static
worst case is materialised and gathered back a pair at a time. A block's
real rows come first, their tokens distinct (a token chooses an expert
once) and ascending (the stable sort keeps pair order); each padding row
goes past the end of the output on a lane of its own and is dropped, so the
indices are sorted and unique (spare rows sliced off read within 1.3 % on a
TPU v5e). The scatter costs a block's width, not its load: on a TPU v5e,
into ``f32[2048, 2048]``, 35 us for a block of 64 rows of 2048 floats, 43
for 128 and 147 for 512 (about 30 us a call, then the rows), and the
layer's forward and backward at Qwen3-Next's call 7.6 ms in blocks of 64
against 13.5 in blocks of 512.

A loop with a data-dependent trip count has no reverse-mode rule, so the
backward pass is written out (``jax.custom_vjp``): the same loop again, each
block recomputing its two hidden products and adding its rows' input
gradient at their tokens as the forward adds (the routing weights' at their
pairs), the weight gradients accumulated expert by expert in place. It works
under ``jax.checkpoint``, inside ``lax.scan`` and - at the cost of running
every lane to the longest trip count - under ``vmap``.

``jax.lax.ragged_dot`` would be the three products in three lines, but the
TPU compiler lowers it to a ``tpu_custom_call`` (compiled for the described
v5e, PR 33), and the benchmark books every custom call of the round program
as aggregation (PERF.md section 7 (7)).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

#: the most rows of one expert a block of the grouped products holds
BLOCK = 512
#: the fewest (``_block``)
MIN_BLOCK = 64


def route(s, router, bias, *, top_k: int, norm_topk: bool, scale: float,
          eps: float = 1e-6, score: str = "sigmoid"):
    """``(chosen experts [N, top_k] int32, their weights [N, top_k])`` for
    tokens ``s [N, d]``; float32 at ``highest``. ``bias`` ``[num_experts]``
    (or None) is added for the selection only, so it has no gradient.
    ``score`` is ``"sigmoid"`` or ``"softmax"`` (over all the experts)."""
    logits = jnp.dot(s.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        p = jax.nn.sigmoid(logits)
    elif score == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"no router score {score!r}")
    _, chosen = jax.lax.top_k(p if bias is None else p + bias, top_k)
    weights = jnp.take_along_axis(p, chosen, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen.astype(jnp.int32), weights * scale


class _Plan(NamedTuple):
    """Where every pair goes, as integer arrays of static shape: the layout
    is the pairs on held experts sorted by expert, each expert's segment
    padded to whole blocks."""

    tokens: jnp.ndarray    # [blocks, block] token a row of a block reads
    valid: jnp.ndarray     # [blocks, block] the row is a real pair
    pairs: jnp.ndarray     # [blocks, block] its pair (token * top_k + choice)
    expert: jnp.ndarray    # [blocks] the held expert a block belongs to
    n_run: jnp.ndarray     # [] blocks the loops run


def _block(n_tokens: int, top_k: int, num_experts: int) -> int:
    """Rows a block of the grouped products holds for a call of
    ``n_tokens`` tokens routed ``top_k`` of ``num_experts``: the smallest
    power of two of at least 1.5 x the expected pairs a held expert gets,
    between ``MIN_BLOCK`` and ``BLOCK``, and no more than the pairs."""
    n_pairs = n_tokens * top_k
    peak = -(-3 * n_pairs // (2 * num_experts))
    return min(BLOCK, n_pairs, max(MIN_BLOCK, 1 << (peak - 1).bit_length()))


def _plan(chosen, first: int, count: int, num_experts: int) -> _Plan:
    n_tokens, top_k = chosen.shape
    n_pairs = n_tokens * top_k
    block = _block(n_tokens, top_k, num_experts)
    rows_max = n_tokens * min(top_k, count)
    blocks_max = rows_max // block + count
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                   dtype=jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    seg_start = jnp.cumsum(load) - load
    n_blocks = -(-load // block)
    block_end = jnp.cumsum(n_blocks)
    block_start = block_end - n_blocks
    # block j: expert, its first sorted row, how many of its rows are real
    j = jnp.arange(blocks_max, dtype=jnp.int32)
    expert = jnp.minimum(jnp.searchsorted(block_end, j, side="right"),
                         count - 1).astype(jnp.int32)
    offset = (j - block_start[expert]) * block
    real = jnp.clip(load[expert] - offset, 0, block)
    real = jnp.where(j < block_end[-1], real, 0)
    lane = jnp.arange(block, dtype=jnp.int32)
    valid = lane[None, :] < real[:, None]
    rows = jnp.minimum(seg_start[expert][:, None] + offset[:, None]
                       + lane[None, :], n_pairs - 1)
    pairs = jnp.where(valid, order[rows], 0)
    return _Plan(pairs // top_k, valid, pairs, expert,
                 block_end[-1].astype(jnp.int32))


def _hidden(x, w1, w3):
    h1, h3 = x @ w1, x @ w3
    return h1, h3, jax.nn.silu(h1) * h3


def _add_rows(acc, at, valid, rows):
    """``acc[at] += rows`` for one block's rows, each padding row dropped
    past the end on a lane of its own: sorted, unique indices."""
    lane = jnp.arange(at.shape[0], dtype=at.dtype)
    return acc.at[jnp.where(valid, at, acc.shape[0] + lane)].add(
        rows, mode="drop", indices_are_sorted=True, unique_indices=True)


def _forward(s, weights, w1, w3, w2, plan: _Plan):
    flat_w = weights.reshape(-1).astype(s.dtype)

    def one(j, y):
        e, rows = plan.expert[j], plan.tokens[j]
        _, _, act = _hidden(s[rows], w1[e], w3[e])
        return _add_rows(y, rows, plan.valid[j],
                         (act @ w2[e]) * flat_w[plan.pairs[j]][:, None])

    return jax.lax.fori_loop(0, plan.n_run, one, jnp.zeros_like(s))


@jax.custom_vjp
def _grouped(s, weights, w1, w3, w2, plan: _Plan):
    """``sum_{k held} weights[n, k] * Expert_{chosen[n, k]}(s[n])``."""
    return _forward(s, weights, w1, w3, w2, plan)


def _grouped_fwd(s, weights, w1, w3, w2, plan):
    return _forward(s, weights, w1, w3, w2, plan), (s, weights, w1, w3, w2,
                                                    plan)


def _grouped_bwd(res, dy):
    s, weights, w1, w3, w2, plan = res
    flat_w = weights.reshape(-1).astype(s.dtype)

    def one(j, carry):
        ds, dw, g1, g3, g2 = carry
        e, rows, ok = plan.expert[j], plan.tokens[j], plan.valid[j]
        x = s[rows]
        h1, h3, act = _hidden(x, w1[e], w3[e])
        dy_rows = jnp.where(ok[:, None], dy[rows], 0)
        pair_w = flat_w[plan.pairs[j]][:, None]
        # the activation's gradient before the row's routing weight: its
        # dot with the activation is that weight's gradient
        g = dy_rows @ w2[e].T
        dact = g * pair_w
        sig = jax.nn.sigmoid(h1)
        dh1 = dact * h3 * sig * (1 + h1 * (1 - sig))
        dh3 = dact * h1 * sig
        ds = _add_rows(ds, rows, ok, dh1 @ w1[e].T + dh3 @ w3[e].T)
        dw = _add_rows(dw, plan.pairs[j], ok, jnp.sum(g * act, axis=-1))
        return (ds, dw, g1.at[e].add(x.T @ dh1), g3.at[e].add(x.T @ dh3),
                g2.at[e].add(act.T @ (dy_rows * pair_w)))

    ds, dw, g1, g3, g2 = jax.lax.fori_loop(
        0, plan.n_run, one,
        (jnp.zeros_like(s), jnp.zeros_like(flat_w), jnp.zeros_like(w1),
         jnp.zeros_like(w3), jnp.zeros_like(w2)))
    dw = dw.reshape(weights.shape).astype(weights.dtype)
    return ds, dw, g1, g3, g2, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def held_slice(experts_held: Tuple[int, int],
               num_experts: int) -> Tuple[int, int]:
    """``experts_held`` as ``(first, count)``, refused unless it is a slice
    of the router's ``num_experts`` experts."""
    first, count = experts_held
    if not (0 <= first and count >= 1 and first + count <= num_experts):
        raise ValueError(f"experts_held {experts_held} is no slice of "
                         f"{num_experts} experts")
    return first, count


def routed_experts(s, router, bias, w1, w3, w2, *, top_k: int,
                   experts_held: Tuple[int, int], norm_topk: bool = True,
                   scale: float = 1.0, eps: float = 1e-6,
                   score: str = "sigmoid"):
    """The held experts' part of a sparse block: ``(y, load, rows)``.

    ``s [..., T, d]`` are the block's inputs, ``router [d, num_experts]``
    and ``bias [num_experts]`` (or None) the published router, ``w1``, ``w3``
    ``[count, d, w]`` and ``w2 [count, w, d]`` the experts ``first ..
    first + count - 1`` (``experts_held = (first, count)``). ``y`` has
    ``s``'s shape; ``load`` ``[..., count]`` counts, for every leading index,
    the (token, choice) pairs of its ``T`` tokens that landed on each held
    expert. All the tokens share one set of grouped products; ``rows``
    (int32) is the rows their block loops ran, padding included: the blocks
    in use times the block's rows. ``eps`` is the normalisation's,
    ``score`` the router's (``route``)."""
    first, count = experts_held
    if w1.shape[0] != count:
        raise ValueError(f"{w1.shape[0]} experts given, experts_held says "
                         f"{count}")
    lead, (length, width) = s.shape[:-2], s.shape[-2:]
    flat = s.reshape(-1, width)
    chosen, weights = route(flat, router, bias, top_k=top_k,
                            norm_topk=norm_topk, scale=scale, eps=eps,
                            score=score)
    chosen = jax.lax.stop_gradient(chosen)
    num_experts = router.shape[-1]
    plan = jax.tree.map(jax.lax.stop_gradient,
                        _plan(chosen, first, count, num_experts))
    y = _grouped(flat, weights, w1, w3, w2, plan)
    local = chosen.reshape(lead + (length * top_k,)) - first
    load = jnp.sum(local[..., None] == jnp.arange(count), axis=-2,
                   dtype=jnp.int32)
    rows = plan.n_run * plan.tokens.shape[-1]
    return y.reshape(s.shape), load, rows
