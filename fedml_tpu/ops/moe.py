"""Routed gated experts without drops, in XLA, for the experts one chip holds.

A sparse block has ``num_experts`` gated (SwiGLU) experts, ``top_k`` of them
a token. Under expert parallelism a chip holds ``experts_held = (first,
count)`` of them: it routes every token over *all* the experts with the
published router, normalises the weights over all ``top_k`` chosen, and adds
the part of the result its own experts give. What the absent experts would
add is left out; there is no exchange and nothing stands in for one.

Selection: ``p = sigmoid(s W_g)``, the ``top_k`` largest of ``p + bias``
(the bias only selects), weights ``p_e / (sum of the chosen p + eps)``
(``eps`` 1e-6 unless the caller's model publishes another). The
router's product, the sigmoid and the top-k run in float32 at ``highest``
precision whatever the rest of the program uses, so that a selection differs
from a float32 reference's only where the layer's input already does.

No token is dropped at any load, and no shape depends on the routing. The
(token, choice) pairs that landed on a held expert are sorted by expert, so
each expert's rows are one segment, and the three products run segment by
segment in blocks of ``BLOCK`` rows: one loop over the blocks in use, each
block one expert's (gather its rows, ``[BLOCK, d] x [d, w]`` twice, the
gate, ``[BLOCK, w] x [w, d]``). The loop's trip count is the number of
blocks the routing needs - ``sum_e ceil(n_e / BLOCK)``, at most ``rows /
BLOCK + count`` for the static worst case of ``tokens x min(top_k, count)``
rows - so the work, and with it the device time, follows the load while
every buffer has its worst-case size. A block costs its expert's three
matrices read before it costs its rows: experts of 2048 x 768 at loads
from 0 to 900 rows ran 0.8 % faster at 512 than at 256 and 3.0 % faster
than at 128 (PR 39), so the block is one constant and no caller's choice.

A loop with a data-dependent trip count has no reverse-mode rule, so the
backward pass is written out (``jax.custom_vjp``): the same loop again, each
block recomputing its two hidden products, the weight gradients accumulated
expert by expert in place. It works under ``jax.checkpoint``, inside
``lax.scan`` and - at the cost of running every lane to the longest trip
count - under ``vmap``.

``jax.lax.ragged_dot`` would be the three products in three lines, but the
TPU compiler lowers it to a ``tpu_custom_call`` (compiled for the described
v5e, PR 33), and the benchmark books every custom call of the round program
as aggregation (PERF.md section 7 (7)).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

#: rows of one expert a block of the grouped products holds
BLOCK = 512


def route(s, router, bias, *, top_k: int, norm_topk: bool, scale: float,
          eps: float = 1e-6):
    """``(chosen experts [N, top_k] int32, their weights [N, top_k])`` for
    tokens ``s [N, d]``; float32 at ``highest``. ``bias`` ``[num_experts]``
    (or None) is added for the selection only, so it has no gradient."""
    logits = jnp.dot(s.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits)
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
    slot: jnp.ndarray      # [N, top_k] a pair's row in the layout (0 if none)
    held: jnp.ndarray      # [N, top_k] the pair landed on a held expert
    n_run: jnp.ndarray     # [] blocks the loops run


def _plan(chosen, first: int, count: int) -> _Plan:
    n_tokens, top_k = chosen.shape
    n_pairs = n_tokens * top_k
    block = min(BLOCK, n_pairs)
    rows_max = n_tokens * min(top_k, count)
    blocks_max = rows_max // block + count
    local = chosen.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)
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
    # a pair's row in the layout: its expert's first block, then its rank
    # in the segment
    rank = jnp.zeros((n_pairs,), jnp.int32).at[order].set(
        jnp.arange(n_pairs, dtype=jnp.int32))
    at = jnp.minimum(key, count - 1)
    slot = jnp.where(held, block_start[at] * block + rank - seg_start[at], 0)
    return _Plan(pairs // top_k, valid, pairs, expert,
                 slot.reshape(n_tokens, top_k),
                 held.reshape(n_tokens, top_k),
                 block_end[-1].astype(jnp.int32))


def _hidden(x, w1, w3):
    h1, h3 = x @ w1, x @ w3
    return h1, h3, jax.nn.silu(h1) * h3


def _forward(s, weights, w1, w3, w2, plan: _Plan):
    block, width = plan.tokens.shape[1], w2.shape[-1]

    def one(j, out):
        e = plan.expert[j]
        _, _, act = _hidden(s[plan.tokens[j]], w1[e], w3[e])
        y = jnp.where(plan.valid[j][:, None], act @ w2[e], 0)
        return jax.lax.dynamic_update_slice(out, y, (j * block, 0))

    out = jax.lax.fori_loop(
        0, plan.n_run, one,
        jnp.zeros((plan.tokens.shape[0] * block, width), s.dtype))
    weights = jnp.where(plan.held, weights, 0).astype(s.dtype)
    return jnp.einsum("nk,nkd->nd", weights, out[plan.slot])


@jax.custom_vjp
def _grouped(s, weights, w1, w3, w2, plan: _Plan):
    """``sum_{k held} weights[n, k] * Expert_{chosen[n, k]}(s[n])``."""
    return _forward(s, weights, w1, w3, w2, plan)


def _grouped_fwd(s, weights, w1, w3, w2, plan):
    return _forward(s, weights, w1, w3, w2, plan), (s, weights, w1, w3, w2,
                                                    plan)


def _grouped_bwd(res, dy):
    s, weights, w1, w3, w2, plan = res
    blocks, block = plan.tokens.shape
    flat_w = jnp.where(plan.held, weights, 0).reshape(-1).astype(s.dtype)

    def one(j, carry):
        dx, dw, g1, g3, g2 = carry
        e, ok = plan.expert[j], plan.valid[j][:, None]
        x = s[plan.tokens[j]]
        h1, h3, act = _hidden(x, w1[e], w3[e])
        dy_rows = jnp.where(ok, dy[plan.tokens[j]], 0)
        pair_w = flat_w[plan.pairs[j]][:, None]
        # the activation's gradient before the row's routing weight: its
        # dot with the activation is that weight's gradient
        g = dy_rows @ w2[e].T
        dact = g * pair_w
        sig = jax.nn.sigmoid(h1)
        dh1 = dact * h3 * sig * (1 + h1 * (1 - sig))
        dh3 = dact * h1 * sig
        dw = jax.lax.dynamic_update_slice(
            dw, jnp.sum(g * act, axis=-1), (j * block,))
        dx = jax.lax.dynamic_update_slice(
            dx, dh1 @ w1[e].T + dh3 @ w3[e].T, (j * block, 0))
        return (dx, dw, g1.at[e].add(x.T @ dh1), g3.at[e].add(x.T @ dh3),
                g2.at[e].add(act.T @ (dy_rows * pair_w)))

    dx, dw, g1, g3, g2 = jax.lax.fori_loop(
        0, plan.n_run, one,
        (jnp.zeros((blocks * block, s.shape[-1]), s.dtype),
         jnp.zeros((blocks * block,), s.dtype),
         jnp.zeros_like(w1), jnp.zeros_like(w3), jnp.zeros_like(w2)))
    held = plan.held.astype(s.dtype)
    ds = jnp.einsum("nk,nkd->nd", held, dx[plan.slot])
    return ds, (dw[plan.slot] * held).astype(weights.dtype), g1, g3, g2, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def routed_experts(s, router, bias, w1, w3, w2, *, top_k: int,
                   experts_held: Tuple[int, int], norm_topk: bool = True,
                   scale: float = 1.0, eps: float = 1e-6):
    """The held experts' part of a sparse block: ``(y, load)``.

    ``s [..., T, d]`` are the block's inputs, ``router [d, num_experts]``
    and ``bias [num_experts]`` (or None) the published router, ``w1``, ``w3``
    ``[count, d, w]`` and ``w2 [count, w, d]`` the experts ``first ..
    first + count - 1`` (``experts_held = (first, count)``). ``y`` has
    ``s``'s shape; ``load`` ``[..., count]`` counts, for every leading index,
    the (token, choice) pairs of its ``T`` tokens that landed on each held
    expert. All the tokens share one set of grouped products; ``eps`` is
    the normalisation's."""
    first, count = experts_held
    if w1.shape[0] != count:
        raise ValueError(f"{w1.shape[0]} experts given, experts_held says "
                         f"{count}")
    lead, (length, width) = s.shape[:-2], s.shape[-2:]
    flat = s.reshape(-1, width)
    chosen, weights = route(flat, router, bias, top_k=top_k,
                            norm_topk=norm_topk, scale=scale, eps=eps)
    chosen = jax.lax.stop_gradient(chosen)
    plan = jax.tree.map(jax.lax.stop_gradient, _plan(chosen, first, count))
    y = _grouped(flat, weights, w1, w3, w2, plan)
    local = chosen.reshape(lead + (length * top_k,)) - first
    load = jnp.sum(local[..., None] == jnp.arange(count), axis=-2,
                   dtype=jnp.int32)
    return y.reshape(s.shape), load
