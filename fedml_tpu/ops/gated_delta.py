"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
WY form, for training, in XLA.

One sequence, ``H`` value heads of ``dv`` channels reading ``Hk`` key heads
of ``dk`` (value head ``j`` reads key head ``j // (H / Hk)``), a state ``S``
of ``dk x dv`` a value head, one scalar log-decay ``g <= 0`` and one write
strength ``beta`` in (0, 1) a value head and step::

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (beta_t (v_t - S_t' k_t))'                     [dk, dv]
    o_t = S_t' q_t                                                 [dv]

Unlike the decayed sum of ``ops/ssd.py`` each write first takes away what the
state already predicts for its key: the state is *corrected*. The sequence
is cut into chunks of ``chunk`` positions. With ``G`` the running sum of
``g`` inside a chunk (inclusive) and ``D_ij = exp(G_i - G_j)`` for ``i >=
j`` (masked *before* the exponential: the upper triangle's exponents are
positive and would overflow)::

    T     = (I + strictly-lower(diag(beta) K K' * D))^-1
    U     = T diag(beta) V ;  W = T diag(beta exp(G)) K
    V'    = U - W S_in
    O     = (Q * exp(G)) S_in + lower-incl-diag(Q K' * D) V'
    S_out = exp(G_C) S_in + (K * exp(G_C - G))' V'

``K K'`` and ``Q K'`` are computed once a key head. ``T`` is the finite
product ``(I + N)(I + N^2)(I + N^4)...`` of the nilpotent ``N = -L``: a
handful of batched ``[C, C]`` products, where a triangular solve is a loop of
``C`` dependent steps a chunk.

The chunks run as ``ops/ssd.py``'s do: an unrolled ``lax.scan`` whose carry
is the state, each chunk rematerialised, the inputs and outputs stacked as
``[chunks, chunk, ...]``. Decays and exponentials are float32; the products
run at the backend's default precision like every other layer's. A length
that is no multiple of ``chunk`` is padded with ``beta = 0``, ``g = 0``,
``k = 0``, which leave the state as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk holds: the published kernels' chunk
CHUNK = 64


def _unit_lower_inverse(lower):
    """``(I + lower)^-1`` of a strictly lower-triangular ``lower [..., C,
    C]``: ``sum_n N^n`` for ``N = -lower``, as the product of ``I + N^(2^i)``
    over the powers below ``C`` (``N^C`` is zero)."""
    size = lower.shape[-1]
    power = -lower
    inverse = jnp.eye(size, dtype=lower.dtype) + power
    reach = 2
    while reach < size:
        power = power @ power
        inverse = inverse + inverse @ power
        reach *= 2
    return inverse


def _chunk(state, inputs):
    """One chunk from the incoming state ``state [Hk, r, dk, dv]``: ``(state
    after the chunk, o [C, H dv])``. ``inputs``: ``q``, ``k [C, Hk dk]``,
    ``v [C, H dv]``, ``g``, ``beta [C, H]``."""
    q, k, v, g, beta = inputs
    length, heads = g.shape
    key_heads, group, dk, dv = state.shape
    q, k = (x.reshape(length, key_heads, dk) for x in (q, k))
    v = jnp.moveaxis(v.reshape(length, key_heads, group, dv), 0, 2)
    # log-decay from the chunk's start to each position, inclusive
    decay = jnp.cumsum(g.astype(jnp.float32), axis=0).T.reshape(
        key_heads, group, length)                          # [Hk, r, C]
    beta = beta.T.reshape(key_heads, group, length)
    pos = jnp.arange(length)
    seen = pos[:, None] >= pos[None, :]
    between = jnp.exp(jnp.where(
        seen, decay[..., :, None] - decay[..., None, :], -jnp.inf))
    kk = jnp.einsum("ihd,jhd->hij", k, k)[:, None]        # once a key head
    qk = jnp.einsum("ihd,jhd->hij", q, k)[:, None]
    lower = jnp.where(pos[:, None] > pos[None, :],
                      beta[..., :, None] * kk * between, 0.0)
    inverse = _unit_lower_inverse(lower)                  # [Hk, r, C, C]
    k_heads = jnp.moveaxis(k, 0, 1)[:, None]              # [Hk, 1, C, dk]
    u = inverse @ (beta[..., None] * v)
    w = inverse @ ((beta * jnp.exp(decay))[..., None] * k_heads)
    fresh = u - w @ state                                 # V'
    q_heads = jnp.moveaxis(q, 0, 1)[:, None]
    out = ((jnp.exp(decay)[..., None] * q_heads) @ state
           + (qk * between) @ fresh)                      # [Hk, r, C, dv]
    to_end = jnp.exp(decay[..., -1:] - decay)[..., None]  # [Hk, r, C, 1]
    state = (jnp.exp(decay[..., -1])[..., None, None] * state
             + jnp.swapaxes(to_end * k_heads, -1, -2) @ fresh)
    return state, jnp.moveaxis(out, 2, 0).reshape(length, heads * dv)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """``o [T, H, dv]`` of the recurrence above for one sequence from a
    zero state. ``q``, ``k [T, Hk, dk]`` (normalised and scaled as the
    caller's model does); ``v [T, H, dv]`` with ``H`` a multiple of ``Hk``;
    ``g [T, H]`` (log-decays, ``<= 0``); ``beta [T, H]``."""
    length, heads, dv = v.shape
    key_heads, dk = k.shape[1:]
    if heads % key_heads:
        raise ValueError(f"{heads} value heads do not divide into "
                         f"{key_heads} key heads")
    pad = (-length) % chunk

    def chunks(x):
        x = jnp.pad(x.reshape((length, -1)), ((0, pad), (0, 0)))
        return x.reshape((-1, chunk, x.shape[-1]))

    state = jnp.zeros((key_heads, heads // key_heads, dk, dv), v.dtype)
    _, out = jax.lax.scan(jax.checkpoint(_chunk), state,
                          tuple(chunks(x) for x in (q, k, v, g, beta)),
                          unroll=True)
    return out.reshape((-1, heads, dv))[:length]


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence step by step: the oracle of the tests."""
    group = v.shape[1] // k.shape[1]
    q, k = (jnp.repeat(x, group, axis=1) for x in (q, k))

    def step(state, x):
        qt, kt, vt, gt, bt = x  # [H, dk], [H, dk], [H, dv], [H], [H]
        state = jnp.exp(gt)[:, None, None] * state
        said = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - said))[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, out = jax.lax.scan(
        step, jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), v.dtype),
        (q, k, v, g, beta))
    return out
