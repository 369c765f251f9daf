"""The state-space recurrence of a Mamba-2 mixer in its chunked dual form
(SSD, arXiv:2405.21060), for training, in XLA.

One sequence, ``H`` heads of ``P`` channels, ``G`` groups that share ``B``
and ``C`` (``H / G`` heads a group), a state of ``P x N`` a head, and **one
scalar decay a head and step**::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * xs_t outer B_t          [H, P, N]
    y_t = S_t C_t                                                  [H, P]

A scalar decay is what lets the recurrence be computed as matrix products
(``ops/selective_scan.py``'s Mamba-1 decays every channel and state element
apart, and has no such form). The sequence is cut into chunks of ``chunk``
positions. With ``alpha_t = dt_t * A`` and ``Lambda`` its running sum
inside a chunk:

* inside the chunk ``Y = ((C B') * L)(dt * xs)`` with ``L[i, j] =
  exp(Lambda_i - Lambda_j)`` for ``i >= j``, else 0 - a masked,
  decay-weighted ``C B'`` like a block of linear attention. The mask is
  applied *before* the exponential: the upper triangle's exponents are
  positive and would overflow. ``C B'`` is computed once a group, not once
  a head;
* the chunk's own state ``sum_j exp(Lambda_end - Lambda_j) (dt_j xs_j)
  outer B_j``, and the state carried on ``exp(Lambda_end) S_in +`` that;
* the incoming state's part of the chunk's outputs, ``exp(Lambda_i) S_in
  C_i``.

The chunks run one after another (``lax.scan``, the carry is the state) and
each is rematerialised (``jax.checkpoint``): the backward pass holds one
chunk's ``[H, chunk, chunk]`` decay matrices at a time and recomputes them
from the chunk's inputs and its incoming state. The scan is unrolled - a
row has a handful of chunks, a static count - and a chunk's inputs and
outputs are stacked as ``[chunks, chunk, H P]``: inside a ``while`` the TPU
compiler lays a stack of eight ``[chunk, H, P]`` blocks out with the chunk
index as the sublane dimension, so that every chunk's write rewrites the
whole stack (a quarter of the round's device time; PERF.md, "PR 35").
Decays, running sums and exponentials are float32; the four products run at
the backend's default precision like every other layer's. XLA only: a
Pallas kernel that keeps the state in VMEM is the next step, and this is
the program it will be measured against (PERF.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _chunk(a, state, inputs):
    """One chunk from the incoming state ``state [H, P, N]``: ``(state
    after the chunk, y [Q, H P])``. ``inputs``: ``xs [Q, H P]``, ``dt [Q,
    H]``, ``b`` and ``c [Q, G, N]``; ``a [H]``."""
    xs, dt, b, c = inputs
    length, heads = dt.shape
    groups = b.shape[1]
    xs = xs.reshape((length, heads, -1))
    # log-decay from the chunk's start to each position, inclusive
    decay = jnp.cumsum((dt * a).astype(jnp.float32), axis=0)  # [Q, H]
    pos = jnp.arange(length)
    seen = (pos[:, None] >= pos[None, :])[None]  # [1, Q, Q]
    between = jnp.exp(jnp.where(
        seen, decay.T[:, :, None] - decay.T[:, None, :], -jnp.inf))
    cb = jnp.einsum("qgn,kgn->gqk", c, b)  # once a group
    scores = (between.reshape((groups, heads // groups, length, length))
              * cb[:, None].astype(jnp.float32)).reshape(between.shape)
    u = dt[:, :, None] * xs  # the step's input, [Q, H, P]
    y = jnp.einsum("hqk,khp->qhp", scores.astype(u.dtype), u)
    # what the incoming state adds to each position
    per_group = state.reshape((groups, heads // groups) + state.shape[1:])
    carried = jnp.einsum("qgn,gjpn->qgjp", c, per_group).reshape(u.shape)
    y = y + jnp.exp(decay)[:, :, None].astype(u.dtype) * carried
    # the chunk's own state, and the state handed on
    to_end = jnp.exp(decay[-1][None, :] - decay).astype(u.dtype)  # [Q, H]
    weighted = (to_end[:, :, None] * u).reshape(
        (length, groups, heads // groups) + u.shape[2:])
    own = jnp.einsum("kgjp,kgn->gjpn", weighted, b).reshape(state.shape)
    state = jnp.exp(decay[-1])[:, None, None].astype(state.dtype) * state \
        + own
    return state, y.reshape((length, -1))


@jax.named_scope("fedml.ssd")
def ssd_scan(xs, dt, a, b, c, *, chunk: int = 256):
    """``y [T, H, P]`` of the recurrence above for one sequence from a zero
    state. ``xs [T, H, P]``; ``dt [T, H]`` (positive step sizes); ``a [H]``
    (negative); ``b``, ``c``: ``[T, G, N]`` with ``H`` a multiple of ``G``.
    A length that is no multiple of ``chunk`` is padded with steps that
    leave the state as it is (``dt = 0``)."""
    length, heads, dim = xs.shape
    if heads % b.shape[1]:
        raise ValueError(f"{heads} heads do not divide into {b.shape[1]} "
                         "groups")
    pad = (-length) % chunk

    def chunks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, chunk) + x.shape[1:])

    # ``_chunk`` is looked up as the scan is traced
    body = jax.checkpoint(lambda state, x: _chunk(a, state, x))
    _, y = jax.lax.scan(
        body, jnp.zeros((heads, dim, b.shape[2]), xs.dtype),
        tuple(chunks(x) for x in (xs.reshape((length, -1)), dt, b, c)),
        unroll=True)
    return y.reshape((-1, heads, dim))[:length]


def ssd_scan_reference(xs, dt, a, b, c):
    """The recurrence step by step: the oracle of the tests."""
    heads, groups = xs.shape[1], b.shape[1]
    b, c = (jnp.repeat(x, heads // groups, axis=1) for x in (b, c))

    def step(state, x):
        xt, dt_t, bt, ct = x  # [H, P], [H], [H, N], [H, N]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros(xs.shape[1:] + (b.shape[2],), xs.dtype),
        (xs, dt, b, c))
    return y
