"""A selective state-space scan that trains at long sequence lengths (XLA).

The recurrence of a Mamba-1 mixer (arXiv:2312.00752, eq. 2 with the
zero-order-hold discretisation of its section 3.3), for one sequence::

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) outer B_t     [Di, N]
    y_t = h_t @ C_t                                                  [Di]

Step by step over T positions the state ``[Di, N]`` is too small a unit of
work for the chip (a loop iteration costs more than its arithmetic) and the
backward pass keeps every ``h_t``: ``T * Di * N`` floats a layer. Here the
sequence is cut twice:

* into **chunks** of ``chunk`` positions that run one after another, the
  state carried from chunk to chunk. Each chunk is rematerialised
  (``jax.checkpoint``), so the backward pass holds one chunk's states at a
  time and recomputes them from the chunk's inputs and its incoming state;
* inside a chunk into ``lanes`` runs of ``chunk // lanes`` positions that
  advance *side by side* from a zero state (one loop of ``chunk // lanes``
  iterations over ``[lanes, N, Di]``), after which the lanes are stitched:
  the recurrence is linear in ``h``, so what a lane's incoming state adds
  to position ``t`` is ``exp(A * sum of delta over the lane up to t)``
  times that state.

The state is laid out ``[N, Di]`` (the wide axis last) so that a TPU tiles
it without padding. Everything is elementwise float32 arithmetic and
reductions: no matrix unit, so no bf16 pass, on any backend. A Pallas
kernel that keeps the state in VMEM is the obvious next step; this is the
program it will be measured against (PERF.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _lanes_first(a, lanes: int):
    """``[chunk, ...]`` -> ``[chunk // lanes, lanes, ...]``: position ``s``
    of every lane side by side."""
    return jnp.swapaxes(a.reshape((lanes, a.shape[0] // lanes) + a.shape[1:]),
                        0, 1)


def _chunk(a_t, lanes: int, h0, inputs):
    """One chunk from the incoming state ``h0 [N, Di]``: ``(state after the
    chunk, y [chunk, Di])``. ``a_t`` is ``A`` transposed, ``[N, Di]``."""
    delta, u, b, c = (_lanes_first(a, lanes) for a in inputs)

    def step(h, x):
        dt, ut, bt, ct = x  # [lanes, Di] x2, [lanes, N] x2
        h = (jnp.exp(dt[:, None, :] * a_t) * h
             + (dt * ut)[:, None, :] * bt[:, :, None])
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    zero = jnp.zeros((lanes,) + a_t.shape, delta.dtype)
    h_end, y = jax.lax.scan(jax.checkpoint(step), zero, (delta, u, b, c))

    # log-decay from a lane's start to each of its positions, inclusive
    decay = jnp.cumsum(delta, axis=0)  # [sub, lanes, Di]

    def stitch(h, x):
        total, end = x  # the lane's whole decay [Di], its own end state
        return jnp.exp(total[None, :] * a_t) * h + end, h

    h_out, h_in = jax.lax.scan(stitch, h0, (decay[-1], h_end))
    carried = jnp.sum(jnp.exp(decay[:, :, None, :] * a_t) * h_in[None]
                      * c[:, :, :, None], axis=2)
    y = jnp.swapaxes(y + carried, 0, 1)
    return h_out, y.reshape((-1, y.shape[-1]))


@jax.named_scope("fedml.ssm_scan")
def selective_scan(delta, u, b, c, a, *, chunk: int = 512, lanes: int = 16):
    """``y [T, Di]`` of the recurrence above for one sequence from a zero
    state. ``delta``, ``u``: ``[T, Di]``; ``b``, ``c``: ``[T, N]``; ``a``:
    ``[Di, N]`` (negative). ``chunk`` must be a multiple of ``lanes``; a
    length that is no multiple of ``chunk`` is padded with steps that leave
    the state as it is (``delta = 0``)."""
    if chunk % lanes:
        raise ValueError(f"chunk {chunk} is not a multiple of lanes {lanes}")
    length = delta.shape[0]
    pad = (-length) % chunk

    def chunks(x):
        x = jnp.pad(x, ((0, pad), (0, 0)))
        return x.reshape((-1, chunk) + x.shape[1:])

    a_t = a.T
    body = jax.checkpoint(lambda h, x: _chunk(a_t, lanes, h, x))
    _, y = jax.lax.scan(body, jnp.zeros(a_t.shape, delta.dtype),
                        tuple(chunks(x) for x in (delta, u, b, c)))
    return y.reshape((-1, y.shape[-1]))[:length]


def selective_scan_reference(delta, u, b, c, a):
    """The recurrence step by step: the oracle of the tests."""

    def step(h, x):
        dt, ut, bt, ct = x
        h = (jnp.exp(dt[:, None] * a) * h
             + (dt * ut)[:, None] * bt[None, :])
        return h, jnp.sum(h * ct[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, delta.dtype),
                        (delta, u, b, c))
    return y
