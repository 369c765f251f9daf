"""Magnitude top-k sparsification for the cross-silo wire (DGC-style).

Deep Gradient Compression (Lin et al., 2018) ships only the largest-
magnitude entries of the model delta; QSGD-style stochastic int8
quantization (ops/quantize.py) compresses the survivors further. Top-k is
a BIASED compressor, so the un-sent remainder must be fed back: the caller
accumulates the returned ``residual`` into the next round's delta before
compressing again (EF-SGD, Karimireddy et al., 2019) — with that loop the
compressed federation still converges to the uncompressed fixed point.

All kernels operate on the same flat f32 layout ``quantize_tree`` uses
(leaves concatenated in treedef order), so sparsify -> quantize composes
without a second flatten. ``k`` and ``d`` are static: one lowering per
(model size, keep fraction), shared by every round of a run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ops.quantize import dequantize_int8, quantize_int8
from fedml_tpu.utils import on_tpu


def k_for(d: int, frac: float) -> int:
    """Survivor count for a ``d``-entry delta at keep-fraction ``frac``
    (ceil, clamped to [1, d] so degenerate tiny models still send)."""
    if not 0.0 < frac <= 1.0:
        raise ValueError(f"topk fraction {frac} outside (0, 1]")
    return max(1, min(d, math.ceil(d * frac)))


@functools.partial(jax.jit, static_argnames=("k",))
def topk_sparsify(x: jax.Array, k: int):
    """Keep the ``k`` largest-|x| entries of a flat ``[d]`` vector.

    Returns ``(idx int32[k], vals f32[k], residual f32[d])`` where
    ``residual`` is ``x`` with the selected entries zeroed — exactly the
    mass the wire does NOT carry, to be error-fed into the next delta.
    """
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    vals = x[idx]
    residual = x.at[idx].set(0.0)
    return idx.astype(jnp.int32), vals, residual


def topk_sparsify_reference(x, k: int):
    """Pure-numpy parity oracle for :func:`topk_sparsify`.

    ``jax.lax.top_k`` selects by descending value and breaks ties by
    LOWEST index first; a stable descending argsort over ``|x|``
    reproduces exactly that order, so indices, values, and the EF
    residual must all match the jitted path bit-for-bit — the contract
    the codec parity tests pin. This path is the oracle only (host
    numpy, no donation, no jit): the wire always rides the jitted
    kernels."""
    x = np.asarray(x, np.float32)
    k = max(1, min(int(x.size), int(k)))
    idx = np.argsort(-np.abs(x), kind="stable")[:k].astype(np.int32)
    vals = x[idx]
    residual = x.copy()
    residual[idx] = 0.0
    return idx, vals, residual


def _donate_flat_input() -> bool:
    """Donate the flat delta buffer only where XLA implements donation
    (tpu aliasing); the CPU backend warns-and-copies, so tests under
    JAX_PLATFORMS=cpu run the identical program without the donation."""
    return on_tpu()


@functools.lru_cache(maxsize=None)
def _donated_topk_sparsify(k: int, donate: bool):
    def fn(x):
        return topk_sparsify(x, k)
    return jax.jit(fn, donate_argnums=((0,) if donate else ()))


def topk_sparsify_donated(x: jax.Array, k: int):
    """:func:`topk_sparsify` with the input buffer donated to the
    computation (the residual reuses the delta's memory on tpu —
    the flat delta is a freshly built temporary at every call site, so
    the aliasing is free bandwidth). Same compiled program otherwise:
    bit-exact with :func:`topk_sparsify` and the numpy reference."""
    return _donated_topk_sparsify(int(k), _donate_flat_input())(x)


@functools.lru_cache(maxsize=None)
def _donated_topk_quantize(k: int, interpret: bool, donate: bool):
    def fn(x, key):
        return topk_quantize(x, key, k, interpret=interpret)
    return jax.jit(fn, donate_argnums=((0,) if donate else ()))


def topk_quantize_donated(x: jax.Array, key: jax.Array, k: int, *,
                          interpret: bool = False):
    """:func:`topk_quantize` with the flat input donated (see
    :func:`topk_sparsify_donated`) — the uplink encode's steady-state
    entry point."""
    return _donated_topk_quantize(int(k), bool(interpret),
                                  _donate_flat_input())(x, key)


@functools.partial(jax.jit, static_argnames=("d",))
def topk_densify(idx: jax.Array, vals: jax.Array, d: int) -> jax.Array:
    """Scatter sparse ``(idx, vals)`` back to a dense ``[d]`` f32 vector."""
    return jnp.zeros((d,), jnp.float32).at[idx].set(
        vals.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_quantize(x: jax.Array, key: jax.Array, k: int, *,
                  interpret: bool = False):
    """Sparsify then int8-quantize the survivors (the uplink hot path).

    Returns ``(idx int32[k], q int8[k], scales f32[ceil(k/BLOCK)],
    residual f32[d])``. The residual charges BOTH error sources: the
    dropped entries keep their full value, and each kept entry carries its
    quantization error ``val - dequant(q)`` — so the error-feedback loop
    sees the exact wire-vs-truth gap, not just the sparsification part.
    """
    idx, vals, residual = topk_sparsify(x, k)
    q, scales = quantize_int8(vals, key, interpret=interpret)
    deq = dequantize_int8(q, scales, k, interpret=interpret)
    residual = residual.at[idx].add(vals - deq)
    return idx, q, scales, residual


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def topk_dequantize(idx: jax.Array, q: jax.Array, scales: jax.Array,
                    d: int, *, interpret: bool = False) -> jax.Array:
    """Inverse of :func:`topk_quantize` — dense ``[d]`` f32 rebuild."""
    k = q.shape[0]
    vals = dequantize_int8(q, scales, k, interpret=interpret)
    return topk_densify(idx, vals, d)


# -- static-analysis hook (fedml_tpu.analysis layer 2) ----------------------
from fedml_tpu.analysis.registry import AuditSpec, hot_entry_point  # noqa: E402

_AUDIT_D, _AUDIT_K = 4096, 128


@hot_entry_point("ops.topk_quantize_fwd")
def _audit_topk_quantize() -> AuditSpec:
    """The uplink compression hot path (sparsify + int8-quantize the
    survivors), swept over two rounds' worth of deltas at the same
    (d, k): every round of a run must hit the one compiled program."""

    def fn(x, key):
        return topk_quantize(x, key, _AUDIT_K, interpret=True)

    sweep = [(jax.random.normal(jax.random.key(i), (_AUDIT_D,),
                                jnp.float32), jax.random.key(100 + i))
             for i in range(2)]
    return AuditSpec(fn=fn, sweep=sweep, max_lowerings=1)


@hot_entry_point("ops.topk_dequant_rebuild")
def _audit_topk_dequant() -> AuditSpec:
    """The server-side rebuild path (dequantize survivors + scatter to the
    dense delta), swept over two payloads of the same (d, k)."""

    def fn(idx, q, scales):
        return topk_dequantize(idx, q, scales, _AUDIT_D, interpret=True)

    def payload(i):
        x = jax.random.normal(jax.random.key(i), (_AUDIT_D,), jnp.float32)
        idx, q, scales, _ = topk_quantize(x, jax.random.key(200 + i),
                                          _AUDIT_K, interpret=True)
        return (idx, q, scales)

    return AuditSpec(fn=fn, sweep=[payload(i) for i in range(2)],
                     max_lowerings=1)
