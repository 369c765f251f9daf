"""Block-scaled int8 quantization kernels for comm compression.

Cross-silo federated rounds ship full model deltas over DCN/WAN; the
reference ships them as full-precision pickled tensors (reference:
mpi_send_thread.py:27, or JSON float lists for mobile — fedavg/utils.py:12).
Here deltas are compressed 4x with per-block int8 quantization + stochastic
rounding (unbiased: E[q] = x, so FedAvg's weighted mean stays unbiased).

The kernel is pure arithmetic — random bits are generated outside with
``jax.random.bits`` and streamed in — so the identical kernel runs under the
Pallas interpreter on the CPU test mesh and compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 512     # values per scale block (4 lanes of 128)
_TILE_R = 32    # row tile; int8 min sublane tile on TPU


def _quant_kernel(x_ref, rand_ref, vals_ref, scales_ref):
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    scaled = x / scale
    # stochastic rounding: floor + Bernoulli(frac) using uniform [0,1) bits.
    # Mosaic has no uint32 -> f32 cast; the 24 surviving bits are
    # non-negative as int32, so the bitcast changes no value
    bits = jax.lax.bitcast_convert_type(rand_ref[:] >> jnp.uint32(8),
                                        jnp.int32)
    u = bits.astype(jnp.float32) * (2.0 ** -24)
    low = jnp.floor(scaled)
    q = low + (u < (scaled - low)).astype(jnp.float32)
    q = jnp.clip(q, -127.0, 127.0)
    vals_ref[:] = q.astype(jnp.int8)
    scales_ref[:] = jnp.broadcast_to(scale, scales_ref.shape)


def _dequant_kernel(vals_ref, scales_ref, out_ref):
    out_ref[:] = vals_ref[:].astype(jnp.float32) * scales_ref[:, :1]


def _pad_rows(d: int) -> tuple[int, int]:
    rows = -(-d // BLOCK)
    return rows, -rows % _TILE_R


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8(x: jax.Array, key: jax.Array, *,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Quantize a flat float vector to (int8 values, per-block f32 scales).

    Returns ``values [D]`` and ``scales [ceil(D/BLOCK)]``. Zero-padding in the
    last block quantizes to zero, so dequantize+slice round-trips exactly.
    """
    (d,) = x.shape
    rows, row_pad = _pad_rows(d)
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * BLOCK - d))
    xp = jnp.pad(xp.reshape(rows, BLOCK), ((0, row_pad), (0, 0)))
    rp = rows + row_pad
    rand = jax.random.bits(key, (rp, BLOCK), jnp.uint32)

    vals, scales = pl.pallas_call(
        _quant_kernel,
        grid=(rp // _TILE_R,),
        in_specs=[
            pl.BlockSpec((_TILE_R, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_R, BLOCK), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TILE_R, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_R, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(xp, rand)
    return vals.reshape(-1)[:d], scales[:rows, 0]


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def dequantize_int8(values: jax.Array, scales: jax.Array, d: int, *,
                    interpret: bool = False) -> jax.Array:
    """Inverse of :func:`quantize_int8` — returns the ``[d]`` f32 vector."""
    rows, row_pad = _pad_rows(d)
    vp = jnp.pad(values, (0, rows * BLOCK - d)).reshape(rows, BLOCK)
    vp = jnp.pad(vp, ((0, row_pad), (0, 0)))
    sp = jnp.pad(scales, (0, row_pad))
    rp = rows + row_pad
    sp = jnp.broadcast_to(sp[:, None], (rp, 128))

    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rp // _TILE_R,),
        in_specs=[
            pl.BlockSpec((_TILE_R, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((_TILE_R, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE_R, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, BLOCK), jnp.float32),
        interpret=interpret,
    )(vp, sp)
    return out.reshape(-1)[:d]


def quantize_tree(tree, key, *, interpret: bool = False):
    """Quantize a parameter pytree; returns ``(values, scales, spec)``.

    ``spec`` carries the treedef + leaf shapes/dtypes needed to rebuild; the
    (values, scales) pair is what goes on the wire — 4x smaller than f32.
    """
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    vals, scales = quantize_int8(flat, key, interpret=interpret)
    spec = (treedef, [(l.shape, l.dtype.name) for l in leaves], flat.size)
    return vals, scales, spec


def dequantize_tree(values, scales, spec, *, interpret: bool = False):
    """Rebuild the pytree from :func:`quantize_tree` output."""
    treedef, leaf_meta, d = spec
    flat = dequantize_int8(values, scales, d, interpret=interpret)
    out, off = [], 0
    for shape, dtype in leaf_meta:
        size = 1
        for s in shape:
            size *= s
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, out)
