"""Fused weighted client aggregation as a Pallas TPU kernel.

The FedAvg server update is ``w_global = sum_i n_i * w_i / sum_i n_i``
(reference: FedAVGAggregator.py:72-80). With client updates stacked as a
``[C, D]`` matrix this is a ``[1, C] @ [C, D]`` matvec — exactly the shape the
MXU wants — so the whole aggregation is one kernel pass over HBM instead of a
per-leaf Python loop. The kernel tiles D into VMEM-sized lanes and keeps the
tiny weight vector resident.

CPU/test path: ``interpret=True`` runs the same kernel through the Pallas
interpreter; ``weighted_mean_flat_reference`` is the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# lane tile for the parameter axis; multiple of 128 (TPU lane width) and
# small enough that [C, TILE_D] fits VMEM for any realistic clients-per-round
_TILE_D = 2048


def _wmean_kernel(w_ref, x_ref, out_ref):
    # w: [1, C], x: [C, TILE_D] -> out: [1, TILE_D]; rides the MXU.
    # HIGHEST: at Mosaic's default precision the MXU multiplies f32
    # operands in one bf16 pass, which rounds every parameter of the new
    # global model to ~3 digits (measured on a v5e: 3e-3 of max|w|).
    # Measured there at [10, 11.2M]: 2.49 ms per call against 2.20 ms at
    # the default precision (CHANGES.md, PR 21)
    out_ref[:] = jnp.dot(w_ref[:], x_ref[:],
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


def weighted_mean_flat_reference(stacked: jax.Array,
                                 weights: jax.Array) -> jax.Array:
    """jnp oracle: sample-weighted mean over axis 0 of ``[C, D]``, f32
    products on every backend (a TPU's default is one bf16 pass)."""
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w)
    return jnp.einsum("c,cd->d", w, stacked.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_mean_flat(stacked: jax.Array, weights: jax.Array,
                       *, interpret: bool = False) -> jax.Array:
    """Sample-weighted mean over the client axis of a ``[C, D]`` stack.

    Returns a ``[D]`` float32 vector. ``weights`` are the per-client sample
    counts ``n_i``; normalization by ``sum(n_i)`` is folded into the weight
    vector so the kernel is a single matvec.
    """
    c, d = stacked.shape
    w = weights.astype(jnp.float32)
    w = (w / jnp.sum(w)).reshape(1, c)

    pad = (-d) % _TILE_D
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    dp = d + pad

    out = pl.pallas_call(
        _wmean_kernel,
        grid=(dp // _TILE_D,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, _TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, _TILE_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(w, stacked)
    return out[0, :d]


@jax.named_scope("fedml.aggregate")
def tree_weighted_mean_pallas(stacked_tree, weights, *,
                              interpret: bool = False):
    """Pytree front-end: ravel all leaves into one ``[C, D]`` matrix, run the
    fused kernel once, and unravel.

    Drop-in for :func:`fedml_tpu.core.pytree.tree_weighted_mean` — one kernel
    launch for the whole model instead of one reduction per leaf, which is the
    difference between a bandwidth-bound single pass and dozens of tiny
    dispatches for deep models (ResNet-56 has 250+ leaves).
    """
    leaves, treedef = jax.tree.flatten(stacked_tree)
    c = leaves[0].shape[0]
    sizes = [leaf[0].size for leaf in leaves]
    shapes = [leaf.shape[1:] for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(c, -1).astype(jnp.float32) for leaf in leaves], axis=1)
    mean = weighted_mean_flat(flat, weights, interpret=interpret)
    out, off = [], 0
    for size, shape, leaf in zip(sizes, shapes, leaves):
        out.append(mean[off:off + size].reshape(shape).astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)
