"""Fused weighted client aggregation as a Pallas TPU kernel.

The FedAvg server update is ``w_global = sum_i n_i * w_i / sum_i n_i``
(reference: FedAVGAggregator.py:72-80). With client updates stacked as a
``[C, D]`` matrix this is a ``[1, C] @ [C, D]`` matvec — exactly the shape the
MXU wants — so the whole aggregation is one kernel pass over HBM instead of a
per-leaf Python loop. The kernel tiles D into VMEM-sized lanes and keeps the
tiny weight vector resident.

Where a copy of the model is gigabytes the clients cannot be stacked: they
train one after another and each result is *folded* into a running float32
sum, ``acc += (n_i / sum n) * w_i`` (``tree_fold_pallas``). That kernel
updates the sum in place (``input_output_aliases``), leaf by leaf in the
leaf's own shape, so a fold reads the sum and the client once and writes
the sum once and nothing is concatenated, padded or copied around it.

CPU/test path: ``interpret=True`` runs the same kernels through the Pallas
interpreter; ``weighted_mean_flat_reference`` is the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# -- the in-place fold ------------------------------------------------------------

#: elements of one block of the fold (float32: 1 MiB; the sum, the client
#: and the result double-buffered are 6 MiB of VMEM)
_FOLD_BLOCK = 1 << 18
#: widest block along the lane axis
_FOLD_LANES = 2048


def _fold_kernel(w_ref, acc_ref, x_ref, out_ref):
    out_ref[:] = acc_ref[:] + w_ref[0, 0] * x_ref[:].astype(jnp.float32)


def _fold_block(rows: int, cols: int):
    """The block of a ``[rows, cols]`` leaf (``rows % 8 == 0``, ``cols % 128
    == 0``): the widest multiple of 128 lanes up to ``_FOLD_LANES`` that
    divides ``cols``, and as many rows as ``_FOLD_BLOCK`` allows."""
    lanes = max(n for n in range(128, min(cols, _FOLD_LANES) + 1, 128)
                if cols % n == 0)
    return min(rows, max(8, _FOLD_BLOCK // lanes // 8 * 8)), lanes


def fold_weighted(acc: jax.Array, x: jax.Array, weight, *,
                  interpret: bool = False) -> jax.Array:
    """``acc + weight * x`` for one leaf, ``acc`` float32 and ``weight`` a
    scalar. A matrix the TPU tiles without padding (rows a multiple of 8,
    columns of 128) goes through the Pallas kernel, which writes the result
    over ``acc``; anything else - vectors, a handful of narrow matrices - is
    a sliver of the model and is left to XLA."""
    weight = jnp.asarray(weight, jnp.float32)
    if acc.ndim != 2 or acc.shape[0] % 8 or acc.shape[1] % 128:
        return acc + weight * x.astype(jnp.float32)
    rows, cols = acc.shape
    block = _fold_block(rows, cols)
    tile = pl.BlockSpec(block, lambda i, j: (i, j))
    return pl.pallas_call(
        _fold_kernel,
        grid=(pl.cdiv(rows, block[0]), cols // block[1]),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(weight.reshape(1, 1), acc, x)


@jax.named_scope("fedml.fold")
def tree_fold_pallas(acc_tree, x_tree, weight, *, interpret: bool = False):
    """Fold one client's model into the running float32 sum, leaf by leaf:
    ``acc + weight * x``. ``weight`` is the client's share ``n_i / sum n``,
    so after the last client the sum is the FedAvg mean."""
    return jax.tree.map(
        lambda a, x: fold_weighted(a, x, weight, interpret=interpret),
        acc_tree, x_tree)
