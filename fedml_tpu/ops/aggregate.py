"""Weighted client aggregation as Pallas TPU kernels.

The FedAvg server update is ``w_global = sum_i n_i * w_i / sum_i n_i``
(reference: FedAVGAggregator.py:72-80). Both forms here work leaf by leaf,
in the shape and layout the trainer left the leaf in, so nothing is
concatenated, padded or copied around a kernel: a round's aggregation reads
every client's parameters once and writes the mean once.

Where the cohort's models are stacked (the ``vmap``-ped round: every leaf
``[C, ...]``), ``tree_weighted_mean_pallas`` takes the mean of each leaf:
a leaf the TPU tiles without padding goes through ``_mean_kernel``, a grid
over row and lane blocks with the clients' shares in SMEM and a float32 sum
over the client axis on the VPU (exact float32: the MXU's default is one
bf16 pass); vectors and narrow matrices, a fiftieth of a convolutional
model, are left to XLA's fused multiply-reduce.

Where a copy of the model is gigabytes the clients cannot be stacked: they
train one after another and each result is *folded* into a running float32
sum, ``acc += (n_i / sum n) * w_i`` (``tree_fold_pallas``). That kernel
updates the sum in place (``input_output_aliases``), so a fold reads the
sum and the client once and writes the sum once.

CPU/test path: ``interpret=True`` runs the same kernels through the Pallas
interpreter; ``weighted_mean_flat_reference`` is the jnp oracle.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def weighted_mean_flat_reference(stacked: jax.Array,
                                 weights: jax.Array) -> jax.Array:
    """jnp oracle: sample-weighted mean over axis 0 of ``[C, D]``, f32
    products on every backend (a TPU's default is one bf16 pass)."""
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w)
    return jnp.einsum("c,cd->d", w, stacked.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


# -- the stacked mean -------------------------------------------------------------

#: bytes of VMEM the stacked mean's input block may take, double-buffered
#: (Mosaic's scoped limit on a v5e is 16 MiB)
_MEAN_VMEM = 8 << 20
#: most elements of one output block: its float32 sum is carried through
#: the loop over clients in vector registers (16 of the 64)
_MEAN_ACC = 1 << 14


def _mean_kernel(share_ref, x_ref, out_ref):
    # share: [C] in SMEM, x: [1, C, r, l] -> out: [1, r, l]. A multiply-add
    # a client on the VPU, which keeps float32 exact; the MXU form needs
    # Precision.HIGHEST for that and measured no faster (CHANGES.md, PR 21)
    def add(c, acc):
        return acc + share_ref[c] * x_ref[0, c].astype(jnp.float32)

    out_ref[0] = jax.lax.fori_loop(
        0, x_ref.shape[1], add, jnp.zeros(out_ref.shape[1:], jnp.float32))


def _mean_block(clients: int, shape, dtype):
    """The ``(rows, lanes)`` block in which ``_mean_kernel`` takes the mean
    of a stacked leaf ``[clients, *shape]``, or None where the leaf is left
    to XLA. The kernel takes a float32 leaf whose last two dimensions the
    TPU tiles without padding (a multiple of 8 by a multiple of 128). The
    block is the widest multiple of 128 lanes that divides the columns, and
    as many rows, with the clients' blocks double-buffered inside
    ``_MEAN_VMEM``."""
    budget = min(_MEAN_ACC, _MEAN_VMEM // (2 * 4 * clients))
    if (len(shape) < 2 or shape[-2] % 8 or shape[-1] % 128
            or dtype != jnp.float32 or budget < 8 * 128):
        return None
    rows, cols = shape[-2:]
    lanes = max(n for n in range(128, min(cols, budget // 8) + 1, 128)
                if cols % n == 0)
    return min(rows, budget // lanes // 8 * 8), lanes


def stacked_mean_leaf(x: jax.Array, share: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """``sum_c share[c] * x[c]`` for one stacked leaf ``[C, ...]``, summed in
    float32 and returned in the leaf's dtype; ``share`` is ``[C]`` float32.
    Which leaves go through the Pallas kernel is read from the shape
    (``_mean_block``); a leaf's mean costs one read of the leaf either way.

    The kernel reads a leaf ``[C, *lead, R, L]`` as ``[prod(lead), C, R,
    L]``: that is how XLA lays out a vmapped convolution's kernels in the
    trainer's loop (``[104, 3, 3, 512, 512]`` is carried as ``[3, 3, 104,
    512, 512]``), so the view is a bitcast where the client axis in front
    would cost a transposing copy of the leaf."""
    c, shape = x.shape[0], x.shape[1:]
    block = _mean_block(c, shape, x.dtype)
    if block is None:
        share = share.reshape((c,) + (1,) * len(shape))
        return jnp.sum(share * x.astype(jnp.float32), axis=0).astype(x.dtype)
    rows, cols = shape[-2:]
    planes = math.prod(shape[:-2])
    return pl.pallas_call(
        _mean_kernel,
        grid=(planes, pl.cdiv(rows, block[0]), cols // block[1]),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, c) + block, lambda p, i, j: (p, 0, i, j))],
        out_specs=pl.BlockSpec((1,) + block, lambda p, i, j: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((planes, rows, cols), jnp.float32),
        interpret=interpret,
    )(share, jnp.moveaxis(x, 0, -3).reshape(planes, c, rows, cols)
      ).reshape(shape)


def mean_kernel_params(tree, clients: int):
    """``(through the kernel, left to XLA)``: the parameters of one model
    ``tree`` on either side of ``stacked_mean_leaf``'s choice when
    ``clients`` copies of it are stacked."""
    kernel = xla = 0
    for leaf in jax.tree.leaves(tree):
        if _mean_block(clients, leaf.shape, leaf.dtype) is None:
            xla += math.prod(leaf.shape)
        else:
            kernel += math.prod(leaf.shape)
    return kernel, xla


@jax.named_scope("fedml.aggregate")
def tree_weighted_mean_pallas(stacked_tree, weights, *,
                              interpret: bool = False):
    """Sample-weighted mean over the leading (client) axis of every leaf,
    leaf by leaf in the leaf's own shape (``stacked_mean_leaf``).

    Drop-in for :func:`fedml_tpu.core.pytree.tree_weighted_mean`;
    ``weights`` are the per-client sample counts ``n_i``, normalised once
    to float32 shares."""
    share = weights.astype(jnp.float32)
    share = share / jnp.sum(share)
    return jax.tree.map(
        lambda x: stacked_mean_leaf(x, share, interpret=interpret),
        stacked_tree)


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
    over ``acc``; a stack of such matrices (routed experts: ``[experts, d,
    w]``) goes through it as one matrix of all their rows, which is the same
    bytes in the same order; anything else - vectors, a handful of narrow
    matrices - is a sliver of the model and is left to XLA."""
    weight = jnp.asarray(weight, jnp.float32)
    if acc.ndim < 2 or acc.shape[-2] % 8 or acc.shape[-1] % 128:
        return acc + weight * x.astype(jnp.float32)
    if acc.ndim > 2:
        flat = (-1, acc.shape[-1])
        return fold_weighted(acc.reshape(flat), x.reshape(flat), weight,
                             interpret=interpret).reshape(acc.shape)
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
