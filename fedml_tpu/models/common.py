"""Shared building blocks for the model zoo."""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.linear import canonicalize_padding
from flax.traverse_util import flatten_dict
from jax import lax


Spec = Tuple[Tuple[str, Tuple[int, ...], Callable], ...]


class Leaves(nn.Module):
    """One named group of parameters, declared from ``specs`` and returned
    as a dictionary: a layer's parameter tree in the decoder stack
    (``models/decoder.py``)."""

    specs: Spec

    @nn.compact
    def __call__(self) -> Dict[str, jnp.ndarray]:
        return {name: self.param(name, init, shape)
                for name, shape, init in self.specs}


def uniform_init(bound: float):
    """Uniform in ``-bound .. bound`` (torch's default for a convolution at
    ``bound = fan_in ** -0.5``)."""
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of step sizes drawn log-uniformly from
    1e-3..1e-1, so that ``softplus(bias)`` starts there (Mamba's and
    Mamba-2's start)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def expert_bias_init(layer: int):
    """A router's selection bias: normal(0, 0.1), from a fixed key and the
    published layer index, so the same for every seed (as a checkpoint's)."""
    def init(key, shape, dtype=jnp.float32):
        del key
        return 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(0), layer), shape, dtype)
    return init


def rms_norm(x, scale, eps: float):
    """``x / rms(x) * scale`` over the last axis."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def rotary(x, theta: float):
    """Rotary positions (rotate-half over the whole last axis) of ``x [...,
    T, D]``, positions ``0 .. T - 1``: ``x cos + rotate_half(x) sin`` with
    frequencies ``theta ** (-2 i / D)``."""
    length, dim = x.shape[-2:]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return (x * jnp.cos(angles).astype(x.dtype)
            + jnp.concatenate([-x2, x1], axis=-1)
            * jnp.sin(angles).astype(x.dtype))


def bn(train: bool, sync_axis: Optional[str] = None) -> nn.BatchNorm:
    """The zoo-wide BatchNorm configuration (torch defaults: momentum 0.1 ->
    flax momentum 0.9, eps 1e-5), running stats in the ``batch_stats``
    collection, frozen in eval mode.

    ``sync_axis``: a mesh axis name to synchronize batch statistics over —
    the TPU re-expression of the reference's SynchronizedBatchNorm
    (fedml_api/model/cv/batchnorm_utils.py, the DataParallel cross-GPU
    stats shim). Inside ``shard_map``/``vmap`` over that named axis, flax
    psums the mean/var so every shard normalizes with the *global* batch
    statistics; no extra machinery needed (tests/test_sync_bn.py proves
    shard==global parity)."""
    return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                        epsilon=1e-5, axis_name=sync_axis)


def live_window(size: int, k: int, stride: int, pad) -> tuple:
    """``(lo, hi, pad_lo, pad_hi)``: the contiguous window ``[lo, hi)`` of a
    ``k``-tap kernel axis whose taps meet a real position of a ``size``-long
    input axis at some output position, under ``stride`` and zero padding
    ``pad = (before, after)``, and the padding a convolution with
    ``kernel[lo:hi]`` takes to give the same outputs. The taps outside the
    window only ever multiply the zero border (a 3x3 kernel with padding 1 on
    a 1x1 map: the centre tap of nine). A pure function of shapes; where
    every tap is live it returns ``(0, k, *pad)``."""
    before, after = pad
    last = (size + before + after - k) // stride  # the last output position
    # output o lays tap t on real position stride * o + t - before: the
    # lowest live tap belongs to the last output that still reaches the
    # input, the highest to the first output whose window has left the border
    o_low = min(last, (size - 1 + before) // stride)
    o_high = max(0, -((k - 1 - before) // stride))
    lo = max(0, before - stride * o_low)
    hi = min(k, size + before - stride * o_high)
    if last < 0 or lo >= hi:  # no output, or none that meets the input
        return 0, k, before, after
    return lo, hi, before - lo, after - (k - hi)


class LiveTapConv(nn.Conv):
    """``nn.Conv`` (same ``kernel`` parameter: shape, name, initialiser) that
    reads only the live window of its kernel (``live_window``, from the
    static spatial shape of its input). The forward pass, the data gradient
    and the weight gradient then leave out exactly the products that have a
    zero of the padding as one factor; the dead taps stay in the parameter
    tree and get a gradient of exactly zero. Where no tap is dead, or the
    convolution is anything but a plain zero-padded one (dilation, groups,
    a mask, a bias, string padding), this is ``nn.Conv.__call__``.

    The stored ``kernel`` may be the declared one or its window already cut
    (``kernel[window]``, the window's shape): a client's local loop carries
    the window alone and hands it over as it is
    (``trainer/functional.py::make_local_train``). The dead taps are still
    in the model's tree because they are the source's parameters: every
    checkpoint, mean, evaluation and any other row shape has all nine.

    It sows the window as ``live_window`` into ``intermediates`` (a no-op
    unless that collection is mutable): ``live_windows`` below reads it."""

    @nn.compact
    def __call__(self, x):
        size = self.kernel_size
        size = (size,) if isinstance(size, int) else tuple(size)
        strides = self.strides or 1
        if isinstance(strides, int):
            strides = (strides,) * len(size)
        pads = canonicalize_padding(self.padding, len(size))
        plain = (
            x.ndim == 4 and len(size) == 2 and not isinstance(pads, str)
            and not self.use_bias and self.mask is None
            and self.feature_group_count == 1
            and self.input_dilation in (None, 1)
            and self.kernel_dilation in (None, 1)
            and self.conv_general_dilated is None
            and self.conv_general_dilated_cls is None)
        windows = [live_window(n, k, s, p) for n, k, s, p in
                   zip(x.shape[1:3], size, strides, pads)] if plain else ()
        if all((lo, hi) == (0, k) for (lo, hi, _, _), k in zip(windows, size)):
            return nn.Conv.__call__(self, x)
        window = tuple((lo, hi) for lo, hi, _, _ in windows)
        self.sow("intermediates", "live_window", window)
        channels = (x.shape[-1], self.features)
        cut = window_shape(window, size + channels)
        is_cut = (self.has_variable("params", "kernel") and
                  self.get_variable("params", "kernel").shape == cut)
        # anything but the window's or the declared shape is ``param``'s
        # shape error
        kernel = self.param("kernel", self.kernel_init,
                            cut if is_cut else size + channels,
                            self.param_dtype)
        x, kernel, _ = self.promote_dtype(x, kernel, None, dtype=self.dtype)
        if not is_cut:
            # the window is cut once, ahead of both passes: without the
            # barrier XLA fuses the slice into the forward convolution and
            # cuts it again from the whole kernel for the data gradient,
            # after the fused SGD update has overwritten that kernel in
            # place - so it first copies the whole kernel, at every local
            # step (PERF.md section 6, "PR 32")
            kernel = lax.optimization_barrier(cut_window(kernel, window))
        return lax.conv_general_dilated(
            x, kernel, strides, [w[2:] for w in windows],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.precision)


def window_shape(window, shape) -> tuple:
    """The shape of ``cut_window(leaf, window)`` for a leaf of ``shape``."""
    return tuple(hi - lo for lo, hi in window) + tuple(shape[len(window):])


def cut_window(leaf, window):
    """``leaf[lo:hi, ...]`` over its leading axes: ``window`` is a tuple of
    ``(lo, hi)``, one an axis."""
    return leaf[tuple(slice(lo, hi) for lo, hi in window)]


def write_window(leaf, cut, window):
    """``leaf`` with ``cut`` written over ``cut_window(leaf, window)``."""
    return lax.dynamic_update_slice(
        leaf, cut, [lo for lo, _ in window] + [0] * (leaf.ndim - len(window)))


def live_windows(module, variables, x) -> Dict[tuple, tuple]:
    """``{path of a kernel in variables["params"]: its live window}`` (a
    tuple of ``(lo, hi)`` over the leading, spatial axes) for every kernel
    ``LiveTapConv`` slices when ``module`` runs on rows shaped like ``x``:
    the part of the leaf a training step at that row shape can read or
    change. Traced under ``eval_shape``, so nothing is computed; a pure
    function of the module and the input's shape, empty for a model with
    no padded convolution on a map smaller than its kernel. A kernel shared
    by calls whose windows differ is left out."""
    found = {}

    def run(variables, x):
        _, sown = module.apply(variables, x, train=False,
                               mutable=["intermediates"])
        found.update(
            (path[:-1] + ("kernel",), windows[0])
            for path, windows in flatten_dict(
                sown.get("intermediates", {})).items()
            if path[-1] == "live_window" and len(set(windows)) == 1)

    jax.eval_shape(run, variables, x)
    return found


def dead_taps(windows, params) -> int:
    """Parameters of ``params`` outside ``windows`` in the leaves they
    name (``live_windows``)."""
    flat = flatten_dict(params)
    return sum(flat[path].size - math.prod(window_shape(window,
                                                        flat[path].shape))
               for path, window in windows.items())


def dead_tap_params(module, variables, x) -> int:
    """Parameters of ``variables`` that sit in kernel taps ``LiveTapConv``
    slices away when ``module`` runs on inputs shaped like ``x``."""
    return dead_taps(live_windows(module, variables, x), variables["params"])
