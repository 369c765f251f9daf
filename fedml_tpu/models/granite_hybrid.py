"""Granite-4.0-H: the dense hybrid decoder of granite-4.0-h-micro
(``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0): Mamba-2
state-space layers beside grouped-query attention without positions, a
fused SwiGLU feed forward in every layer, and four scalar multipliers.

For a row ``tokens [T]``, published layer ``l`` (``layer_ids`` says which
published layers this instance holds, so a cut in depth moves no layer's
kind)::

    x = embedding_multiplier * E[tokens]
    x = x + residual_multiplier * Mixer_l(RMSNorm(x))
    [a, b] = RMSNorm'(x) W_in ;  x = x + residual_multiplier
                                         * (silu(a) * b) W_out
    logits = RMSNorm''(x) E' / logits_scaling

* ``layer_types[l] == "attention"`` - 32 query and 8 key/value heads, no
  bias and **no positions at all** (``position_embedding_type`` ``nope``);
  causal softmax at ``attention_multiplier`` (not ``head_dim ** -0.5``);
  ``ops/block_attention.py``.
* ``"mamba"`` - Mamba-2 (arXiv:2405.21060): ``[z | xBC | dt] = s W_in``;
  ``xBC = silu(causal depthwise conv_4(xBC) + bias)`` over ``x``, ``B`` and
  ``C`` together, zeros before the row; ``[xs | B | C] = xBC`` with ``xs``
  as ``H`` heads of ``P`` and ``B``, ``C`` as ``G`` groups of ``N``; ``dt =
  softplus(dt + dt_bias)`` a head, ``A = -exp(A_log)`` a head (float32);
  the recurrence of ``ops/ssd.py`` (one scalar decay a head and step, a
  state of ``P x N`` a head) in its chunked dual form; ``y += D * xs``;
  ``y = RMSNorm(y * silu(z))`` over all ``H P`` channels - the gate first,
  then the norm (one group) - and ``W_out``. No projection bias, no clamp
  on ``dt``.

``vocab_size`` is the rows of the tied embedding held (a vocabulary-
parallel share is a smaller vocabulary). The model runs through the decoder
stack of ``models/decoder.py``; its hidden states reach the head already
divided by ``logits_scaling``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import decoder
from fedml_tpu.models.common import Spec, dt_bias_init, rms_norm, uniform_init
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.ssd import ssd_scan

#: granite-4.0-h-micro's published pattern: attention at 5, 15, 25, 35
GRANITE_H_MICRO_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))

_normal = nn.initializers.normal(0.02)
_ones = nn.initializers.ones


def _a_log(key, shape, dtype=jnp.float32):
    """``log(a)``, ``a`` uniform in 1..16 a head (Mamba-2's start)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


@jax.named_scope("fedml.mamba2")
def _mamba2(p, s, cfg):
    """``s [B, T, d]`` -> the mixer's output before the residual."""
    heads, dim = cfg["mamba_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["mamba_groups"], cfg["mamba_state"]
    inner, rows, length = heads * dim, s.shape[0], s.shape[1]
    z, xbc, dt = jnp.split(s @ p["in_proj"],
                           [inner, 2 * inner + 2 * groups * state], axis=-1)
    taps = p["conv_kernel"].shape[0]
    shifted = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j weighs the input j positions back
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        shifted[:, taps - 1 - j:taps - 1 - j + length] * p["conv_kernel"][j]
        for j in range(taps)))
    xs, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    xs = xs.reshape(rows, length, heads, dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y = jax.vmap(functools.partial(ssd_scan, chunk=cfg["mamba_chunk"]),
                 in_axes=(0, 0, None, 0, 0))(
        xs, dt, a, b.reshape(rows, length, groups, state),
        c.reshape(rows, length, groups, state))
    y = (y + p["d_skip"][:, None] * xs).reshape(rows, length, inner)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm_scale"], cfg["eps"])
    return y @ p["out_proj"]


@jax.named_scope("fedml.attention")
def _attention(p, s, cfg):
    rows, length, _ = s.shape

    def heads(w):  # [B, T, H * D] -> [B, H, T, D]
        return jnp.swapaxes(
            (s @ w).reshape(rows, length, -1, cfg["head_dim"]), 1, 2)

    out = jax.vmap(functools.partial(
        causal_attention, scale=cfg["attention_multiplier"],
        block=cfg["attn_block"]))(
            heads(p["q_proj"]), heads(p["k_proj"]), heads(p["v_proj"]))
    return jnp.swapaxes(out, 1, 2).reshape(rows, length, -1) @ p["o_proj"]


@jax.named_scope("fedml.mlp")
def _mlp(p, s):
    gate, up = jnp.split(s @ p["ffn_in"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["ffn_out"]


def _layer(p, x, *, kind: str, cfg):
    """One layer on a batch of rows ``x [B, T, d]``."""
    s = rms_norm(x, p["input_norm_scale"], cfg["eps"])
    mixer = _mamba2 if kind == "mamba" else _attention
    x = x + cfg["residual_multiplier"] * mixer(p, s, cfg)
    s = rms_norm(x, p["post_norm_scale"], cfg["eps"])
    return x + cfg["residual_multiplier"] * _mlp(p, s)


class GraniteHybridLM(nn.Module):
    """See the module docstring. Defaults are granite-4.0-h-micro's
    published sizes."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    shared_intermediate_size: int = 8192
    layer_ids: Tuple[int, ...] = tuple(range(40))
    layer_types: Tuple[str, ...] = GRANITE_H_MICRO_LAYER_TYPES
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    attn_block: int = 512
    return_logits: bool = False

    def _specs(self, layer: int) -> Spec:
        d, head_dim = self.hidden_size, self.hidden_size // self.num_heads
        kv = self.num_kv_heads * head_dim
        heads = self.mamba_n_heads
        inner = heads * self.mamba_d_head
        conv = inner + 2 * self.mamba_n_groups * self.mamba_d_state
        norms = (("input_norm_scale", (d,), _ones),
                 ("post_norm_scale", (d,), _ones))
        if self.layer_types[layer] == "mamba":
            bound = self.mamba_d_conv ** -0.5
            mixer = (("in_proj", (d, inner + conv + heads), _normal),
                     ("conv_kernel", (self.mamba_d_conv, conv),
                      uniform_init(bound)),
                     ("conv_bias", (conv,), uniform_init(bound)),
                     ("dt_bias", (heads,), dt_bias_init),
                     ("a_log", (heads,), _a_log),
                     ("d_skip", (heads,), _ones),
                     ("gate_norm_scale", (inner,), _ones),
                     ("out_proj", (inner, d), _normal))
        else:
            mixer = (("q_proj", (d, d), _normal),
                     ("k_proj", (d, kv), _normal),
                     ("v_proj", (d, kv), _normal),
                     ("o_proj", (d, d), _normal))
        ff = (("ffn_in", (d, 2 * self.shared_intermediate_size), _normal),
              ("ffn_out", (self.shared_intermediate_size, d), _normal))
        return norms + mixer + ff

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout
        d = self.hidden_size
        kinds = [self.layer_types[layer] for layer in self.layer_ids]
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer kinds {sorted(set(kinds))}: only "
                             "'mamba' and 'attention' are known")
        cfg = dict(head_dim=d // self.num_heads, eps=self.rms_norm_eps,
                   attn_block=self.attn_block,
                   attention_multiplier=self.attention_multiplier,
                   residual_multiplier=self.residual_multiplier,
                   mamba_heads=self.mamba_n_heads,
                   mamba_head_dim=self.mamba_d_head,
                   mamba_groups=self.mamba_n_groups,
                   mamba_state=self.mamba_d_state,
                   mamba_chunk=self.mamba_chunk_size)

        def forward(embedding, layers, final):
            x, routing = decoder.run(
                layers, decoder.embed(embedding, tokens,
                                      self.embedding_multiplier),
                step=lambda p, x, layer: _layer(
                    p, x, kind=self.layer_types[layer], cfg=cfg))
            return (rms_norm(x, final["norm_scale"], cfg["eps"])
                    / self.logits_scaling, routing)

        return decoder.decode(self, tokens, forward, specs=self._specs,
                              final=(("norm_scale", (d,), _ones),))
