"""LFM2-MoE: the hybrid decoder of LFM2-8B-A1B (``model_type`` ``lfm2_moe``):
gated short convolutions and grouped-query attention, a dense SwiGLU feed
forward in the leading layers and routed SwiGLU experts in the others.

Every layer is ``x += Op(RMSNorm(x)); x += FF(RMSNorm(x))``. A layer's kind
and feed forward are read from its *published* index ``l`` (``layer_ids``
says which published layers this instance holds, so a cut in depth moves no
layer's kind):

* ``layer_types[l] == "conv"`` - gated short convolution: ``[B, C, u] =
  W_in s``; ``v = B * u``; ``c_t = sum_j k_j * v_{t-j}`` per channel over
  ``conv_L_cache`` taps (causal, zeros before the row); ``Op = W_out(C *
  c)``. No bias.
* ``"full_attention"`` - grouped-query attention: ``q`` and ``k`` RMSNorm-ed
  over each head (learned scale), rotary positions (rotate-half over the
  whole head), causal softmax at ``head_dim ** -0.5``, ``W_o``. No bias.
* ``l < num_dense_layers`` - ``FF = W_2(silu(W_1 s) * W_3 s)`` at
  ``intermediate_size``; otherwise the sparse block of
  ``ops/moe.py::routed_experts``: ``num_experts`` experts of
  ``moe_intermediate_size``, ``num_experts_per_tok`` a token chosen by
  ``sigmoid`` scores plus a selection bias, weights normalised over the
  chosen.

``experts_held = (first, count)`` is this chip's share of every sparse block
under expert parallelism: the router and its bias keep their published width
and the three expert leaves hold ``count`` experts; the block adds its own
experts' part of the result and nothing for the others. ``vocab_size`` is
the rows of the tied embedding held (a vocabulary-parallel share is a
smaller vocabulary). The expert bias is a parameter leaf with no gradient
path - it only selects - so SGD and FedAvg leave it as initialised: drawn
once, from a fixed key and the layer's published index, the same in every
run (the published checkpoint's is the outcome of a balancing rule its
config does not give, and none is invented here).

The model runs through the decoder stack of ``models/decoder.py``, whose
routed output carries, per row and sparse layer, the (token, choice) pairs
that landed on each held expert and, per sparse layer, the rows the grouped
products ran.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import decoder
from fedml_tpu.models.common import Spec, expert_bias_init, rms_norm, rotary
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.moe import held_slice, routed_experts

#: LFM2-8B-A1B's published pattern: full attention at 2, 6, 10, 14, 18, 21
LFM2_8B_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))

_normal = nn.initializers.normal(0.02)
_ones = nn.initializers.ones


@jax.named_scope("fedml.short_conv")
def _short_conv(p, s):
    """``s [B, T, d]`` -> the operator's output before the residual."""
    gate_in, gate_out, u = jnp.split(s @ p["in_proj"], 3, axis=-1)
    v = gate_in * u
    taps, length = p["conv_kernel"].shape[0], s.shape[1]
    shifted = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(shifted[:, taps - 1 - j:taps - 1 - j + length]
               * p["conv_kernel"][j] for j in range(taps))
    return (gate_out * conv) @ p["out_proj"]


@jax.named_scope("fedml.attention")
def _attention(p, s, cfg):
    rows, length, _ = s.shape
    dim = cfg["head_dim"]

    def heads(w, scale=None):
        h = (s @ w).reshape(rows, length, -1, dim)
        h = jnp.swapaxes(h, 1, 2)  # [B, H, T, D]
        if scale is None:
            return h
        return rotary(rms_norm(h, scale, cfg["eps"]), cfg["rope_theta"])

    out = jax.vmap(functools.partial(
        causal_attention, scale=dim ** -0.5, block=cfg["attn_block"]))(
            heads(p["q_proj"], p["q_norm_scale"]),
            heads(p["k_proj"], p["k_norm_scale"]), heads(p["v_proj"]))
    return jnp.swapaxes(out, 1, 2).reshape(rows, length, -1) @ p["o_proj"]


@jax.named_scope("fedml.mlp")
def _dense_ff(p, s):
    return (jax.nn.silu(s @ p["ffn_w1"]) * (s @ p["ffn_w3"])) @ p["ffn_w2"]


def _layer(p, x, *, kind: str, dense: bool, cfg):
    """One layer on a batch of rows ``x [B, T, d]``; returns ``(x, load,
    rows)``, ``load [B, held]`` the pairs on each held expert and ``rows``
    the rows the grouped products' block loops ran (both None for a dense
    layer)."""
    s = rms_norm(x, p["operator_norm_scale"], cfg["eps"])
    x = x + (_short_conv(p, s) if kind == "conv" else _attention(p, s, cfg))
    s = rms_norm(x, p["ffn_norm_scale"], cfg["eps"])
    if dense:
        return x + _dense_ff(p, s), None, None
    with jax.named_scope("fedml.moe"):
        y, load, rows = routed_experts(
            s, p["router"], p.get("expert_bias"), p["experts_w1"],
            p["experts_w3"], p["experts_w2"], top_k=cfg["top_k"],
            experts_held=cfg["experts_held"], norm_topk=cfg["norm_topk"],
            scale=cfg["scale"])
    return x + y, load, rows


class Lfm2MoeLM(nn.Module):
    """See the module docstring. Defaults are LFM2-8B-A1B's published
    sizes, every expert held."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 32)
    layer_ids: Tuple[int, ...] = tuple(range(24))
    layer_types: Tuple[str, ...] = LFM2_8B_LAYER_TYPES
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    attn_block: int = 512
    return_logits: bool = False

    def _specs(self, layer: int) -> Spec:
        d, head_dim = self.hidden_size, self.hidden_size // self.num_heads
        kv = self.num_kv_heads * head_dim
        held, width = self.experts_held[1], self.moe_intermediate_size
        norms = (("operator_norm_scale", (d,), _ones),
                 ("ffn_norm_scale", (d,), _ones))
        if self.layer_types[layer] == "conv":
            op = (("in_proj", (d, 3 * d), _normal),
                  ("conv_kernel", (self.conv_L_cache, d), _normal),
                  ("out_proj", (d, d), _normal))
        else:
            op = (("q_proj", (d, d), _normal), ("k_proj", (d, kv), _normal),
                  ("v_proj", (d, kv), _normal),
                  ("q_norm_scale", (head_dim,), _ones),
                  ("k_norm_scale", (head_dim,), _ones),
                  ("o_proj", (d, d), _normal))
        if layer < self.num_dense_layers:
            ff = (("ffn_w1", (d, self.intermediate_size), _normal),
                  ("ffn_w3", (d, self.intermediate_size), _normal),
                  ("ffn_w2", (self.intermediate_size, d), _normal))
        else:
            ff = (("router", (d, self.num_experts), _normal),
                  ("experts_w1", (held, d, width), _normal),
                  ("experts_w3", (held, d, width), _normal),
                  ("experts_w2", (held, width, d), _normal))
            if self.use_expert_bias:
                ff += (("expert_bias", (self.num_experts,),
                        expert_bias_init(layer)),)
        return norms + op + ff

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout
        d = self.hidden_size
        cfg = dict(head_dim=d // self.num_heads, eps=self.norm_eps,
                   rope_theta=self.rope_theta, attn_block=self.attn_block,
                   top_k=self.num_experts_per_tok,
                   experts_held=held_slice(self.experts_held,
                                           self.num_experts),
                   norm_topk=self.norm_topk_prob,
                   scale=self.routed_scaling_factor)

        def forward(embedding, layers, final):
            x, routing = decoder.run(
                layers, decoder.embed(embedding, tokens), routes=True,
                step=lambda p, x, layer: _layer(
                    p, x, kind=self.layer_types[layer],
                    dense=layer < self.num_dense_layers, cfg=cfg))
            return rms_norm(x, final["norm_scale"], cfg["eps"]), routing

        sparse = sum(layer >= self.num_dense_layers
                     for layer in self.layer_ids)
        return decoder.decode(self, tokens, forward, specs=self._specs,
                              final=(("norm_scale", (d,), _ones),),
                              experts=(sparse, cfg["experts_held"][1]))
