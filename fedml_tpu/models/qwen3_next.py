"""The ``qwen3_next`` decoder as Qwen3-Next-80B-A3B-Instruct publishes it:
Gated DeltaNet linear attention in three layers of every four, gated softmax
attention in the fourth, and in every layer routed SwiGLU experts beside one
gated shared expert.

Every layer is ``x += Mix(RMSNorm0(x)); x += Sparse(RMSNorm0(x))`` with the
zero-centred ``RMSNorm0(x; w) = x / rms(x) * (1 + w)``. A layer's mixer is
read from its *published* index ``l`` (``layer_ids`` says which published
layers this instance holds, so a cut in depth moves no layer's kind): full
attention where ``(l + 1) % full_attention_interval == 0``, else Gated
DeltaNet.

* Gated DeltaNet (``Hk`` key heads of ``dk``, ``H`` value heads of ``dv``,
  ``r = H / Hk``): ``s W_qkvz`` is, per key head, ``[q | k | r values | r
  gates z]`` and ``s W_ba`` per key head ``[r betas | r decays]`` (the
  published per-key-head layout); ``[q | k | v]`` pass a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps without bias and a SiLU;
  ``q`` and ``k`` are L2-normed a head (epsilon 1e-6) and ``q`` scaled by
  ``dk ** -0.5``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; value head ``j`` reads key head ``j // r`` through
  ``ops/gated_delta.py``; the output is RMS-normed a head (a scale shared by
  the heads) *and then* gated by ``silu(z)``, and ``W_out`` maps it back.
* Gated attention (``num_attention_heads`` query heads, ``num_key_value_heads``
  key/value heads of ``head_dim``, no bias): ``s W_q`` is ``[q | gate]`` a
  head; ``q`` and ``k`` are zero-centred-RMS-normed a head; rotary positions
  (rotate-half) over the first ``partial_rotary_factor`` of each head's
  channels; causal softmax at ``head_dim ** -0.5``, query head ``h`` reading
  key/value head ``h // (Hq / Hkv)``; the heads' outputs times
  ``sigmoid(gate)``, then ``W_o``.
* Sparse: ``ops/moe.py::routed_experts`` (``num_experts`` experts of
  ``moe_intermediate_size``, ``num_experts_per_tok`` a token by a *softmax*
  over all the experts, the chosen weights normalised over the chosen) plus
  ``sigmoid(s w_sg) W_2(silu(W_1 s) * W_3 s)``, the shared expert of
  ``shared_expert_intermediate_size`` every token passes behind a gate of its
  own.

``experts_held = (first, count)`` is this chip's share of every sparse block
under expert parallelism, as in ``models/deepseek_v3.py``: the router keeps
its published width, the expert leaves hold ``count`` experts, the block adds
its own experts' part; the shared expert is computed whole here as on every
chip. ``vocab_size`` is the rows held of the embedding and of the *untied*
head. The multi-token-prediction module the family describes is not here: no
key of the config names it and the causal-LM loss does not run it.

The model runs through the decoder stack of ``models/decoder.py``, with the
head's own leaf scoring the hidden states.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import decoder
from fedml_tpu.models.common import Spec, rms_norm, rotary, uniform_init
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.gated_delta import gated_delta_rule
from fedml_tpu.ops.moe import held_slice, routed_experts

_normal = nn.initializers.normal(0.02)
_ones = nn.initializers.ones
_zeros = nn.initializers.zeros


def _a_log(key, shape, dtype=jnp.float32):
    """``log(A)``, ``A`` uniform in (0, 16) a value head (the family's
    start; ``granite_hybrid._a_log`` draws from 1..16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype,
                                      jnp.finfo(dtype).tiny, 16.0))


def norm0(x, weight, eps: float):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + weight)``."""
    return rms_norm(x, 1.0 + weight, eps)


def _l2_norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def _norm_then_gate(out, z, scale, eps: float):
    """The linear block's output: RMS-normed a head, *then* gated by
    ``silu(z)`` (Mamba-2 gates first)."""
    return rms_norm(out, scale, eps) * jax.nn.silu(z)


def _output_gate(out, gate):
    """The full-attention block's heads times ``sigmoid(gate)``."""
    return out * jax.nn.sigmoid(gate)


@jax.named_scope("fedml.gated_delta")
def _gated_delta(p, s, cfg):
    """``s [B, T, d]`` -> the linear-attention block's output before the
    residual."""
    rows, length, _ = s.shape
    key_heads, heads = cfg["key_heads"], cfg["value_heads"]
    dk, dv, group = cfg["dk"], cfg["dv"], heads // key_heads
    qkvz = (s @ p["in_proj_qkvz"]).reshape(rows, length, key_heads, -1)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + group * dv], axis=-1)
    b, a = jnp.split((s @ p["in_proj_ba"]).reshape(
        rows, length, key_heads, 2 * group), 2, axis=-1)
    mixed = jnp.concatenate([x.reshape(rows, length, -1) for x in (q, k, v)],
                            axis=-1)
    taps = p["conv_kernel"].shape[0]
    shifted = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j weighs the input taps - 1 - j positions back (torch's order)
    mixed = jax.nn.silu(sum(shifted[:, j:j + length] * p["conv_kernel"][j]
                            for j in range(taps)))
    q, k, v = jnp.split(mixed, [key_heads * dk, 2 * key_heads * dk], axis=-1)
    q = _l2_norm(q.reshape(rows, length, key_heads, dk)) * dk ** -0.5
    k = _l2_norm(k.reshape(rows, length, key_heads, dk))
    beta = jax.nn.sigmoid(b.reshape(rows, length, heads))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.reshape(rows, length, heads).astype(jnp.float32) + p["dt_bias"])
    with jax.named_scope("fedml.gated_delta_core"):
        out = jax.vmap(gated_delta_rule)(
            q, k, v.reshape(rows, length, heads, dv), g, beta)
    out = _norm_then_gate(out, z.reshape(rows, length, heads, dv),
                          p["norm_scale"], cfg["eps"])
    return out.reshape(rows, length, heads * dv) @ p["out_proj"]


@jax.named_scope("fedml.gated_attention")
def _gated_attention(p, s, cfg):
    """``s [B, T, d]`` -> the full-attention block's output before the
    residual."""
    rows, length, _ = s.shape
    dim, turned = cfg["head_dim"], cfg["rotary_dim"]

    def heads(x):  # [B, T, H * D] -> [B, H, T, D]
        return jnp.swapaxes(x.reshape(rows, length, -1, dim), 1, 2)

    q, gate = jnp.split((s @ p["q_proj"]).reshape(
        rows, length, -1, 2 * dim), 2, axis=-1)
    q = norm0(jnp.swapaxes(q, 1, 2), p["q_norm_scale"], cfg["eps"])
    k = norm0(heads(s @ p["k_proj"]), p["k_norm_scale"], cfg["eps"])

    def rope(x):
        return jnp.concatenate([rotary(x[..., :turned], cfg["rope_theta"]),
                                x[..., turned:]], axis=-1)

    out = jax.vmap(functools.partial(causal_attention, scale=dim ** -0.5))(
        rope(q), rope(k), heads(s @ p["v_proj"]))
    out = jnp.swapaxes(out, 1, 2).reshape(rows, length, -1)
    return _output_gate(out, gate.reshape(out.shape)) @ p["o_proj"]


def _swiglu(s, w1, w3, w2):
    return (jax.nn.silu(s @ w1) * (s @ w3)) @ w2


@jax.named_scope("fedml.shared_experts")
def _shared_expert(p, s):
    return jax.nn.sigmoid(s @ p["shared_gate"]) * _swiglu(
        s, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def _layer(p, x, *, full: bool, cfg):
    """One layer on a batch of rows ``x [B, T, d]``; returns ``(x, load,
    rows)``, ``load [B, held]`` the pairs on each held expert and ``rows``
    the rows the grouped products' block loops ran."""
    mixer = _gated_attention if full else _gated_delta
    x = x + mixer(p, norm0(x, p["input_norm_scale"], cfg["eps"]), cfg)
    s = norm0(x, p["post_attention_norm_scale"], cfg["eps"])
    with jax.named_scope("fedml.moe"):
        y, load, rows = routed_experts(
            s, p["router"], None, p["experts_w1"], p["experts_w3"],
            p["experts_w2"], top_k=cfg["top_k"],
            experts_held=cfg["experts_held"], norm_topk=cfg["norm_topk"],
            eps=0.0, score="softmax")
    return x + y + _shared_expert(p, s), load, rows


class Qwen3NextLM(nn.Module):
    """See the module docstring. Defaults are Qwen3-Next-80B-A3B-Instruct's
    published sizes, every expert held."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    experts_held: Tuple[int, int] = (0, 512)
    layer_ids: Tuple[int, ...] = tuple(range(48))
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    return_logits: bool = False

    def is_full(self, layer: int) -> bool:
        """Whether published layer ``layer`` is full attention."""
        return (layer + 1) % self.full_attention_interval == 0

    def _specs(self, layer: int) -> Spec:
        d, held = self.hidden_size, self.experts_held[1]
        width, shared = (self.moe_intermediate_size,
                         self.shared_expert_intermediate_size)
        if self.is_full(layer):
            heads, dim = self.num_attention_heads, self.head_dim
            kv = self.num_key_value_heads * dim
            mixer = (
                ("q_proj", (d, 2 * heads * dim), _normal),
                ("k_proj", (d, kv), _normal),
                ("v_proj", (d, kv), _normal),
                ("q_norm_scale", (dim,), _zeros),
                ("k_norm_scale", (dim,), _zeros),
                ("o_proj", (heads * dim, d), _normal))
        else:
            keys = self.linear_num_key_heads * self.linear_key_head_dim
            values = self.linear_num_value_heads * self.linear_value_head_dim
            taps = self.linear_conv_kernel_dim
            mixer = (
                ("in_proj_qkvz", (d, 2 * keys + 2 * values), _normal),
                ("in_proj_ba", (d, 2 * self.linear_num_value_heads), _normal),
                ("conv_kernel", (taps, 2 * keys + values),
                 uniform_init(taps ** -0.5)),
                ("dt_bias", (self.linear_num_value_heads,), _ones),
                ("A_log", (self.linear_num_value_heads,), _a_log),
                ("norm_scale", (self.linear_value_head_dim,), _ones),
                ("out_proj", (values, d), _normal))
        return (("input_norm_scale", (d,), _zeros),
                ("post_attention_norm_scale", (d,), _zeros)) + mixer + (
            ("router", (d, self.num_experts), _normal),
            ("experts_w1", (held, d, width), _normal),
            ("experts_w3", (held, d, width), _normal),
            ("experts_w2", (held, width, d), _normal),
            ("shared_gate", (d, 1), _normal),
            ("shared_w1", (d, shared), _normal),
            ("shared_w3", (d, shared), _normal),
            ("shared_w2", (shared, d), _normal))

    def cfg(self) -> dict:
        """What a layer's function reads of the module."""
        return dict(key_heads=self.linear_num_key_heads,
                    value_heads=self.linear_num_value_heads,
                    dk=self.linear_key_head_dim,
                    dv=self.linear_value_head_dim, head_dim=self.head_dim,
                    rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                    rope_theta=self.rope_theta, eps=self.rms_norm_eps,
                    top_k=self.num_experts_per_tok,
                    experts_held=held_slice(self.experts_held,
                                            self.num_experts),
                    norm_topk=self.norm_topk_prob)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout
        cfg = self.cfg()

        def forward(embedding, layers, final):
            x, routing = decoder.run(
                layers, decoder.embed(embedding, tokens), routes=True,
                step=lambda p, x, layer: _layer(
                    p, x, full=self.is_full(layer), cfg=cfg))
            return norm0(x, final["norm_scale"], cfg["eps"]), routing

        return decoder.decode(
            self, tokens, forward, specs=self._specs,
            final=(("norm_scale", (self.hidden_size,), _zeros),), untied=True,
            experts=(len(self.layer_ids), cfg["experts_held"][1]))
