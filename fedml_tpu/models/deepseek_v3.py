"""The ``deepseek_v3`` decoder as kanana-2-30b-a3b-instruct-2601 publishes it:
latent attention without a query bottleneck, a dense SwiGLU feed forward in
the leading layers and, in the others, routed SwiGLU experts beside shared
ones.

Every layer is ``x += MLA(RMSNorm(x)); x += FF(RMSNorm(x))``. A layer's feed
forward is read from its *published* index ``l`` (``layer_ids`` says which
published layers this instance holds, so a cut in depth moves no layer's
kind):

* latent attention (``num_heads`` heads, no bias, ``q_lora_rank`` null):
  ``q = s W_q`` is ``[q_nope | q_rope]`` a head (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``); ``[c | k_rope] = s W_kv_a`` is the latent of
  ``kv_lora_rank`` and ONE rope key of ``qk_rope_head_dim`` for all the
  heads; ``c`` is RMSNorm-ed and ``[k_nope | v] = c W_kv_b`` a head
  (``qk_nope_head_dim`` + ``v_head_dim``). Rotary positions cover the rope
  channels alone; they are stored as pairs ``(2i, 2i + 1)`` (the published
  ``rope_interleave``, the only reading here) and brought to half-split
  order before ``models/common.rotary``. A
  head's key is ``[k_nope_h | k_rope]``; causal softmax at ``(nope + rope)
  ** -0.5``; ``W_o`` over the heads' ``v_head_dim`` outputs. This is the
  decompressed (training) form; its causal core (``_core``) is the Pallas
  flash kernel of ``ops/flash_attention.py`` on a TPU, whose score blocks
  never leave VMEM, and ``ops/block_attention.py`` elsewhere. The form that
  scores in the latent space is a serving form and is not here.
* ``l < num_dense_layers`` (``first_k_dense_replace``) - ``FF = W_2(silu(W_1
  s) * W_3 s)`` at ``intermediate_size``; otherwise ``Routed(s) +
  Shared(s)``: ``ops/moe.py::routed_experts`` (``n_routed_experts`` experts
  of ``moe_intermediate_size``, ``num_experts_per_tok`` a token by
  ``sigmoid`` scores plus a selection bias, weights normalised over the
  chosen with an epsilon of 1e-20 and times ``routed_scaling_factor``) and
  one SwiGLU of ``n_shared_experts x moe_intermediate_size`` every token
  passes.

``experts_held = (first, count)`` is this chip's share of every sparse block
under expert parallelism, as in ``models/lfm2_moe.py``: the router and its
bias keep their published width, the three expert leaves hold ``count``
experts, and the block adds its own experts' part; the shared experts are
computed whole here as on every chip. ``vocab_size`` is the rows held of the
embedding and of the *untied* head (a vocabulary-parallel share). The
selection bias is a leaf with no gradient path, drawn once from a fixed key
and the layer's published index (``common.expert_bias_init``): the rule that
balances the published model's bias is not in its config, and none is
invented.

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
from fedml_tpu.models.common import Spec, expert_bias_init, rms_norm, rotary
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.flash_attention import flash_attention_heads
from fedml_tpu.ops.moe import held_slice, routed_experts
from fedml_tpu.utils import on_tpu

_normal = nn.initializers.normal(0.02)
_ones = nn.initializers.ones


def half_split(x):
    """Rope channels stored as pairs ``(2i, 2i + 1)`` brought to the order
    ``0, 2, .., D - 2, 1, 3, .., D - 1`` that rotate-half pairs up."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _rope(x, cfg):
    return rotary(half_split(x), cfg["rope_theta"])


#: the kernel's (query, key) blocks: the fastest pair at a row of 2,048
#: tokens, 32 heads of 192 | 128 on a v5e (PERF.md section 6, PR 40)
CORE_BLOCKS = (512, 1024)


def _core(q, k, v, scale):
    """Causal attention over head-major ``q``, ``k`` ``[B, H, T, D]``,
    ``v [B, H, T, Dv]``: on a TPU one Pallas flash kernel, forward and
    backward, with a block's scores in VMEM; elsewhere XLA's blockwise
    attention, whose arithmetic the CPU tests and the reference share."""
    if on_tpu():
        return flash_attention_heads(q, k, v, causal=True, scale=scale,
                                     block_q=CORE_BLOCKS[0],
                                     block_k=CORE_BLOCKS[1])
    return jax.vmap(functools.partial(causal_attention, scale=scale))(q, k, v)


@jax.named_scope("fedml.mla")
def _mla(p, s, cfg):
    """``s [B, T, d]`` -> the block's output before the residual."""
    rows, length, _ = s.shape
    nope, rope, v_dim = cfg["nope"], cfg["rope"], cfg["v_dim"]

    def heads(x, dim):  # [B, T, H * dim] -> [B, H, T, dim]
        return jnp.swapaxes(x.reshape(rows, length, -1, dim), 1, 2)

    q = heads(s @ p["q_proj"], nope + rope)
    latent, k_rope = jnp.split(s @ p["kv_a_proj"], [cfg["kv_rank"]], axis=-1)
    kv = heads(rms_norm(latent, p["kv_norm_scale"], cfg["eps"])
               @ p["kv_b_proj"], nope + v_dim)
    k_rope = _rope(k_rope[:, None], cfg)  # one key for all the heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, kv.shape[:-1] + (rope,))], axis=-1)
    with jax.named_scope("fedml.mla_core"):
        out = _core(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return jnp.swapaxes(out, 1, 2).reshape(rows, length, -1) @ p["o_proj"]


def _swiglu(s, w1, w3, w2):
    return (jax.nn.silu(s @ w1) * (s @ w3)) @ w2


@jax.named_scope("fedml.shared_experts")
def _shared_experts(p, s):
    return _swiglu(s, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def _layer(p, x, *, dense: bool, cfg):
    """One layer on a batch of rows ``x [B, T, d]``; returns ``(x, load,
    rows)``, ``load [B, held]`` the pairs on each held expert and ``rows``
    the rows the grouped products' block loops ran (both None for a dense
    layer)."""
    x = x + _mla(p, rms_norm(x, p["input_norm_scale"], cfg["eps"]), cfg)
    s = rms_norm(x, p["post_attention_norm_scale"], cfg["eps"])
    if dense:
        with jax.named_scope("fedml.mlp"):
            return (x + _swiglu(s, p["ffn_w1"], p["ffn_w3"], p["ffn_w2"]),
                    None, None)
    with jax.named_scope("fedml.moe"):
        y, load, rows = routed_experts(
            s, p["router"], p["expert_bias"], p["experts_w1"],
            p["experts_w3"], p["experts_w2"], top_k=cfg["top_k"],
            experts_held=cfg["experts_held"], norm_topk=cfg["norm_topk"],
            scale=cfg["scale"], eps=1e-20)
    return x + y + _shared_experts(p, s), load, rows


class DeepseekV3LM(nn.Module):
    """See the module docstring. Defaults are kanana-2-30b-a3b-instruct-
    2601's published sizes, every expert held."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    experts_held: Tuple[int, int] = (0, 128)
    layer_ids: Tuple[int, ...] = tuple(range(48))
    num_dense_layers: int = 1
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    return_logits: bool = False

    def _specs(self, layer: int) -> Spec:
        d, heads = self.hidden_size, self.num_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        held, width = self.experts_held[1], self.moe_intermediate_size
        shared = self.n_shared_experts * width
        block = (
            ("input_norm_scale", (d,), _ones),
            ("post_attention_norm_scale", (d,), _ones),
            ("q_proj", (d, heads * qk), _normal),
            ("kv_a_proj", (d, self.kv_lora_rank + self.qk_rope_head_dim),
             _normal),
            ("kv_norm_scale", (self.kv_lora_rank,), _ones),
            ("kv_b_proj", (self.kv_lora_rank, heads * (
                self.qk_nope_head_dim + self.v_head_dim)), _normal),
            ("o_proj", (heads * self.v_head_dim, d), _normal))
        if layer < self.num_dense_layers:
            return block + (
                ("ffn_w1", (d, self.intermediate_size), _normal),
                ("ffn_w3", (d, self.intermediate_size), _normal),
                ("ffn_w2", (self.intermediate_size, d), _normal))
        return block + (
            ("router", (d, self.n_routed_experts), _normal),
            ("expert_bias", (self.n_routed_experts,),
             expert_bias_init(layer)),
            ("experts_w1", (held, d, width), _normal),
            ("experts_w3", (held, d, width), _normal),
            ("experts_w2", (held, width, d), _normal),
            ("shared_w1", (d, shared), _normal),
            ("shared_w3", (d, shared), _normal),
            ("shared_w2", (shared, d), _normal))

    def attention_cores_in_kernel(self) -> int:
        """Core calls a row makes through the Pallas kernel (``_core``):
        one a layer on a TPU, none elsewhere."""
        return len(self.layer_ids) if on_tpu() else 0

    def cfg(self) -> dict:
        """What a layer's function reads of the module."""
        return dict(nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim,
                    v_dim=self.v_head_dim, kv_rank=self.kv_lora_rank,
                    eps=self.rms_norm_eps, rope_theta=self.rope_theta,
                    top_k=self.num_experts_per_tok,
                    experts_held=held_slice(self.experts_held,
                                            self.n_routed_experts),
                    norm_topk=self.norm_topk_prob,
                    scale=self.routed_scaling_factor)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout
        cfg = self.cfg()

        def forward(embedding, layers, final):
            x, routing = decoder.run(
                layers, decoder.embed(embedding, tokens), routes=True,
                step=lambda p, x, layer: _layer(
                    p, x, dense=layer < self.num_dense_layers, cfg=cfg))
            return rms_norm(x, final["norm_scale"], cfg["eps"]), routing

        sparse = sum(layer >= self.num_dense_layers
                     for layer in self.layer_ids)
        return decoder.decode(
            self, tokens, forward, specs=self._specs,
            final=(("norm_scale", (self.hidden_size,), _ones),), untied=True,
            experts=(sparse, cfg["experts_held"][1]))
