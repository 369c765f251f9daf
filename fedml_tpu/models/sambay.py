"""SambaY: the decoder-hybrid-decoder language model of
Phi-4-mini-flash-reasoning (arXiv:2507.06607; ``model_type`` ``phi4flash``).

A *self-decoder* of (Mamba-1, sliding-window attention) pairs ends in a
boundary pair - a Mamba layer whose scan output is kept as the memory ``m``
and a full-attention layer whose keys and values are kept - and a
*cross-decoder* of (gated memory unit, cross attention) pairs reads those
two: it has no recurrence and no key/value projection of its own. Attention
is differential (arXiv:2410.05258). There is no positional encoding; order
comes from the state-space layers. Embedding and output head are tied.

Every layer is ``x += Mix(LN(x)); x += W_down(silu(g) * p)`` with ``[g, p] =
W_gate_up LN'(x)``. The mixer of published layer ``l`` of ``L``:

========================  =====================================================
``l`` even, ``l <= L/2``  Mamba-1 (``l == L/2`` also emits ``m``, its ``y``
                          before the gate)
``l`` odd,  ``l <  L/2``  differential attention, keys at most ``window - 1``
                          back
``l == L/2 + 1``          differential attention, full causal; emits K, V
``l`` odd,  ``l >  L/2+1``  cross: queries only, K and V of layer ``L/2 + 1``
``l`` even, ``l >  L/2``  gated memory unit ``W_2(m * silu(W_1 s))``
========================  =====================================================

``layer_ids`` says which published layers this instance holds (a cut in
depth keeps the published indices, which set each attention layer's
``lambda_init``), ``vocab_size`` how many rows of the embedding (a
vocabulary-parallel share is a smaller vocabulary: ids, logits and loss are
over the rows held).

The model runs through the decoder stack of ``models/decoder.py``, one row
at a time, with the two hand-ons carried from layer to layer. The scan is
``ops/selective_scan.py``, the attention ``ops/block_attention.py``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import decoder
from fedml_tpu.models.common import (Spec, dt_bias_init as _dt_bias,
                                     uniform_init as _uniform)
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.selective_scan import selective_scan


# -- initialisers ---------------------------------------------------------------

_normal = nn.initializers.normal(0.02)
_zeros = nn.initializers.zeros
_ones = nn.initializers.ones


def _a_log(key, shape, dtype=jnp.float32):
    """``A = -(1..N)`` in every channel (Mamba's S4D-real start)."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


# -- the arithmetic -------------------------------------------------------------

def _layer_norm(p, name: str, x, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p[name + "_scale"]
            + p[name + "_bias"])


def _mamba(p, s, cfg):
    """``(Mix, y before the gate)`` for one sequence ``s [T, d]``."""
    d_state, rank = cfg["d_state"], cfg["dt_rank"]
    a, z = jnp.split(s @ p["in_proj"], 2, axis=-1)
    taps = p["conv_kernel"].shape[0]
    shifted = jnp.pad(a, ((taps - 1, 0), (0, 0)))
    conv = sum(shifted[j:j + a.shape[0]] * p["conv_kernel"][j]
               for j in range(taps))
    u = jax.nn.silu(conv + p["conv_bias"])
    dbc = u @ p["x_proj"]
    delta = jax.nn.softplus(dbc[:, :rank] @ p["dt_proj"] + p["dt_bias"])
    y = selective_scan(delta, u, dbc[:, rank:rank + d_state],
                       dbc[:, rank + d_state:], -jnp.exp(p["a_log"]),
                       chunk=cfg["scan_chunk"], lanes=cfg["scan_lanes"])
    y = y + p["d_skip"] * u
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def lambda_init(layer: int) -> float:
    """The differential attention's starting lambda of published layer
    ``layer`` (arXiv:2410.05258, section 2.1, 0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@jax.named_scope("fedml.diff_attention")
def _diff_attention(p, q, k, v, layer: int, window: Optional[int], cfg):
    """Differential attention of one sequence: ``q [T, Hq*D]``, ``k``,
    ``v [T, Hk*D]`` -> ``[T, Hq*D]`` before the output projection.

    Adjacent heads pair up, ``(q1, q2)`` and ``(k1, k2)``; a pair's values
    are its two value heads side by side, ``2D`` wide. Query pair ``j``
    reads key/value pair ``j // (Hq / Hk)``."""
    length, dim = q.shape[0], cfg["head_dim"]
    q = q.reshape(length, -1, 2, dim)
    k = k.reshape(length, -1, 2, dim)
    pairs, kv_pairs = q.shape[1], k.shape[1]
    v = v.reshape(length, kv_pairs, 2 * dim)
    # the first softmax's heads, then the second's: of the 2 * pairs query
    # heads, head j reads key head j // (pairs / kv_pairs) of 2 * kv_pairs
    def heads(first, second):
        return jnp.swapaxes(jnp.concatenate([first, second], axis=1), 0, 1)

    out = causal_attention(
        heads(q[:, :, 0], q[:, :, 1]), heads(k[:, :, 0], k[:, :, 1]),
        heads(v, v), scale=dim ** -0.5, window=window,
        block=cfg["attn_block"])
    first, second = out[:pairs], out[pairs:]  # [pairs, T, 2D] each
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lambda_init(layer))
    o = first - lam * second
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg["eps"]) * p["subln_scale"]
    o = o * (1.0 - lambda_init(layer))
    return jnp.swapaxes(o, 0, 1).reshape(length, -1)


@jax.named_scope("fedml.gmu")
def _gmu(p, s, memory):
    return (memory * jax.nn.silu(s @ p["gmu_in"])) @ p["gmu_out"]


@jax.named_scope("fedml.mlp")
def _mlp(p, s):
    gate, up = jnp.split(s @ p["gate_up_proj"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["down_proj"]


def _layer(p, x, memory, kv, *, kind: str, layer: int, cfg):
    """One layer on one sequence; returns ``(x, memory, kv)`` with the two
    hand-ons replaced where this layer makes them."""
    s = _layer_norm(p, "norm1", x, cfg["eps"])
    if kind == "mamba":
        mix, y = _mamba(p, s, cfg)
        if layer == cfg["boundary"]:
            memory = y
    elif kind == "gmu":
        mix = _gmu(p, s, memory)
    else:
        q_width = cfg["num_heads"] * cfg["head_dim"]
        if kind == "cross":
            q = s @ p["q_proj"] + p["q_bias"]
            k, v = kv
        else:
            kv_width = cfg["num_kv_heads"] * cfg["head_dim"]
            q, k, v = jnp.split(s @ p["qkv_proj"] + p["qkv_bias"],
                                [q_width, q_width + kv_width], axis=-1)
            if kind == "full":
                kv = (k, v)
        window = cfg["window"] if kind == "window" else None
        mix = (_diff_attention(p, q, k, v, layer, window, cfg)
               @ p["o_proj"] + p["o_bias"])
    x = x + mix
    return x + _mlp(p, _layer_norm(p, "norm2", x, cfg["eps"])), memory, kv


class SambaYLM(nn.Module):
    """See the module docstring. Defaults are the published widths of
    Phi-4-mini-flash-reasoning; sizes its ``config.json`` does not give are
    the Mamba family's defaults (``d_state`` 16, ``d_conv`` 4, ``expand`` 2,
    ``dt_rank`` ``ceil(d / 16)``)."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_ids: Tuple[int, ...] = tuple(range(32))
    published_layers: int = 32
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    layer_norm_eps: float = 1e-5
    scan_chunk: int = 512
    scan_lanes: int = 16
    attn_block: int = 512
    return_logits: bool = False

    def kind(self, layer: int) -> str:
        half = self.published_layers // 2
        if layer % 2 == 0:
            return "mamba" if layer <= half else "gmu"
        if layer < half:
            return "window"
        return "full" if layer == half + 1 else "cross"

    def _specs(self, layer: int) -> Spec:
        kind = self.kind(layer)
        d, inner = self.hidden_size, self.expand * self.hidden_size
        head_dim = d // self.num_heads
        kv = self.num_kv_heads * head_dim
        rank = self.dt_rank or -(-d // 16)
        norms = tuple((f"norm{i}_{part}", (d,), init) for i in (1, 2)
                      for part, init in (("scale", _ones), ("bias", _zeros)))
        mlp = (("gate_up_proj", (d, 2 * self.intermediate_size), _normal),
               ("down_proj", (self.intermediate_size, d), _normal))
        lambdas = tuple((f"lambda_{n}", (head_dim,),
                         nn.initializers.normal(0.1))
                        for n in ("q1", "k1", "q2", "k2"))
        attention = lambdas + (("subln_scale", (2 * head_dim,), _ones),
                               ("o_proj", (d, d), _normal),
                               ("o_bias", (d,), _zeros))
        mixer = {
            "mamba": (
                ("in_proj", (d, 2 * inner), _normal),
                ("conv_kernel", (self.d_conv, inner),
                 _uniform(self.d_conv ** -0.5)),
                ("conv_bias", (inner,), _uniform(self.d_conv ** -0.5)),
                ("x_proj", (inner, rank + 2 * self.d_state), _normal),
                ("dt_proj", (rank, inner), _uniform(rank ** -0.5)),
                ("dt_bias", (inner,), _dt_bias),
                ("a_log", (inner, self.d_state), _a_log),
                ("d_skip", (inner,), _ones),
                ("out_proj", (inner, d), _normal)),
            "gmu": (("gmu_in", (d, inner), _normal),
                    ("gmu_out", (inner, d), _normal)),
            "cross": (("q_proj", (d, d), _normal),
                      ("q_bias", (d,), _zeros)) + attention,
        }
        qkv = (("qkv_proj", (d, d + 2 * kv), _normal),
               ("qkv_bias", (d + 2 * kv,), _zeros)) + attention
        return norms + mixer.get(kind, qkv) + mlp

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout (embd_pdrop, resid_pdrop 0)
        d = self.hidden_size
        half = self.published_layers // 2
        kinds = [self.kind(layer) for layer in self.layer_ids]
        if (("gmu" in kinds and half not in self.layer_ids)
                or ("cross" in kinds and half + 1 not in self.layer_ids)):
            raise ValueError(
                f"layers {self.layer_ids} hold a cross-decoder layer without "
                f"the boundary pair ({half}, {half + 1}) it reads")
        cfg = dict(d_state=self.d_state,
                   dt_rank=self.dt_rank or -(-d // 16),
                   head_dim=d // self.num_heads, num_heads=self.num_heads,
                   num_kv_heads=self.num_kv_heads, window=self.sliding_window,
                   boundary=half, eps=self.layer_norm_eps,
                   scan_chunk=self.scan_chunk, scan_lanes=self.scan_lanes,
                   attn_block=self.attn_block)

        def forward(embedding, layers, final):
            def sequence(ids):  # (x, memory, kv) from layer to layer
                (x, _, _), routing = decoder.run(
                    layers, (decoder.embed(embedding, ids), None, None),
                    step=lambda p, state, layer: _layer(
                        p, *state, kind=self.kind(layer), layer=layer,
                        cfg=cfg))
                return _layer_norm(final, "norm1", x, cfg["eps"]), routing

            return jax.vmap(sequence)(tokens)

        return decoder.decode(self, tokens, forward, specs=self._specs,
                              final=(("norm1_scale", (d,), _ones),
                                     ("norm1_bias", (d,), _zeros)))
