"""The decoder stack the language models (``sambay``, ``granite_hybrid``,
``lfm2_moe``, ``deepseek_v3``, ``qwen3_next``) run through: their parameter
layout, their layer loop and their output, written once. A model is a flax
module with ``vocab_size``, ``hidden_size``, ``layer_ids`` (the published
layers held) and ``return_logits``; it keeps its layers' leaves and function,
its final norm and its embedding's scale. Every layer is a pure function of
its parameter tree, rematerialised whole (``jax.checkpoint``): at 2,048
positions and the published widths the backward pass holds one layer's
activations. The output is a :class:`TiedHead`, or a :class:`RoutedTiedHead`
for a model with routed experts, which the ``lm_rows`` task head scores in
blocks of positions; with ``return_logits``, the logits.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.common import Leaves


class TiedHead(NamedTuple):
    """What a language model with a tied output head hands its task head in
    place of logits: the final hidden states ``[B, T, d]`` and the embedding
    ``[V, d]`` whose rows score them. The head then forms the logits
    ``hidden @ embedding.T`` in blocks of positions and never holds
    ``[B, T, V]`` at once."""

    hidden: jnp.ndarray
    embedding: jnp.ndarray


class RoutedTiedHead(NamedTuple):
    """A :class:`TiedHead` of a model with routed experts: beside the hidden
    states and the embedding, ``expert_load [B, sparse layers, experts
    held]`` - per row and sparse layer the (token, choice) pairs that landed
    on each expert held here - and ``block_rows [sparse layers]``, the rows
    each layer's grouped products ran over all the rows (padding included).
    ``lm_rows_head`` turns them into three stat sums."""

    hidden: jnp.ndarray
    embedding: jnp.ndarray
    expert_load: jnp.ndarray
    block_rows: jnp.ndarray


def embed(embedding, tokens, scale: Optional[float] = None):
    """The rows of ``embedding`` the ``tokens`` name, times ``scale``."""
    with jax.named_scope("fedml.embed"):
        x = embedding[tokens]
        return x if scale is None else scale * x


def run(layers, x, *, step, routes: bool = False):
    """``x`` through the held ``layers`` (leaves by published index) in
    order, each layer ``step(p, x, layer)`` rematerialised whole. Where the
    model ``routes``, a step returns ``(x, load, rows)``: ``load [B, held]``
    the pairs on each held expert and ``rows`` the rows the grouped products
    ran, both None for a dense layer. Returns ``x`` and every sparse layer's
    ``(load, rows)`` as float32, gathered in two lists."""
    loads, block_rows = [], []
    for layer, p in layers.items():
        x = jax.checkpoint(functools.partial(step, layer=layer))(p, x)
        if routes:
            x, load, rows = x
            if load is not None:
                loads.append(load.astype(jnp.float32))
                block_rows.append(rows.astype(jnp.float32))
    return x, (loads, block_rows)


def decode(module, tokens, forward, *, specs, final, untied: bool = False,
           experts: Optional[Tuple[int, int]] = None):
    """``module``'s output for ``tokens [B, T]``.

    Declares, in the order that sets ``init``'s random stream:
    ``embedding [V, d]``; ``specs(layer)`` for every held layer, named
    ``layer_{NN}`` by its published index; ``final`` as ``final_norm``; and
    ``lm_head [V, d]`` where the head is ``untied``. Then ``forward(embedding,
    layers, final)`` gives ``(hidden [B, T, d], run's routing)``. A model
    with routed experts gives ``experts = (sparse layers held, experts held
    a layer)`` and returns a :class:`RoutedTiedHead` even with no sparse
    layer held."""
    d, vocab = module.hidden_size, module.vocab_size
    normal = nn.initializers.normal(0.02)
    embedding = module.param("embedding", normal, (vocab, d))
    layers = {layer: Leaves(specs(layer), name=f"layer_{layer:02d}")()
              for layer in module.layer_ids}
    final = Leaves(final, name="final_norm")()
    head = module.param("lm_head", normal, (vocab, d)) if untied else embedding
    if module.is_initializing():
        # the parameters are declared; their shapes do not depend on the
        # tokens, so ``init`` need not run the layers eagerly
        if module.return_logits:
            return jnp.zeros(tokens.shape + (vocab,))
        hidden = jnp.zeros(tokens.shape + (d,), embedding.dtype)
        routing = ([], [])
    else:
        hidden, routing = forward(embedding, layers, final)
        if module.return_logits:
            return jnp.einsum("btd,vd->btv", hidden, head)
    if experts is None:
        return TiedHead(hidden, head)
    (loads, block_rows), (sparse, held) = routing, experts
    if not loads:  # at ``init``, or no sparse layer held
        return RoutedTiedHead(
            hidden, head, jnp.zeros((tokens.shape[0], sparse, held),
                                    jnp.float32),
            jnp.zeros((sparse,), jnp.float32))
    return RoutedTiedHead(hidden, head, jnp.stack(loads, axis=1),
                          jnp.stack(block_rows))
