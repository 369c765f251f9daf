"""The decoder stack of ``models/decoder.py`` as the five language models
run through it, at the sizes of their model tests: the parameter layout in
declaration order, an ``init`` that returns what ``apply`` returns without
running a layer, one rematerialised layer a held layer, and the routing
arrays exactly where a model routes. Traced and shaped, nothing compiled."""

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import RoutedTiedHead, TiedHead
from tests import (test_deepseek_v3_model, test_granite_hybrid_model,
                   test_lfm2_moe_model, test_qwen3_next_model,
                   test_sambay_model)

LFM2 = test_lfm2_moe_model.SMALL
#: case -> (model, its model test's sizes, untied head, sparse layers held
#: or None where the model does not route)
DECODERS = {
    "sambay": ("sambay", test_sambay_model.SMALL, False, None),
    "granite_hybrid": ("granite_hybrid", test_granite_hybrid_model.SMALL,
                       False, None),
    "lfm2_moe": ("lfm2_moe", LFM2, False, 3),
    # the leading layer alone: a routed model that holds no sparse layer
    "lfm2_moe_dense": ("lfm2_moe", {**LFM2, "layer_ids": (1,)}, False, 0),
    "deepseek_v3": ("deepseek_v3", test_deepseek_v3_model.SMALL, True, 2),
    "qwen3_next": ("qwen3_next", test_qwen3_next_model.SMALL, True, 2),
}
VOCAB, ROWS, LENGTH = 96, 2, 37


@pytest.mark.parametrize("case", sorted(DECODERS))
def test_the_stack_declares_shortcuts_rematerialises_and_routes(case):
    name, sizes, untied, sparse = DECODERS[case]
    module = create_model(name, output_dim=VOCAB, **sizes)
    tokens = jnp.zeros((ROWS, LENGTH), jnp.int32)
    order = []

    def init(tokens):
        out, variables = module.init_with_output(jax.random.key(0), tokens)
        order.extend(variables["params"])  # flax keeps declaration order
        return out, variables

    at_init, variables = jax.eval_shape(init, tokens)
    layers = [f"layer_{layer:02d}" for layer in sizes["layer_ids"]]
    assert order == (["embedding"] + layers + ["final_norm"]
                     + ["lm_head"] * untied)

    # init's zeros have apply's structure, shapes and dtypes
    out = jax.eval_shape(module.apply, variables, tokens)
    assert type(at_init) is type(out)
    assert (jax.tree.structure(at_init) == jax.tree.structure(out))
    assert ([(a.shape, a.dtype) for a in jax.tree.leaves(at_init)]
            == [(a.shape, a.dtype) for a in jax.tree.leaves(out)])

    # each held layer is one rematerialised call that takes all its leaves
    jaxpr = jax.make_jaxpr(module.apply)(variables, tokens).jaxpr
    remats = [{v for v in e.invars if not hasattr(v, "val")}
              for e in jaxpr.eqns if e.primitive.name == "remat2"]
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(
        (variables, tokens))[0]]  # ((0, "params", top key, ...), ...)
    for layer in layers:
        mine = {v for path, v in zip(paths, jaxpr.invars)
                if len(path) > 2 and path[2].key == layer}
        assert mine and sum(mine <= r for r in remats) == 1, layer
    assert sum(any(v in r for v in jaxpr.invars) for r in remats) == len(
        layers)

    if sparse is None:
        assert type(out) is TiedHead
        return
    held = sizes["experts_held"][1]
    assert type(out) is RoutedTiedHead
    assert out.expert_load.shape == (ROWS, sparse, held)
    assert out.block_rows.shape == (sparse,)
    assert out.expert_load.dtype == out.block_rows.dtype == jnp.float32
    head = variables["params"]["lm_head" if untied else "embedding"]
    assert out.embedding.shape == head.shape


@pytest.mark.parametrize("name,sizes,held", [
    ("lfm2_moe", LFM2, (6, 4)),
    ("deepseek_v3", test_deepseek_v3_model.SMALL, (12, 8)),
    ("qwen3_next", test_qwen3_next_model.SMALL, (12, 8)),
])
def test_an_expert_share_outside_the_experts_is_refused(name, sizes, held):
    module = create_model(name, output_dim=8, **{
        **sizes, "experts_held": held})
    with pytest.raises(ValueError, match="no slice"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
