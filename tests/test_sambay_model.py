"""The SambaY hybrid decoder (Phi-4-mini-flash-reasoning) against its plain
reference, at small widths on the CPU: the same layer kinds under the
published layer indices (which set ``lambda_init``), seeded weights,
``highest`` precision. Also its two training-time operators (the chunked
selective scan, the blockwise windowed attention), the hand-ons of the
decoder-hybrid-decoder (layer 16's scan output, layer 17's keys and values)
and the vocabulary share."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.sambay import lambda_init
from fedml_tpu.ops.block_attention import causal_attention
from fedml_tpu.ops.selective_scan import (selective_scan,
                                          selective_scan_reference)
from fedml_tpu.trainer.tasks import TiedHead, lm_rows_head

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = (0, 1, 16, 17, 18, 19)
VOCAB, LENGTH = 96, 37
SMALL = dict(hidden_size=64, num_heads=8, num_kv_heads=4,
             intermediate_size=96, sliding_window=8, layer_ids=LAYERS,
             scan_chunk=16, scan_lanes=4, attn_block=16)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "hybrid_lm_local_sgd", os.path.join(
            ROOT, "benchmark", "references", "hybrid_lm_local_sgd.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _seeded(module, tokens, seed=1, noise=0.05):
    """Initial variables with every leaf perturbed, so that biases and
    scales that start at 0 or 1 take part."""
    variables = jax.jit(lambda t: module.init(jax.random.key(seed), t,
                                              train=False))(tokens[:1])
    leaves, treedef = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + noise * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def small():
    module = create_model("sambay", output_dim=VOCAB, **SMALL)
    rows = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (2, LENGTH + 1)))
    x, y = rows[:, :-1], rows[:, 1:]
    return module, _seeded(module, x), x, y


def _loss(module, params, x, y, mask):
    stats = lm_rows_head(module.apply({"params": params}, x), y, mask)
    return stats["loss_sum"] / stats["count"]


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


# -- the model against the reference -------------------------------------------

def test_logits_equal_the_references(small, reference):
    module, variables, x, _ = small
    got = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    hp = reference.hyperparameters(module)
    want = jax.jit(lambda p: jnp.stack([
        reference.logits_of(p, hp, row) for row in x]))(variables["params"])
    assert got.shape == (2, LENGTH, VOCAB)
    assert _rel(got, want) < 1e-5


def test_the_tied_head_in_blocks_equals_the_logits_head(small, monkeypatch):
    module, variables, x, y = small
    mask = jnp.asarray([1.0, 0.0])
    whole = jax.jit(lambda v: lm_rows_head(module.clone(
        return_logits=True).apply(v, x), y, mask))(variables)
    from fedml_tpu.trainer import tasks
    monkeypatch.setattr(tasks, "LOGIT_BLOCK", 16)  # 37 positions: 3 blocks
    blocked = jax.jit(lambda v: lm_rows_head(module.apply(v, x), y, mask))(
        variables)
    assert float(blocked["count"]) == 1.0
    for key in whole:
        np.testing.assert_allclose(blocked[key], whole[key], rtol=1e-6)


def test_loss_and_every_gradient_leaf_equal_the_references(small, reference):
    module, variables, x, y = small
    mask = jnp.ones(2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(module, p, x, y, mask)))(variables["params"])
    hp = reference.hyperparameters(module)

    def want_loss(p):
        return jnp.mean(jnp.stack([reference.row_mean_cross_entropy(
            reference.logits_of(p, hp, x[i]), y[i]) for i in range(2)]))

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(want_loss))(
        variables["params"])
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    errors = jax.tree.map(_rel, grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errors),
                key=lambda item: item[1])
    assert worst[1] < 1e-4, worst


def test_one_sgd_step_equals_the_references_step(small, reference):
    module, variables, x, y = small
    lr, mask = 0.1, jnp.asarray([1.0, 1.0])
    grads = jax.jit(jax.grad(lambda p: _loss(module, p, x, y, mask)))(
        variables["params"])
    ours = jax.tree.map(lambda p, g: p - lr * g, variables["params"], grads)
    step = reference.make_step(module, "lm_rows", {"lr": lr}, remat=True)
    theirs, loss_sum, count = jax.jit(
        lambda p: step(p, x, y, mask, None))(variables["params"])
    assert float(count) == 2.0
    np.testing.assert_allclose(
        float(loss_sum) / 2.0,
        float(jax.jit(lambda p: _loss(module, p, x, y, mask))(
            variables["params"])), rtol=1e-5)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), ours,
                         theirs)
    assert max(jax.tree.leaves(moved)) < 1e-6


def test_parameter_count_at_the_published_widths():
    module = create_model("sambay", output_dim=50016, layer_ids=list(LAYERS))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    sizes = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(group))
             for name, group in shapes["params"].items()}
    mlp, norms = 78_643_200, 10_240
    assert sizes["layer_00"] == sizes["layer_16"] == 41_241_600 + mlp + norms
    assert sizes["layer_01"] == sizes["layer_17"] == 19_668_864 + mlp + norms
    assert sizes["layer_18"] == 26_214_400 + mlp + norms
    assert sizes["layer_19"] == 13_112_704 + mlp + norms
    assert sizes["embedding"] == 50016 * 2560
    assert sum(sizes.values()) == 761_114_752
    # the whole published model: 9 x (Mamba, attention) + 7 x (GMU, cross)
    whole = (9 * (sizes["layer_00"] + sizes["layer_01"])
             + 7 * (sizes["layer_18"] + sizes["layer_19"])
             + sizes["final_norm"] + 200064 * 2560)
    assert whole == 3_852_562_944


@pytest.mark.parametrize("layer, kind", [
    (0, "mamba"), (14, "mamba"), (16, "mamba"), (1, "window"),
    (15, "window"), (17, "full"), (19, "cross"), (31, "cross"),
    (18, "gmu"), (30, "gmu")])
def test_layer_kinds_follow_the_published_index(layer, kind, reference):
    module = create_model("sambay", output_dim=8)
    assert module.kind(layer) == kind
    assert reference._kind(layer, 32) == kind


def test_lambda_init_uses_the_published_index():
    assert lambda_init(0) == pytest.approx(0.2)
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    assert lambda_init(1) < lambda_init(17) < lambda_init(19) < 0.8


def test_a_cross_decoder_layer_without_the_boundary_pair_is_refused():
    module = create_model("sambay", output_dim=8, **{
        **SMALL, "layer_ids": [0, 1, 18, 19]})
    with pytest.raises(ValueError, match="boundary pair"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


# -- the selective scan ---------------------------------------------------------

def _scan_inputs(length, d_inner=24, d_state=16, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    return (jax.nn.softplus(f(length, d_inner)), f(length, d_inner),
            f(length, d_state), f(length, d_state),
            -jnp.exp(0.3 * f(d_inner, d_state)))


@pytest.mark.parametrize("chunk, lanes", [(16, 4), (8, 1), (32, 32)])
@pytest.mark.parametrize("length", [5, 16, 37, 64])
def test_chunked_scan_equals_the_recurrence(length, chunk, lanes):
    args = _scan_inputs(length)
    got = selective_scan(*args, chunk=chunk, lanes=lanes)
    want = selective_scan_reference(*args)
    assert got.shape == want.shape == (length, 24)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("length", [11, 40])
def test_chunked_scan_gradients_equal_the_recurrences(length):
    args = _scan_inputs(length, seed=3)
    weight = jnp.asarray(np.random.RandomState(7).randn(length, 24),
                         jnp.float32)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=(0, 1, 2, 3, 4))(*args)

    got = through(lambda *a: selective_scan(*a, chunk=16, lanes=4))
    want = through(selective_scan_reference)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-4


def test_scan_refuses_a_chunk_the_lanes_do_not_divide():
    with pytest.raises(ValueError, match="multiple of lanes"):
        selective_scan(*_scan_inputs(8), chunk=12, lanes=8)


# -- the window ------------------------------------------------------------------

def test_a_key_512_back_is_barred_and_511_back_is_not():
    length, window, at = 600, 512, 599
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, length, 8), jnp.float32)
    k = jnp.asarray(rs.randn(1, length, 8), jnp.float32)
    v = jnp.asarray(rs.randn(1, length, 4), jnp.float32)

    @jax.jit
    def out(v):
        return causal_attention(q, k, v, scale=8 ** -0.5, window=window,
                                block=256)[:, at]

    base = out(v)
    barred = out(v.at[0, at - window].add(100.0))
    seen = out(v.at[0, at - window + 1].add(100.0))
    np.testing.assert_array_equal(barred, base)
    assert float(jnp.max(jnp.abs(seen - base))) > 1e-3
    # and without a window the same key is seen
    full = lambda v: causal_attention(  # noqa: E731
        q, k, v, scale=8 ** -0.5, block=256)[:, at]
    assert float(jnp.max(jnp.abs(
        full(v.at[0, at - window].add(100.0)) - full(v)))) > 1e-3


@pytest.mark.parametrize("window", [None, 8, 20])
@pytest.mark.parametrize("block", [16, 37, 64])
def test_blockwise_attention_equals_whole_score_matrices(window, block):
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(4, LENGTH, 8), jnp.float32)
    k = jnp.asarray(rs.randn(2, LENGTH, 8), jnp.float32)
    v = jnp.asarray(rs.randn(2, LENGTH, 6), jnp.float32)
    got = causal_attention(q, k, v, scale=0.3, window=window, block=block)
    pos = np.arange(LENGTH)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window
    for head in range(4):
        scores = jnp.where(seen, q[head] @ k[head // 2].T * 0.3, -jnp.inf)
        want = jax.nn.softmax(scores, axis=-1) @ v[head // 2]
        assert _rel(got[head], want) < 1e-5


# -- the hand-ons of the decoder-hybrid-decoder ---------------------------------

def _grads_with(small, silenced):
    """d loss / d parameters with the listed layers' own mixer outputs cut
    off (their output projections zeroed), so that what such a layer
    computes can reach the loss only through another layer that reads it."""
    module, variables, x, y = small
    params = jax.tree.map(lambda a: a, variables["params"])
    for layer, names in silenced.items():
        for name in names:
            params[layer][name] = jnp.zeros_like(params[layer][name])
    return jax.jit(jax.grad(
        lambda p: _loss(module, p, x, y, jnp.ones(2))))(params)


def test_cross_attention_reads_layer_17s_keys_and_values(small):
    grads = _grads_with(small, {"layer_17": ["o_proj", "o_bias"],
                                "layer_01": ["o_proj", "o_bias"]})
    # the K and V columns of Wqkv's bias: q is the first 64 columns
    kv_17 = grads["layer_17"]["qkv_bias"][64:]
    kv_01 = grads["layer_01"]["qkv_bias"][64:]
    assert float(jnp.max(jnp.abs(kv_17))) > 1e-6  # through layer 19 alone
    np.testing.assert_array_equal(kv_01, 0.0)  # nobody reads layer 1's
    params = small[1]["params"]
    assert "q_proj" in params["layer_19"]
    assert "qkv_proj" not in params["layer_19"]


def test_the_gated_memory_unit_reads_layer_16s_scan_output(small):
    grads = _grads_with(small, {"layer_16": ["out_proj"],
                                "layer_00": ["out_proj"]})
    skip_16 = grads["layer_16"]["d_skip"]
    skip_00 = grads["layer_00"]["d_skip"]
    assert float(jnp.max(jnp.abs(skip_16))) > 1e-6  # through layer 18 alone
    np.testing.assert_array_equal(skip_00, 0.0)


# -- the vocabulary share --------------------------------------------------------

def test_a_vocabulary_share_is_the_wholes_columns(small, reference):
    """Four chips hold a quarter of the embedding's rows each. With ids
    drawn from share 0, its logits are the whole model's first columns, and
    the exp-sums of the four shares' logits add up to the whole softmax
    denominator."""
    module, variables, _, _ = small
    share = VOCAB // 4
    x = jnp.asarray(np.random.RandomState(5).randint(0, share, (1, LENGTH)))
    hp = reference.hyperparameters(module)
    whole = jax.jit(lambda p: reference.logits_of(p, hp, x[0]))(
        variables["params"])
    sliced = create_model("sambay", output_dim=share, **SMALL)
    embedding = variables["params"]["embedding"]
    mine = {"params": {**variables["params"],
                       "embedding": embedding[:share]}}
    got = jax.jit(sliced.clone(return_logits=True).apply)(mine, x)[0]
    assert _rel(got, whole[:, :share]) < 1e-5
    out = jax.jit(sliced.apply)(mine, x)
    assert isinstance(out, TiedHead)
    hidden = out.hidden[0]
    exp_sums = sum(jnp.sum(jnp.exp(
        hidden @ embedding[k * share:(k + 1) * share].T), axis=-1)
        for k in range(4))
    np.testing.assert_allclose(
        jnp.log(exp_sums), jax.scipy.special.logsumexp(whole, axis=-1),
        rtol=1e-5)
