"""The ``qwen3_next`` decoder (Qwen3-Next-80B-A3B-Instruct: Gated DeltaNet
beside gated attention, routed experts by a softmax router beside a gated
shared expert) against its plain reference, at small widths on the CPU:
seeded weights with every norm leaf drawn at random, float32 at
``highest``; the shares of a sparse block against the uncut block; the
layer kinds from the published indices; and the folded FedAvg round against
the reference's."""

import functools
import importlib.util
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.models import create_model, qwen3_next
from fedml_tpu.models.common import rms_norm
from fedml_tpu.ops import moe
from fedml_tpu.trainer.functional import TrainConfig, make_local_train
from fedml_tpu.trainer.tasks import RoutedTiedHead, lm_rows_head

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
#: published layers 2 (linear) and 3 (full); 2 key heads and 4 value heads
#: of 8 | 8, 4 query heads and 2 KV heads of 16 (a rope over 4); experts
#: 4-11 of 16, top-3, a shared expert
SMALL = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8,
             moe_intermediate_size=16, shared_expert_intermediate_size=24,
             num_experts=16, num_experts_per_tok=3, experts_held=(4, 8),
             layer_ids=(2, 3))


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_local_sgd", os.path.join(
            ROOT, "benchmark", "references", "qwen3_next_local_sgd.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The delta rule in chunks of 16, the core in blocks of 16 queries and
    the grouped products in blocks of 8 rows (the model runs the ops' own
    64, 512 and ``BLOCK``, read as the layer is traced), so that a row of 40
    tokens spans chunks and blocks and ends inside one."""
    with mock.patch.object(qwen3_next, "gated_delta_rule", functools.partial(
            qwen3_next.gated_delta_rule, chunk=16)), \
            mock.patch.object(qwen3_next, "causal_attention",
                              functools.partial(qwen3_next.causal_attention,
                                                block=16)), \
            mock.patch.object(moe, "BLOCK", 8):
        yield


def _seeded(module, tokens, seed=1, noise=0.05):
    """Initial variables with every leaf perturbed, so that the norms'
    weights (zero-centred ones at 0, the gated one at 1) take part and the
    router's scores spread."""
    variables = jax.jit(lambda t: module.init(jax.random.key(seed), t,
                                              train=False))(tokens[:1])
    leaves, treedef = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + noise * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _rows(length):
    rows = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (2, length + 1)))
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module")
def small():
    module = create_model("qwen3_next", output_dim=VOCAB, **SMALL)
    x, y = _rows(40)
    return module, _seeded(module, x), x, y


def _loss(module, params, x, y, mask):
    stats = lm_rows_head(module.apply({"params": params}, x), y, mask)
    return stats["loss_sum"] / stats["count"]


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


def _block_rows(out, tokens, top_k, num_experts):
    """Per sparse layer the rows one forward's block loops ran: the blocks
    each held expert's pairs over all the rows need, times the block."""
    block = moe._block(tokens, top_k, num_experts)
    load = np.asarray(out.expert_load).sum(0)
    return block * np.sum(-(-load // block), axis=-1)


def _block_rows_over_steps(module, variables, x, y, top_k, num_experts):
    """``moe_block_rows`` of a local epoch of one row a step at lr 0 (every
    step sees the same weights), and the sum of each row's forward."""
    local_train = make_local_train(module, "lm_rows", TrainConfig(
        epochs=1, batch_size=1, lr=0.0))
    _, stats = jax.jit(local_train)(variables, x, y, jnp.ones(len(x)),
                                    jax.random.key(0))
    apply = jax.jit(module.apply)
    want = sum(_block_rows(apply(variables, x[i:i + 1]), x.shape[1], top_k,
                           num_experts).sum() for i in range(len(x)))
    return stats, want


def _reference_logits(reference, module, variables, x):
    hp = reference.hyperparameters(module)
    return jax.jit(lambda p: jnp.stack([
        reference.logits_of(p, hp, row) for row in x]))(variables["params"])


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("length", [40, 64])
def test_logits_equal_the_references(small, reference, length):
    """1e-5 of the largest logit: both sides are float32 at ``highest`` and
    differ in the order of their sums alone (chunks of the delta rule,
    blocks of queries and of an expert's rows here; the step recurrence and
    whole matrices there); measured 2e-6. Rows of 40 tokens end inside a
    chunk of 16, rows of 64 on one."""
    module, variables, _, _ = small
    x, _ = _rows(length)
    got = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    want = _reference_logits(reference, module, variables, x)
    assert got.shape == x.shape + (VOCAB,)
    assert _rel(got, want) < 1e-5


def test_loss_and_every_gradient_leaf_equal_the_references(small, reference):
    """1e-4 of a leaf's largest gradient: the delta rule's chunks, the
    attention blocks and the grouped products are rematerialised and the
    grouped products' backward pass is written out by hand - float32 sums in
    another order, ten times the room of the logits'."""
    module, variables, x, y = small
    mask = jnp.ones(2)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(module, p, x, y, mask)))(variables["params"])
    hp = reference.hyperparameters(module)

    def want_loss(p):
        return jnp.mean(jnp.stack([reference._LOOP.row_mean_cross_entropy(
            reference.logits_of(p, hp, x[i]), y[i]) for i in range(2)]))

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(want_loss))(
        variables["params"])
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    errors = jax.tree.map(_rel, grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errors),
                key=lambda item: item[1])
    assert worst[1] < 1e-4, worst
    # the untied head, the embedding, every norm and both gates learn
    for layer in ("layer_02", "layer_03"):
        for name in ("input_norm_scale", "post_attention_norm_scale",
                     "shared_gate", "router"):
            assert np.any(np.asarray(grads[layer][name])), (layer, name)
    for name in ("A_log", "dt_bias", "norm_scale", "conv_kernel"):
        assert np.any(np.asarray(grads["layer_02"][name])), name
    for name in ("q_norm_scale", "k_norm_scale"):
        assert np.any(np.asarray(grads["layer_03"][name])), name
    assert np.any(np.asarray(grads["lm_head"]))
    assert np.any(np.asarray(grads["final_norm"]["norm_scale"]))


@pytest.mark.parametrize("fault", ["plain_norms", "gate_before_norm",
                                   "no_output_gate"])
def test_each_reading_of_the_norms_and_gates_is_held(small, reference, fault):
    """The reference agrees with the model, and not with it read otherwise:
    ``x / rms(x) * w`` for the zero-centred ``(1 + w)`` (the norm leaves
    are drawn at random, so the two differ), the linear block gated before
    its norm (Mamba-2's order), the attention heads without their gate."""
    module, variables, x, _ = small
    logits = module.clone(return_logits=True)
    patch = {
        "plain_norms": mock.patch.object(
            qwen3_next, "norm0", lambda x, w, eps: rms_norm(x, w, eps)),
        "gate_before_norm": mock.patch.object(
            qwen3_next, "_norm_then_gate",
            lambda out, z, scale, eps: rms_norm(out * jax.nn.silu(z), scale,
                                                eps)),
        "no_output_gate": mock.patch.object(
            qwen3_next, "_output_gate", lambda out, gate: out)}[fault]
    with patch:
        # a function of its own: no trace is shared with the sound call
        got = jax.jit(lambda v, t: logits.apply(v, t))(variables, x)
    ours = jax.jit(logits.apply)(variables, x)
    want = _reference_logits(reference, module, variables, x)
    assert _rel(ours, want) < 1e-5 < 1e-3 < _rel(got, want)


# -- kinds, depth and shares -------------------------------------------------------

def test_the_layer_kinds_come_from_the_published_indices():
    module = create_model("qwen3_next", output_dim=8, **{
        **SMALL, "layer_ids": tuple(range(8))})
    assert [module.is_full(layer) for layer in range(8)] == [
        False, False, False, True] * 2
    whole = create_model("qwen3_next", output_dim=8)
    assert [layer for layer in range(48) if whole.is_full(layer)] == list(
        range(3, 48, 4))
    # a cut that starts at published layer 3 holds a full layer first
    shapes = jax.eval_shape(lambda: module.clone(layer_ids=(3, 4)).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32), train=False))
    p = shapes["params"]
    assert set(p) == {"embedding", "lm_head", "final_norm", "layer_03",
                      "layer_04"}
    assert "q_proj" in p["layer_03"] and "in_proj_qkvz" not in p["layer_03"]
    assert "in_proj_qkvz" in p["layer_04"] and "q_proj" not in p["layer_04"]


def test_layer_three_alone_is_the_full_models_layer(small):
    """A cut in depth keeps the published indices: with layer 2 made to add
    nothing (its output projections zeroed, the experts' and the shared
    expert's) the model is the model of ``layer_ids`` (3,) on the same
    leaves: still a full-attention layer."""
    module, variables, x, _ = small
    params = jax.tree.map(lambda a: a, variables["params"])
    params["layer_02"] = {**params["layer_02"], **{
        name: jnp.zeros_like(params["layer_02"][name])
        for name in ("out_proj", "experts_w2", "shared_w2")}}
    full = jax.jit(module.clone(return_logits=True).apply)(
        {"params": params}, x)
    held = {k: v for k, v in params.items() if k != "layer_02"}
    part = jax.jit(module.clone(layer_ids=(3,),
                                return_logits=True).apply)(
        {"params": held}, x)
    np.testing.assert_allclose(part, full, rtol=0, atol=1e-6)


@pytest.mark.parametrize("layer", [2, 3])
def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(
        small, reference, layer):
    """The routed parts of the 4 models ``experts_held = (4 i, 4)`` plus the
    shared expert counted once equal the uncut reference's whole layer: each
    share's layer is ``x + Mix + Routed_i + Shared``, so the four sum to the
    whole layer and three times the layer without routed experts (the
    reference's, ``experts`` false). A linear layer and the full one."""
    module, _, x, _ = small
    uncut = module.clone(experts_held=(0, 16))
    params = _seeded(uncut, x, seed=7)["params"]
    p = params[f"layer_{layer:02d}"]
    h = 0.5 * jax.random.normal(jax.random.key(3), (2, x.shape[1], 32))
    total = 0.0
    for i in range(4):
        share = module.clone(experts_held=(4 * i, 4))
        mine = {**p, **{name: p[name][4 * i:4 * i + 4] for name in
                        ("experts_w1", "experts_w3", "experts_w2")}}
        out, load, _ = jax.jit(lambda q, h, share=share: qwen3_next._layer(
            q, h, full=share.is_full(layer), cfg=share.cfg()))(mine, h)
        assert load.shape == (2, 4)
        total = total + out
    hp = reference.hyperparameters(uncut)
    whole = jnp.stack([reference._layer(p, row, layer, hp, False, True, True)
                       for row in h])
    without = jnp.stack([reference._layer(p, row, layer, hp, False, False,
                                          True) for row in h])
    assert float(jnp.max(jnp.abs(whole - without))) > 0.01  # experts matter
    assert _rel(total - 3.0 * without, whole) < 1e-5
    assert _rel(out, whole) > 1e-3  # and a share alone is not the layer


def test_softmax_routing_chooses_over_all_the_experts(small):
    """The router's width is the published count whatever the share: the
    weights of a token's held pairs are its softmax over all 16 experts,
    renormalised over its 3 chosen, and the chosen sum to one with the
    absent experts' weights."""
    module, variables, x, _ = small
    p = variables["params"]["layer_02"]
    s = jax.random.normal(jax.random.key(4), (x.shape[1], 32))
    chosen, weights = moe.route(s, p["router"], None, top_k=3,
                                norm_topk=True, scale=1.0, eps=0.0,
                                score="softmax")
    prob = np.asarray(jax.nn.softmax(s @ p["router"], axis=-1))
    want = np.argsort(-prob, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    np.testing.assert_allclose(np.sum(weights, -1), 1.0, rtol=1e-6)
    assert p["router"].shape == (32, 16)


# -- sizes ---------------------------------------------------------------------------

def test_parameter_count_at_the_published_widths():
    module = create_model("qwen3_next", output_dim=18992,
                          experts_held=[0, 32], layer_ids=[0, 1, 2, 3])
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    sizes = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(group))
             for name, group in shapes["params"].items()}
    linear = 25_165_824 + 131_072 + 32_768 + 64 + 128 + 8_388_608
    full = 16_777_216 + 1_048_576 + 1_048_576 + 8_388_608 + 512
    sparse = 1_048_576 + 3_145_728 + 2_048 + 32 * 3_145_728
    assert (linear, full, sparse) == (33_718_464, 27_263_488, 104_859_648)
    for layer in range(3):
        assert sizes[f"layer_{layer:02d}"] == linear + sparse + 4_096 \
            == 138_582_208
    assert sizes["layer_03"] == full + sparse + 4_096 == 132_127_232
    assert sizes["embedding"] == sizes["lm_head"] == 38_895_616
    assert sizes["final_norm"] == 2_048
    assert sum(sizes.values()) == 625_667_136
    p = shapes["params"]
    assert p["layer_00"]["in_proj_qkvz"].shape == (2048, 12288)
    assert p["layer_00"]["in_proj_ba"].shape == (2048, 64)
    assert p["layer_00"]["conv_kernel"].shape == (4, 8192)
    assert p["layer_03"]["q_proj"].shape == (2048, 8192)
    assert p["layer_03"]["k_proj"].shape == (2048, 512)
    assert p["layer_03"]["experts_w1"].shape == (32, 2048, 512)
    assert p["layer_03"]["router"].shape == (2048, 512)
    assert module.cfg()["rotary_dim"] == 64
    # every expert of all 48 layers: the published model's 80 B
    whole = create_model("qwen3_next", output_dim=151936)
    shapes = jax.eval_shape(lambda: whole.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 79_674_391_296


def test_the_initial_leaves_are_the_familys(small):
    module = create_model("qwen3_next", output_dim=8, **SMALL)
    p = module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    train=False)["params"]
    a = np.exp(np.asarray(p["layer_02"]["A_log"]))
    assert np.all((a > 0) & (a < 16))
    np.testing.assert_array_equal(p["layer_02"]["dt_bias"], 1.0)
    np.testing.assert_array_equal(p["layer_02"]["norm_scale"], 1.0)
    for name in ("input_norm_scale", "post_attention_norm_scale"):
        np.testing.assert_array_equal(p["layer_02"][name], 0.0)
    np.testing.assert_array_equal(p["layer_03"]["q_norm_scale"], 0.0)
    np.testing.assert_array_equal(p["final_norm"]["norm_scale"], 0.0)
    assert float(np.max(np.abs(p["layer_02"]["conv_kernel"]))) <= 0.5
    assert np.any(np.asarray(p["lm_head"]) != np.asarray(p["embedding"]))


def test_the_output_is_a_routed_head_with_the_untied_leaf(small):
    module, variables, x, y = small
    out = jax.jit(module.apply)(variables, x)
    assert isinstance(out, RoutedTiedHead)
    assert out.expert_load.shape == (2, 2, 8)  # every layer is sparse
    np.testing.assert_array_equal(out.embedding,
                                  variables["params"]["lm_head"])
    stats = lm_rows_head(out, y, jnp.ones(2))
    assert 0 < float(stats["moe_assignments"]) <= 2 * 2 * x.shape[1] * 3
    assert float(stats["moe_assignments"]) == float(out.expert_load.sum())
    # the rows the loops ran: blocks in use x block, both layers
    np.testing.assert_array_equal(out.block_rows,
                                  _block_rows(out, x.size, 3, 16))
    assert float(stats["moe_block_rows"]) == float(out.block_rows.sum())
    assert float(stats["moe_assignments"]) <= float(stats["moe_block_rows"])


def test_the_block_rows_are_what_the_loops_ran_step_by_step(small):
    module, variables, x, y = small
    stats, want = _block_rows_over_steps(module, variables, x, y, 3, 16)
    assert float(stats["moe_block_rows"]) == want > 0
    assert float(stats["moe_assignments"]) <= want


# -- the folded round against the reference's ---------------------------------------

def _token_silos(silos=6, rows=(2, 2, 1, 2, 2, 2), length=24, seed=0):
    rs = np.random.RandomState(seed)
    train, test = {}, {}
    for c in range(silos):
        seq = rs.randint(0, VOCAB, (rows[c] + 1, length + 1)).astype(np.int32)
        train[c] = (seq[:-1, :-1], seq[:-1, 1:])
        test[c] = (seq[-1:, :-1], seq[-1:, 1:])
    return FederatedDataset.from_client_arrays(train, test, class_num=VOCAB)


#: widths at which the fold kernel takes the matrices and the stacks of experts
FOLD = {**SMALL, "hidden_size": 128, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128}
TRAIN = {"batch_size": 1, "epochs": 1, "lr": 0.05, "client_optimizer": "sgd"}


def _fold_round0(dataset, module, variables):
    api = FedAvgAPI(dataset, module, task="lm_rows", config=FedAvgConfig(
        comm_round=4, client_num_per_round=4, prefetch_depth=0,
        fold_clients=True,
        train=TrainConfig(epochs=1, batch_size=1, lr=TRAIN["lr"])))
    api.variables = jax.tree.map(jnp.asarray, variables)
    idxs, stats = api.run_round(0)
    return api, idxs, stats, jax.device_get(api.variables)


def _dist(a, b):
    return np.sqrt(sum(float(np.sum((np.asarray(x, np.float64) - y) ** 2))
                       for x, y in zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b))))


@pytest.fixture(scope="module")
def folded(reference):
    """The folded driver's own round 0 (``FedAvgAPI(fold_clients=True)``, 4
    of 6 tiny silos) and ``qwen3_next_local_sgd.run_round`` over the same
    cohort."""
    dataset = _token_silos()
    module = create_model("qwen3_next", output_dim=VOCAB, **FOLD)
    init = jax.device_get(_seeded(module, jnp.zeros((1, 24), jnp.int32),
                                  seed=5, noise=0.02))
    api, idxs, stats, got = _fold_round0(dataset, module, init)
    api.run_round(1)
    assert api._round_fn._cache_size() == 1  # no recompilation
    ref = reference.run_round(module, "lm_rows", TRAIN, init, dataset,
                              seed=api.config.seed, round_idx=0,
                              clients=idxs, aggregate=True)
    return dataset, module, init, idxs, stats, got, ref


def test_the_folded_round_equals_the_references_round(folded):
    """What decides ``correct`` on the chip, at a small size and in the
    harness's own norm: both sides float32 at ``highest``, so a thousandth
    of the change is rounding's room many times over."""
    dataset, _, init, idxs, stats, got, ref = folded
    change = _dist(init, ref["variables"])
    assert change > 0
    assert _dist(got, ref["variables"]) < 1e-3 * change
    np.testing.assert_allclose(sum(ref["loss_sum"].values()),
                               float(stats["loss_sum"]), rtol=1e-5)
    rows = sum(dataset.train_data_local_num_dict[int(c)] for c in idxs)
    assert float(stats["count"]) == rows
    # 2 sparse layers, 24 tokens x 3 choices a row, 8 of 16 experts held
    assert 0 < float(stats["moe_assignments"]) <= rows * 2 * 24 * 3
    # in blocks of 8 rows, each held pair in one
    assert float(stats["moe_assignments"]) <= float(stats["moe_block_rows"])
    assert float(stats["moe_block_rows"]) % 8 == 0


@pytest.mark.parametrize("fault",
                         ["no_shared_gate", "beta_one", "reference_bf16"])
def test_the_timed_bound_fails_the_planted_faults(folded, fault):
    """Controls of ``benchmark/tools/gated_delta_check_controls.py`` at the
    small size: each lands further from the reference's round than the
    configuration's ``check.timed.param_fraction`` of the change
    (``reference_bf16``: the reference's own result held in bfloat16, the
    precision below the configuration's float32)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b_ep16.json")) as f:
        bound = json.load(f)["check"]["timed"]["param_fraction"]
    dataset, module, init, idxs, _, _, ref = folded
    rule = qwen3_next.gated_delta_rule
    if fault == "reference_bf16":
        wrong = jax.tree.map(lambda a: np.asarray(a).astype(
            jnp.bfloat16).astype(np.float32), ref["variables"])
    else:
        if fault == "no_shared_gate":
            patch = mock.patch.object(
                qwen3_next, "_shared_expert",
                lambda p, s: qwen3_next._swiglu(
                    s, p["shared_w1"], p["shared_w3"], p["shared_w2"]))
        else:
            patch = mock.patch.object(
                qwen3_next, "gated_delta_rule", lambda q, k, v, g, beta:
                rule(q, k, v, g, jnp.ones_like(beta)))
        with patch:
            _, trained, _, wrong = _fold_round0(dataset, module, init)
        assert list(trained) == list(idxs)
    reading = _dist(wrong, ref["variables"]) / _dist(init, ref["variables"])
    assert reading > bound, f"{fault}: {reading:.4f} of the change"
