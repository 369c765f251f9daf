"""The LFM2-MoE hybrid decoder (LFM2-8B-A1B) against its plain reference, at
small widths on the CPU: the same layer kinds under the published layer
indices, seeded weights, ``highest`` precision; the routing stats of the
``lm_rows`` head; and the folded FedAvg round against the reference's."""

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
from fedml_tpu.models import create_model
from fedml_tpu.models.common import rms_norm, rotary
from fedml_tpu.models.lfm2_moe import LFM2_8B_LAYER_TYPES
from fedml_tpu.ops import moe
from fedml_tpu.trainer.functional import TrainConfig, make_local_train
from fedml_tpu.trainer.tasks import RoutedTiedHead, TiedHead, lm_rows_head

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, LENGTH = 96, 37
#: published layers 1 (convolution, dense), 2 (attention, sparse), 3
#: (convolution, sparse), 6 (attention, sparse); experts 2-5 of 8, top-2
SMALL = dict(hidden_size=64, num_heads=4, num_kv_heads=2,
             intermediate_size=96, moe_intermediate_size=48, num_experts=8,
             num_experts_per_tok=2, experts_held=(2, 4),
             layer_ids=(1, 2, 3, 6), attn_block=16)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_local_sgd", os.path.join(
            ROOT, "benchmark", "references", "lfm2_moe_local_sgd.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module", autouse=True)
def expert_blocks_of_16_rows():
    """37 tokens, top-2: blocks of 16 rows so that an expert's segment spans
    blocks (the layer reads ``BLOCK`` as it is traced)."""
    with mock.patch.object(moe, "BLOCK", 16):
        yield


def _seeded(module, tokens, seed=1, noise=0.05):
    """Initial variables with every leaf perturbed, so that scales that
    start at 1 take part and the router's scores spread (at 64 wide an
    initial router of 0.02 leaves the selection to the bias alone)."""
    variables = jax.jit(lambda t: module.init(jax.random.key(seed), t,
                                              train=False))(tokens[:1])
    leaves, treedef = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + noise * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


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


@pytest.fixture(scope="module")
def small():
    module = create_model("lfm2_moe", output_dim=VOCAB, **SMALL)
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


def test_loss_and_every_gradient_leaf_equal_the_references(small, reference):
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
    # the bias only selects: no gradient, on either side
    for layer in ("layer_02", "layer_03", "layer_06"):
        assert not np.any(np.asarray(grads[layer]["expert_bias"]))
        assert not np.any(np.asarray(ref_grads[layer]["expert_bias"]))


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


def test_the_four_expert_shares_of_the_model_are_one_router(small):
    """Every share routes with the same router and bias: the loads the four
    chips report add up to every (token, choice) pair of the first sparse
    layer (later layers see other inputs, their own share's)."""
    module, variables, x, _ = small
    total = 0.0
    for first in (0, 2, 4, 6):
        share = module.clone(experts_held=(first, 2))
        params = jax.tree.map(lambda a: a, variables["params"])
        for name, layer in params.items():
            for leaf in ("experts_w1", "experts_w3", "experts_w2"):
                if leaf in layer:
                    layer[leaf] = layer[leaf][:2]
        out = jax.jit(share.apply)({"params": params}, x)
        assert out.expert_load.shape == (2, 3, 2)
        total += float(out.expert_load[:, 0].sum())
    assert total == 2 * LENGTH * 2


# -- sizes and kinds -------------------------------------------------------------

def test_parameter_count_at_the_published_widths():
    module = create_model("lfm2_moe", output_dim=16384, experts_held=[0, 8],
                          layer_ids=[1, 2, 3, 4, 5, 6, 7])
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    sizes = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(group))
             for name, group in shapes["params"].items()}
    conv, attention, norms = 16_783_360, 10_485_888, 4_096
    dense, sparse = 44_040_192, 88_145_952
    assert sizes["layer_01"] == conv + dense + norms == 60_827_648
    assert sizes["layer_02"] == sizes["layer_06"] == attention + sparse + norms
    for name in ("layer_03", "layer_04", "layer_05", "layer_07"):
        assert sizes[name] == conv + sparse + norms == 104_933_408
    assert sizes["embedding"] + sizes["final_norm"] == 33_556_480
    assert sum(sizes.values()) == 711_389_632
    experts = sum(int(np.prod(a.shape)) for path, a in
                  jax.tree_util.tree_leaves_with_path(shapes)
                  if "experts_w" in str(path))
    assert 0.74 < experts / 711_389_632 < 0.75
    assert shapes["params"]["layer_03"]["experts_w1"].shape == (8, 2048, 1792)
    assert shapes["params"]["layer_03"]["router"].shape == (2048, 32)


@pytest.mark.parametrize("layer, kind, dense", [
    (0, "conv", True), (1, "conv", True), (2, "full_attention", False),
    (3, "conv", False), (6, "full_attention", False), (7, "conv", False),
    (18, "full_attention", False), (21, "full_attention", False),
    (22, "conv", False), (23, "conv", False)])
def test_a_layers_kind_follows_its_published_index(layer, kind, dense):
    """A cut in depth keeps the published indices: the leaves a layer has
    are its published kind's, whatever its position here."""
    assert LFM2_8B_LAYER_TYPES[layer] == kind
    module = create_model("lfm2_moe", output_dim=8, **{
        **SMALL, "layer_ids": [layer]})
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32), train=False))
    leaves = set(shapes["params"][f"layer_{layer:02d}"])
    assert ("conv_kernel" in leaves) == (kind == "conv")
    assert ("q_norm_scale" in leaves) == (kind == "full_attention")
    assert ("ffn_w1" in leaves) == dense
    assert ("router" in leaves) == (not dense)


def test_the_expert_bias_is_one_draw_for_every_seed():
    module = create_model("lfm2_moe", output_dim=8, **SMALL)
    tokens = jnp.zeros((1, 4), jnp.int32)
    a = module.init(jax.random.key(0), tokens, train=False)["params"]
    b = module.init(jax.random.key(1), tokens, train=False)["params"]
    np.testing.assert_array_equal(a["layer_02"]["expert_bias"],
                                  b["layer_02"]["expert_bias"])
    assert np.any(np.asarray(a["layer_02"]["expert_bias"])
                  != np.asarray(a["layer_03"]["expert_bias"]))
    assert np.any(np.asarray(a["layer_02"]["router"])
                  != np.asarray(b["layer_02"]["router"]))
    assert 0.02 < float(jnp.std(a["layer_02"]["expert_bias"])) < 0.3
    plain = create_model("lfm2_moe", output_dim=8, **{
        **SMALL, "use_expert_bias": False})
    assert "expert_bias" not in plain.init(
        jax.random.key(0), tokens, train=False)["params"]["layer_02"]


# -- the helpers ------------------------------------------------------------------

def test_rotary_is_a_rotation_by_position_times_frequency():
    x = jnp.asarray(np.random.RandomState(0).randn(3, 5, 8), jnp.float32)
    got = rotary(x, 100.0)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)  # position 0
    # pairs (i, i + D/2) turn by t * theta^(-2i/D); norms are kept
    t, i = 3, 1
    angle = t * 100.0 ** (-2 * i / 8)
    want = (x[0, t, i] * np.cos(angle) - x[0, t, i + 4] * np.sin(angle),
            x[0, t, i + 4] * np.cos(angle) + x[0, t, i] * np.sin(angle))
    np.testing.assert_allclose((got[0, t, i], got[0, t, i + 4]), want,
                               rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_rms_norm_divides_by_the_root_mean_square():
    x = jnp.asarray([[3.0, 4.0, 0.0, 0.0]])
    np.testing.assert_allclose(
        rms_norm(x, jnp.asarray([1.0, 2.0, 1.0, 1.0]), 0.0),
        [[3.0 / 2.5, 2 * 4.0 / 2.5, 0.0, 0.0]], rtol=1e-6)


def test_the_short_convolution_is_causal_and_three_taps_long(small):
    module, variables, x, _ = small
    one = module.clone(layer_ids=(3,), return_logits=True)
    params = {"embedding": variables["params"]["embedding"],
              "final_norm": variables["params"]["final_norm"],
              "layer_03": variables["params"]["layer_03"]}
    base = jax.jit(one.apply)({"params": params}, x[:1])
    at = 20
    moved = jax.jit(one.apply)({"params": params}, x[:1].at[0, at].set(
        (x[0, at] + 1) % VOCAB))
    changed = np.flatnonzero(np.max(np.abs(np.asarray(moved - base)[0]),
                                    axis=-1) > 0)
    assert changed.tolist() == [at, at + 1, at + 2]


# -- the routing stats --------------------------------------------------------------

def test_the_head_sums_the_routing_of_the_real_rows(small, reference):
    module, variables, x, y = small
    out = jax.jit(module.apply)(variables, x)
    assert isinstance(out, RoutedTiedHead)
    assert out.expert_load.shape == (2, 3, 4)
    # the first sparse layer's input is the reference's: count its choices
    hp = reference.hyperparameters(module)
    p = variables["params"]

    def first_choices(tokens):
        h = reference._layer(p["layer_01"], p["embedding"][tokens], 1, hp,
                             False, True)
        h = h + reference._attention(p["layer_02"], reference._rms(
            h, p["layer_02"]["operator_norm_scale"], hp["eps"]), hp, False)
        s = reference._rms(h, p["layer_02"]["ffn_norm_scale"], hp["eps"])
        prob = jax.nn.sigmoid(s @ p["layer_02"]["router"])
        return jax.lax.top_k(prob + p["layer_02"]["expert_bias"], 2)[1]

    for row in range(2):
        chosen = np.asarray(first_choices(x[row]))
        np.testing.assert_array_equal(
            out.expert_load[row, 0],
            [np.sum(chosen == e) for e in range(2, 6)])
    load = np.asarray(out.expert_load)
    for mask in ([1.0, 1.0], [1.0, 0.0], [0.0, 0.0]):
        stats = lm_rows_head(out, y, jnp.asarray(mask))
        assert set(stats) == {"loss_sum", "count", "correct_sum",
                              "moe_assignments",
                              "moe_top_expert_assignments", "moe_block_rows"}
        kept = np.einsum("b,ble->le", np.asarray(mask), load)
        assert float(stats["moe_assignments"]) == kept.sum()
        assert float(stats["moe_top_expert_assignments"]) == \
            kept.max(-1).sum()
        # the rows the loops ran, padding rows' pairs included
        assert float(stats["moe_block_rows"]) == float(out.block_rows.sum())
        assert float(stats["moe_assignments"]) <= float(
            stats["moe_block_rows"])
    np.testing.assert_array_equal(out.block_rows,
                                  _block_rows(out, x.size, 2, 8))
    # an output without routing counts gets the keys it got before
    plain = lm_rows_head(TiedHead(out.hidden, out.embedding), y, jnp.ones(2))
    assert set(plain) == {"loss_sum", "count", "correct_sum"}
    whole = lm_rows_head(out, y, jnp.ones(2))
    for key in plain:
        np.testing.assert_array_equal(plain[key], whole[key])


def test_the_block_rows_are_what_the_loops_ran_step_by_step(small):
    module, variables, x, y = small
    stats, want = _block_rows_over_steps(module, variables, x, y, 2, 8)
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


FOLD = {**SMALL, "hidden_size": 128, "moe_intermediate_size": 128,
        "intermediate_size": 128}
TRAIN = {"batch_size": 1, "epochs": 1, "lr": 0.05, "client_optimizer": "sgd"}


def _fold_api(dataset, module, lr=TRAIN["lr"]):
    return FedAvgAPI(dataset, module, task="lm_rows", config=FedAvgConfig(
        comm_round=4, client_num_per_round=4, prefetch_depth=0,
        fold_clients=True, train=TrainConfig(epochs=1, batch_size=1, lr=lr)))


def _dist(a, b):
    return np.sqrt(sum(float(np.sum((np.asarray(x, np.float64) - y) ** 2))
                       for x, y in zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b))))


@pytest.fixture(scope="module")
def folded(reference):
    """The folded driver's own round 0 (``FedAvgAPI(fold_clients=True)``, 4
    of 6 tiny silos; widths at which the fold kernel takes the matrices and
    the stacks of experts) and ``lfm2_moe_local_sgd.run_round`` over the
    same cohort."""
    dataset = _token_silos()
    module = create_model("lfm2_moe", output_dim=VOCAB, **FOLD)
    api = _fold_api(dataset, module)
    api.variables = _seeded(module, jnp.zeros((1, 24), jnp.int32), seed=5,
                            noise=0.02)
    init = jax.device_get(api.variables)
    idxs, stats = api.run_round(0)
    got = jax.device_get(api.variables)
    api.run_round(1)
    assert api._round_fn._cache_size() == 1  # no recompilation
    ref = reference.run_round(module, "lm_rows", TRAIN, init, dataset,
                              seed=api.config.seed, round_idx=0,
                              clients=idxs, aggregate=True)
    return dataset, module, init, idxs, stats, got, ref


def test_the_folded_round_equals_the_references_round(folded):
    """What decides ``correct`` on the chip, at a small size and in the
    harness's own norm; the stat totals carry the routing counts."""
    dataset, _, init, idxs, stats, got, ref = folded
    change = _dist(init, ref["variables"])
    assert change > 0
    assert _dist(got, ref["variables"]) < 1e-3 * change
    np.testing.assert_allclose(sum(ref["loss_sum"].values()),
                               float(stats["loss_sum"]), rtol=1e-5)
    rows = sum(dataset.train_data_local_num_dict[int(c)] for c in idxs)
    assert float(stats["count"]) == rows
    # 3 sparse layers, 24 tokens x 2 choices a row, 4 of 8 experts held
    assert 0 < float(stats["moe_assignments"]) <= rows * 3 * 24 * 2
    assert float(stats["moe_assignments"]) / 4 <= float(
        stats["moe_top_expert_assignments"]) <= float(
            stats["moe_assignments"])
    # in blocks of 16 rows, each held pair in one
    assert float(stats["moe_assignments"]) <= float(stats["moe_block_rows"])
    assert float(stats["moe_block_rows"]) % 16 == 0
    # the bias is nobody's to move: the mean of four equal values, to
    # float32 rounding of shares that are sevenths
    np.testing.assert_allclose(got["params"]["layer_02"]["expert_bias"],
                               init["params"]["layer_02"]["expert_bias"],
                               rtol=1e-6)


@pytest.mark.parametrize("fault", ["top_k_less_one", "a_dropped_expert",
                                   "a_halved_step", "a_missing_silo",
                                   "a_bfloat16_result"])
def test_the_timed_bound_fails_the_faults_it_is_there_for(folded, fault,
                                                          reference):
    """The faults the configuration's ``check.timed.param_fraction`` has to
    fail, at the small size: each lands further from the reference's round
    than that fraction of the change (the sound round: under 1e-3)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b_ep4.json")) as f:
        bound = json.load(f)["check"]["timed"]["param_fraction"]
    dataset, module, init, idxs, _, got, ref = folded
    change = _dist(init, ref["variables"])
    if fault == "a_bfloat16_result":
        wrong = jax.tree.map(lambda a: np.asarray(
            jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)), got)
    elif fault == "a_missing_silo":
        wrong = reference.run_round(
            module, "lm_rows", TRAIN, init, dataset, seed=0, round_idx=0,
            clients=idxs[:-1], aggregate=True)["variables"]
    else:
        variables = init
        if fault == "top_k_less_one":
            module = module.clone(num_experts_per_tok=1)
        elif fault == "a_dropped_expert":
            # the share without its last expert: three of the four held
            module = module.clone(experts_held=(2, 3))
            variables = jax.tree.map(lambda a: a, init)
            for layer in variables["params"].values():
                for leaf in ("experts_w1", "experts_w3", "experts_w2"):
                    if leaf in layer:
                        layer[leaf] = layer[leaf][:3]
        api = _fold_api(dataset, module,
                        lr=TRAIN["lr"] / (2 if fault == "a_halved_step"
                                          else 1))
        api.variables = jax.tree.map(jnp.asarray, variables)
        assert list(api.run_round(0)[0]) == list(idxs)
        wrong = jax.device_get(api.variables)
        if fault == "a_dropped_expert":
            # compare what both hold: the three experts' leaves
            ref = {"variables": jax.tree.map(lambda a: a, ref["variables"])}
            for layer in ref["variables"]["params"].values():
                for leaf in ("experts_w1", "experts_w3", "experts_w2"):
                    if leaf in layer:
                        layer[leaf] = layer[leaf][:3]
    reading = _dist(wrong, ref["variables"]) / change
    assert reading > bound, f"{fault}: {reading:.4f} of the change"
