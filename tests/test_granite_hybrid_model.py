"""The Granite-4.0-H hybrid decoder (granite-4.0-h-micro) against its plain
reference, at small widths on the CPU: Mamba-2, attention, Mamba-2 under
their published layer indices, seeded weights, ``highest`` precision; the
four multipliers; and the folded FedAvg round against the reference's."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.models import create_model
from fedml_tpu.models.granite_hybrid import GRANITE_H_MICRO_LAYER_TYPES
from fedml_tpu.ops import ssd
from fedml_tpu.trainer.functional import TrainConfig
from fedml_tpu.trainer.tasks import TiedHead, lm_rows_head

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
#: published layers 4 (Mamba-2), 5 (attention), 6 (Mamba-2); 8 state-space
#: heads of 16 in 2 groups, a state of 16, chunks of 16 positions
SMALL = dict(hidden_size=64, num_heads=4, num_kv_heads=2,
             shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=16,
             layer_ids=(4, 5, 6), attn_block=16)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "granite_hybrid_local_sgd", os.path.join(
            ROOT, "benchmark", "references", "granite_hybrid_local_sgd.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _seeded(module, tokens, seed=1, noise=0.05):
    """Initial variables with every leaf perturbed, so that the scales and
    the skip that start at 1 take part."""
    variables = jax.jit(lambda t: module.init(jax.random.key(seed), t,
                                              train=False))(tokens[:1])
    leaves, treedef = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + noise * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _rows(length, count=2, seed=0):
    rows = jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB, (count, length + 1)))
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module", params=[40, 64],
                ids=["a_ragged_last_chunk", "whole_chunks"])
def small(request):
    module = create_model("granite_hybrid", output_dim=VOCAB, **SMALL)
    x, y = _rows(request.param)
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
    assert got.shape == x.shape + (VOCAB,)
    # float32 on both sides; the chunked form sums in another order
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
    for leaf in ("a_log", "dt_bias", "d_skip", "conv_kernel", "conv_bias",
                 "gate_norm_scale", "in_proj"):
        assert float(jnp.max(jnp.abs(grads["layer_04"][leaf]))) > 0, leaf


def test_the_rematerialised_reference_is_the_same_arithmetic(small,
                                                              reference):
    """``run_round`` cuts the recurrence into rematerialised runs of 64
    positions (only where the row is a multiple of 64)."""
    module, variables, x, y = small
    hp = reference.hyperparameters(module)

    def loss(p, remat):
        return reference._LOOP.row_mean_cross_entropy(
            reference.logits_of(p, hp, x[0], remat), y[0])

    plain = jax.jit(jax.grad(lambda p: loss(p, False)))(variables["params"])
    remat = jax.jit(jax.grad(lambda p: loss(p, True)))(variables["params"])
    assert max(jax.tree.leaves(jax.tree.map(_rel, remat, plain))) < 1e-5


def test_one_sgd_step_equals_the_references_step(small, reference):
    module, variables, x, y = small
    train = {"batch_size": 2, "lr": 0.1, "client_optimizer": "sgd"}
    step = jax.jit(reference.make_step(module, "lm_rows", train,
                                       remat=False))
    want, loss_sum, count = step(variables["params"], x, y, jnp.ones(2),
                                 None)
    grads = jax.jit(jax.grad(lambda p: _loss(module, p, x, y, jnp.ones(2))))(
        variables["params"])
    got = jax.tree.map(lambda p, g: p - 0.1 * g, variables["params"], grads)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         want, variables["params"])
    assert max(jax.tree.leaves(moved)) > 0 and float(count) == 2.0
    assert max(jax.tree.leaves(jax.tree.map(_rel, got, want))) < 1e-5


def test_a_cut_in_depth_moves_no_layers_kind(small):
    """The model that holds published layers 4-6 is layers 4-6 of the model
    that holds 3-7, on the same leaves."""
    module, variables, x, _ = small
    wide = module.clone(layer_ids=(3, 4, 5, 6, 7))
    shapes = jax.eval_shape(lambda: wide.init(jax.random.key(0), x[:1],
                                              train=False))["params"]
    assert set(shapes) == {"embedding", "final_norm"} | {
        f"layer_{i:02d}" for i in (3, 4, 5, 6, 7)}
    assert "q_proj" in shapes["layer_05"] and "in_proj" in shapes["layer_04"]
    assert "q_proj" not in shapes["layer_03"]
    # layers 3 and 7 turned into the identity: every product that feeds a
    # residual zeroed
    params = jax.tree.map(jnp.zeros_like, dict(shapes))
    params.update(variables["params"])
    got = jax.jit(wide.clone(return_logits=True).apply)({"params": params},
                                                        x)
    want = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("layer, kind", [
    (0, "mamba"), (4, "mamba"), (5, "attention"), (6, "mamba"),
    (15, "attention"), (25, "attention"), (35, "attention"), (39, "mamba")])
def test_a_layers_kind_follows_its_published_index(layer, kind):
    assert GRANITE_H_MICRO_LAYER_TYPES[layer] == kind
    assert len(GRANITE_H_MICRO_LAYER_TYPES) == 40
    assert GRANITE_H_MICRO_LAYER_TYPES.count("attention") == 4
    module = create_model("granite_hybrid", output_dim=VOCAB,
                          **{**SMALL, "layer_ids": (layer,)})
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"][f"layer_{layer:02d}"]
    assert ("a_log" in shapes) == (kind == "mamba")
    assert ("k_proj" in shapes) == (kind == "attention")


@pytest.mark.parametrize("name, other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_each_of_the_four_multipliers_changes_the_output(small, reference,
                                                         name, other):
    module, variables, x, _ = small
    assert getattr(module, name) == {
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8.0}[name]
    sound = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    changed = module.clone(return_logits=True, **{name: other})
    got = jax.jit(changed.apply)(variables, x)
    assert _rel(got, sound) > 1e-3
    # and the reference reads the same attribute
    hp = reference.hyperparameters(changed)
    want = jax.jit(lambda p: jnp.stack([
        reference.logits_of(p, hp, row) for row in x]))(variables["params"])
    assert _rel(got, want) < 1e-5


def test_parameter_count_at_the_published_widths():
    module = create_model("granite_hybrid", output_dim=12544,
                          layer_ids=tuple(range(10)))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert mixer == 25_847_232
    assert count(shapes["layer_00"]) == mixer + 50_331_648 + 4_096 \
        == 76_182_976
    assert shapes["layer_00"]["in_proj"].shape == (2048, 8512)
    assert shapes["layer_00"]["conv_kernel"].shape == (4, 4352)
    assert count(shapes["layer_05"]) == 10_485_760 + 50_331_648 + 4_096 \
        == 60_821_504
    assert count(shapes) == (9 * 76_182_976 + 60_821_504 + 2_048
                             + 12544 * 2048) == 772_160_448
    whole = create_model("granite_hybrid", output_dim=100352)
    total = count(jax.eval_shape(lambda: whole.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False)))
    assert total == (36 * 76_182_976 + 4 * 60_821_504 + 2_048
                     + 100352 * 2048) == 3_191_396_096


def test_the_start_of_the_state_space_leaves():
    module = create_model("granite_hybrid", output_dim=VOCAB,
                          **{**SMALL, "mamba_n_heads": 64,
                             "mamba_d_head": 2})
    p = module.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]["layer_04"]
    a = np.exp(np.asarray(p["a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    assert (np.asarray(p["d_skip"]) == 1).all()
    assert (np.asarray(p["gate_norm_scale"]) == 1).all()
    assert np.abs(np.asarray(p["conv_kernel"])).max() <= 0.5


def test_the_model_is_causal(small):
    """A token changes nothing before its own position, and nothing in
    another row."""
    module, variables, x, _ = small
    out = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    at = 17
    other = x.at[0, at].set((x[0, at] + 1) % VOCAB)
    moved = jax.jit(module.clone(return_logits=True).apply)(variables, other)
    diff = np.abs(np.asarray(moved - out)).max(-1)
    assert (diff[0, :at] == 0).all() and diff[0, at] > 0
    assert (diff[1] == 0).all()


def test_the_output_is_a_tied_head_scaled_once(small):
    module, variables, x, y = small
    out = jax.jit(module.apply)(variables, x)
    assert isinstance(out, TiedHead)
    logits = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    np.testing.assert_allclose(
        jnp.einsum("btd,vd->btv", out.hidden, out.embedding), logits,
        rtol=1e-5, atol=1e-6)
    assert set(lm_rows_head(out, y, jnp.ones(2))) == {
        "loss_sum", "count", "correct_sum"}


def test_unknown_layer_kinds_are_refused():
    module = create_model("granite_hybrid", output_dim=VOCAB, **{
        **SMALL, "layer_types": ("mamba",) * 4 + ("conv",) * 36})
    with pytest.raises(ValueError, match="conv"):
        module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)


# -- the folded round against the reference's ---------------------------------------

def _token_silos(silos=6, rows=(2, 2, 1, 2, 2, 2), length=40, seed=0):
    rs = np.random.RandomState(seed)
    train, test = {}, {}
    for c in range(silos):
        seq = rs.randint(0, VOCAB, (rows[c] + 1, length + 1)).astype(np.int32)
        train[c] = (seq[:-1, :-1], seq[:-1, 1:])
        test[c] = (seq[-1:, :-1], seq[-1:, 1:])
    return FederatedDataset.from_client_arrays(train, test, class_num=VOCAB)


FOLD = {**SMALL, "hidden_size": 128, "shared_intermediate_size": 128}
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
    of 6 tiny silos, rows of 40 tokens: two chunks and a ragged third) and
    ``granite_hybrid_local_sgd.run_round`` over the same cohort."""
    dataset = _token_silos()
    module = create_model("granite_hybrid", output_dim=VOCAB, **FOLD)
    api = _fold_api(dataset, module)
    api.variables = _seeded(module, jnp.zeros((1, 40), jnp.int32), seed=5,
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
    harness's own norm."""
    dataset, _, init, idxs, stats, got, ref = folded
    change = _dist(init, ref["variables"])
    assert change > 0
    assert _dist(got, ref["variables"]) < 1e-3 * change
    np.testing.assert_allclose(sum(ref["loss_sum"].values()),
                               float(stats["loss_sum"]), rtol=1e-5)
    rows = sum(dataset.train_data_local_num_dict[int(c)] for c in idxs)
    assert float(stats["count"]) == rows
    assert set(stats) >= {"loss_sum", "count"}
    assert not any(key.startswith("moe_") for key in stats)


@pytest.mark.parametrize("fault", [
    "chunks_as_separate_rows", "a_residual_multiplier_of_one",
    "a_bfloat16_result", "a_halved_step", "a_missing_silo"])
def test_the_faults_of_the_check_fail_the_comparison(folded, fault,
                                                     reference):
    """The faults the configuration's ``check.timed.param_fraction`` has to
    fail, at the small size: each lands further from the reference's round
    than that fraction of the change (the sound round: 2.4e-4). The chunks
    treated as separate rows are held to the float32 comparison above
    instead (1e-3 of the change, which they miss three times over): at 128
    wide with chunks of 16 the state's part of a layer is small beside the
    skip ``D xs`` and the embedding takes 91 % of the change, so the fault
    reads 0.0030; at the published widths the gradient of two Mamba-2
    layers and the head moves 5.8 % without the carried state (float32 on
    the CPU, one row of 2,048 tokens), and the chip's control reads it at
    the cell's size (PERF.md)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite_4_0_h_micro_10l.json")) as f:
        bound = json.load(f)["check"]["timed"]["param_fraction"]
    dataset, module, init, idxs, _, got, ref = folded
    change = _dist(init, ref["variables"])
    if fault == "a_bfloat16_result":
        wrong = jax.tree.map(lambda a: np.asarray(
            jax.lax.reduce_precision(jnp.asarray(a), 8, 7)), got)
    elif fault == "a_missing_silo":
        wrong = reference.run_round(
            module, "lm_rows", TRAIN, init, dataset, seed=0, round_idx=0,
            clients=idxs[:-1], aggregate=True)["variables"]
    else:
        chunk = ssd._chunk
        if fault == "chunks_as_separate_rows":
            bound = 2e-3
            ssd._chunk = lambda a, state, x: chunk(
                a, jnp.zeros_like(state), x)
        elif fault == "a_residual_multiplier_of_one":
            module = module.clone(residual_multiplier=1.0)
        try:
            api = _fold_api(dataset, module,
                            lr=TRAIN["lr"] / (2 if fault == "a_halved_step"
                                              else 1))
            api.variables = jax.tree.map(jnp.asarray, init)
            assert list(api.run_round(0)[0]) == list(idxs)
        finally:
            ssd._chunk = chunk
        wrong = jax.device_get(api.variables)
    reading = _dist(wrong, ref["variables"]) / change
    assert reading > bound, f"{fault}: {reading:.4f} of the change"
