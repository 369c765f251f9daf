"""The ``deepseek_v3`` decoder (kanana-2-30b-a3b-instruct-2601: latent
attention, routed experts beside shared ones) against its plain reference, at
small widths on the CPU: seeded weights, float32 at ``highest``; the shares
of a sparse layer against the uncut layer; the rope's reading; and the folded
FedAvg round against the reference's."""

import contextlib
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
from fedml_tpu.models import create_model, deepseek_v3
from fedml_tpu.models.common import rotary
from fedml_tpu.ops import moe
from fedml_tpu.ops.flash_attention import flash_attention_heads
from fedml_tpu.trainer.functional import TrainConfig, make_local_train
from fedml_tpu.trainer.tasks import RoutedTiedHead, lm_rows_head

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
#: published layers 0 (dense), 1, 2 (sparse); 4 heads of 24 + 8 | 16, latent
#: 32; experts 4-11 of 16, top-3, one shared expert
SMALL = dict(hidden_size=64, num_heads=4, qk_nope_head_dim=24,
             qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
             intermediate_size=96, moe_intermediate_size=32,
             n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3,
             experts_held=(4, 8), layer_ids=(0, 1, 2))


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v3_local_sgd", os.path.join(
            ROOT, "benchmark", "references", "deepseek_v3_local_sgd.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The core in blocks of 16 queries and the grouped products in blocks
    of 8 rows (the model runs the ops' own 512 and ``BLOCK``, read as the
    layer is traced), so that a row of 40 tokens spans blocks of queries
    and ends inside one, and an expert's segment spans blocks."""
    with mock.patch.object(deepseek_v3, "causal_attention", functools.partial(
            deepseek_v3.causal_attention, block=16)), \
            mock.patch.object(moe, "BLOCK", 8):
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


def _rows(length):
    rows = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (2, length + 1)))
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module")
def small():
    module = create_model("deepseek_v3", output_dim=VOCAB, **SMALL)
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


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("length", [40, 64])
def test_logits_equal_the_references(small, reference, length):
    """1e-5 of the largest logit: both sides are float32 at ``highest`` and
    differ in the order of their sums alone (blocks of queries and of an
    expert's rows here, whole matrices there); measured 5e-7. Rows of 40
    tokens end inside a block of 16 queries, rows of 64 on one."""
    module, variables, _, _ = small
    x, _ = _rows(length)
    got = jax.jit(module.clone(return_logits=True).apply)(variables, x)
    hp = reference.hyperparameters(module)
    want = jax.jit(lambda p: jnp.stack([
        reference.logits_of(p, hp, row) for row in x]))(variables["params"])
    assert got.shape == x.shape + (VOCAB,)
    assert _rel(got, want) < 1e-5


def test_loss_and_every_gradient_leaf_equal_the_references(small, reference):
    """1e-4 of a leaf's largest gradient: the backward pass of the grouped
    products is written out by hand and recomputes its hidden products, the
    attention blocks are rematerialised - float32 sums in another order, ten
    times the room of the logits'."""
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
    # the untied head and the embedding both learn; the bias only selects
    assert np.any(np.asarray(grads["lm_head"]))
    assert np.any(np.asarray(grads["embedding"]))
    for layer in ("layer_01", "layer_02"):
        assert not np.any(np.asarray(grads[layer]["expert_bias"]))
        assert not np.any(np.asarray(ref_grads[layer]["expert_bias"]))
        assert np.any(np.asarray(grads[layer]["shared_w2"]))


def test_one_sgd_step_equals_the_references_step(small, reference):
    module, variables, x, y = small
    lr, mask = 0.1, jnp.asarray([1.0, 1.0])
    grads = jax.jit(jax.grad(lambda p: _loss(module, p, x, y, mask)))(
        variables["params"])
    ours = jax.tree.map(lambda p, g: p - lr * g, variables["params"], grads)
    step = reference.make_step(module, "lm_rows", {"lr": lr}, remat=True)
    theirs, _, count = jax.jit(
        lambda p: step(p, x, y, mask, None))(variables["params"])
    assert float(count) == 2.0
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), ours,
                         theirs)
    assert max(jax.tree.leaves(moved)) < 1e-6


def test_layers_one_and_two_alone_are_the_full_models_layers(small):
    """A cut in depth keeps the published indices: with layer 0 made to add
    nothing (its two output projections zeroed) the full model is the model
    of ``layer_ids`` (1, 2) on the same leaves."""
    module, variables, x, _ = small
    params = jax.tree.map(lambda a: a, variables["params"])
    params["layer_00"] = {**params["layer_00"],
                          "o_proj": jnp.zeros_like(
                              params["layer_00"]["o_proj"]),
                          "ffn_w2": jnp.zeros_like(
                              params["layer_00"]["ffn_w2"])}
    full = jax.jit(module.clone(return_logits=True).apply)(
        {"params": params}, x)
    held = {k: v for k, v in params.items() if k != "layer_00"}
    part = jax.jit(module.clone(layer_ids=(1, 2), return_logits=True).apply)(
        {"params": held}, x)
    np.testing.assert_allclose(part, full, rtol=0, atol=1e-6)
    shapes = jax.eval_shape(lambda: module.clone(layer_ids=(1, 2)).init(
        jax.random.key(0), x[:1], train=False))["params"]
    assert set(shapes) == {"embedding", "lm_head", "final_norm", "layer_01",
                           "layer_02"}
    assert "router" in shapes["layer_01"] and "ffn_w1" not in shapes[
        "layer_01"]


def test_the_eight_shares_and_the_shared_experts_once_are_the_uncut_layer(
        small, reference):
    """The routed parts of the 8 models ``experts_held = (2 i, 2)`` plus the
    shared experts counted once equal the uncut reference's whole sparse
    layer: each share's layer is ``x + MLA + Routed_i + Shared``, so the
    eight sum to the whole layer and seven times the layer without routed
    experts (the reference's, ``experts`` false)."""
    module, variables, x, _ = small
    uncut = module.clone(experts_held=(0, 16))
    params = _seeded(uncut, x, seed=7)["params"]
    p = params["layer_01"]
    h = 0.5 * jax.random.normal(jax.random.key(3), (2, x.shape[1], 64))
    total = 0.0
    for i in range(8):
        share = module.clone(experts_held=(2 * i, 2))
        mine = {**p, **{name: p[name][2 * i:2 * i + 2] for name in
                        ("experts_w1", "experts_w3", "experts_w2")}}
        out, load, _ = jax.jit(lambda q, h, share=share: deepseek_v3._layer(
            q, h, dense=False, cfg=share.cfg()))(mine, h)
        assert load.shape == (2, 2)
        total = total + out
    hp = reference.hyperparameters(uncut)
    whole = jnp.stack([reference._layer(p, row, 1, hp, False, True, True)
                       for row in h])
    without = jnp.stack([reference._layer(p, row, 1, hp, False, False, True)
                         for row in h])
    routed = whole - without
    assert float(jnp.max(jnp.abs(routed))) > 0.01  # the experts matter
    assert _rel(total - 7.0 * without, whole) < 1e-5
    # and a share alone is not the layer
    assert _rel(out, whole) > 1e-3


# -- the rope ----------------------------------------------------------------------

def test_half_split_brings_the_pairs_to_rotate_halfs_order():
    x = jnp.arange(8.0)[None]
    np.testing.assert_array_equal(deepseek_v3.half_split(x)[0],
                                  [0, 2, 4, 6, 1, 3, 5, 7])
    # channel pair (2i, 2i + 1) at position t turns by t theta^(-2i / D)
    v = jnp.asarray(np.random.RandomState(0).randn(5, 8), jnp.float32)
    got = rotary(deepseek_v3.half_split(v), 100.0)
    t, i = 3, 2
    angle = t * 100.0 ** (-2 * i / 8)
    a, b = float(v[t, 2 * i]), float(v[t, 2 * i + 1])
    np.testing.assert_allclose(
        (got[t, i], got[t, i + 4]),
        (a * np.cos(angle) - b * np.sin(angle),
         b * np.cos(angle) + a * np.sin(angle)), rtol=1e-5)


def test_the_interleaved_reading_is_not_rotate_half_on_the_stored_order(
        small, reference):
    """Rotate-half without the permutation pairs channel ``i`` with ``i +
    D/2`` where the stored pairs are ``(2i, 2i + 1)``: other logits than the
    program's, and than the reference's pairwise rotation."""
    module, variables, x, _ = small
    logits = module.clone(return_logits=True)
    with mock.patch.object(deepseek_v3, "half_split", lambda x: x):
        # a function of its own: no trace is shared with the sound call
        got = jax.jit(lambda v, t: logits.apply(v, t))(variables, x)
    ours = jax.jit(logits.apply)(variables, x)
    assert _rel(got, ours) > 1e-3
    hp = reference.hyperparameters(module)
    want = jnp.stack([reference.logits_of(variables["params"], hp, row)
                      for row in x])
    assert _rel(ours, want) < 1e-5 < 1e-3 < _rel(got, want)


def test_the_rope_key_is_one_for_all_the_heads(small):
    """``W_kv_a`` gives the latent and ONE key of ``qk_rope_head_dim``; each
    head's key ends in it. Moving the key's eight columns of ``W_kv_a``
    moves every head's scores; a per-head key would need 4 x 8 columns."""
    module, variables, x, _ = small
    p = variables["params"]["layer_01"]
    assert p["kv_a_proj"].shape == (64, 32 + 8)
    assert p["kv_b_proj"].shape == (32, 4 * (24 + 16))
    assert p["q_proj"].shape == (64, 4 * (24 + 8))
    assert p["o_proj"].shape == (4 * 16, 64)
    shapes = {}

    def core(q, k, v, **kw):
        """The rope part of each head's key, then eight of its own
        channels, in place of the core's output."""
        shapes.update(q=q.shape, k=k.shape, v=v.shape)
        return jnp.concatenate([k[..., 24:], k[..., :8]], axis=-1)

    s = jax.random.normal(jax.random.key(0), (1, x.shape[1], 64))
    with mock.patch.object(deepseek_v3, "causal_attention", core):
        out = deepseek_v3._mla({**p, "o_proj": jnp.eye(64)}, s, module.cfg())
    assert shapes == {"q": (4, x.shape[1], 32), "k": (4, x.shape[1], 32),
                      "v": (4, x.shape[1], 16)}
    keys = out[0].reshape(x.shape[1], 4, 16)
    for head in range(1, 4):
        np.testing.assert_array_equal(keys[:, head, :8], keys[:, 0, :8])
        assert np.any(np.asarray(keys[:, head, 8:] != keys[:, 0, 8:]))
    # position 0 is not turned: the key there is the projection itself
    np.testing.assert_allclose(
        keys[0, 0, :8],
        deepseek_v3.half_split((s[0, 0] @ p["kv_a_proj"])[32:]), rtol=1e-5)


# -- sizes and kinds -------------------------------------------------------------

def test_parameter_count_at_the_published_widths():
    module = create_model("deepseek_v3", output_dim=16032,
                          experts_held=[0, 16], layer_ids=[0, 1, 2, 3, 4, 5])
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    sizes = {name: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(group))
             for name, group in shapes["params"].items()}
    attention = 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    assert attention == 26_345_984
    assert sizes["layer_00"] == attention + 4_096 + 37_748_736 == 64_098_816
    sparse = attention + 4_096 + 262_144 + 128 + 9_437_184 + 16 * 4_718_592
    for layer in range(1, 6):
        assert sizes[f"layer_{layer:02d}"] == sparse == 111_547_008
    assert sizes["embedding"] == sizes["lm_head"] == 32_833_536
    assert sizes["final_norm"] == 2_048
    assert sum(sizes.values()) == 687_502_976
    layer = shapes["params"]["layer_03"]
    assert layer["experts_w1"].shape == (16, 2048, 768)
    assert layer["router"].shape == (2048, 128)
    assert layer["shared_w1"].shape == (2048, 1536)
    assert layer["kv_a_proj"].shape == (2048, 576)
    # every expert of all 48 layers: the published model's 30 B
    whole = create_model("deepseek_v3", output_dim=128256)
    shapes = jax.eval_shape(lambda: whole.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 30.0e9 < count < 31.0e9


def test_the_expert_bias_is_one_draw_for_every_seed():
    module = create_model("deepseek_v3", output_dim=8, **SMALL)
    tokens = jnp.zeros((1, 4), jnp.int32)
    a = module.init(jax.random.key(0), tokens, train=False)["params"]
    b = module.init(jax.random.key(1), tokens, train=False)["params"]
    np.testing.assert_array_equal(a["layer_01"]["expert_bias"],
                                  b["layer_01"]["expert_bias"])
    assert np.any(np.asarray(a["layer_01"]["expert_bias"])
                  != np.asarray(a["layer_02"]["expert_bias"]))
    assert np.any(np.asarray(a["layer_01"]["router"])
                  != np.asarray(b["layer_01"]["router"]))
    assert np.any(np.asarray(a["lm_head"]) != np.asarray(a["embedding"]))


def test_the_output_is_a_routed_head_with_the_untied_leaf(small):
    module, variables, x, y = small
    out = jax.jit(module.apply)(variables, x)
    assert isinstance(out, RoutedTiedHead)
    assert out.expert_load.shape == (2, 2, 8)
    np.testing.assert_array_equal(out.embedding,
                                  variables["params"]["lm_head"])
    stats = lm_rows_head(out, y, jnp.ones(2))
    assert set(stats) == {"loss_sum", "count", "correct_sum",
                          "moe_assignments", "moe_top_expert_assignments",
                          "moe_block_rows"}
    assert 0 < float(stats["moe_assignments"]) <= 2 * 2 * x.shape[1] * 3
    assert float(stats["moe_assignments"]) == float(out.expert_load.sum())
    # the rows the loops ran: blocks in use x block, both sparse layers
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
        "intermediate_size": 128, "kv_lora_rank": 128}
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
    of 6 tiny silos) and ``deepseek_v3_local_sgd.run_round`` over the same
    cohort."""
    dataset = _token_silos()
    module = create_model("deepseek_v3", output_dim=VOCAB, **FOLD)
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
    np.testing.assert_allclose(got["params"]["layer_01"]["expert_bias"],
                               init["params"]["layer_01"]["expert_bias"],
                               rtol=1e-6)


@pytest.mark.parametrize("fault", ["no_shared_experts",
                                   "rope_off_shared_key", "top_k_less_one"])
def test_the_timed_bound_fails_the_planted_faults(folded, fault):
    """The controls of ``benchmark/tools/mla_check_controls.py`` at the
    small size: each lands further from the reference's round than the
    configuration's ``check.timed.param_fraction`` of the change."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana_2_30b_a3b_ep8.json")) as f:
        bound = json.load(f)["check"]["timed"]["param_fraction"]
    dataset, module, init, idxs, _, _, ref = folded
    rope = deepseek_v3._rope
    if fault == "no_shared_experts":
        patch = mock.patch.object(deepseek_v3, "_shared_experts",
                                  lambda p, s: jnp.zeros_like(s))
    elif fault == "rope_off_shared_key":
        patch = mock.patch.object(
            deepseek_v3, "_rope",
            lambda x, cfg: x if x.shape[1] == 1 else rope(x, cfg))
    else:
        patch = contextlib.nullcontext()
        module = module.clone(num_experts_per_tok=2)
    with patch:
        _, trained, _, wrong = _fold_round0(dataset, module, init)
    assert list(trained) == list(idxs)
    reading = _dist(wrong, ref["variables"]) / _dist(init, ref["variables"])
    assert reading > bound, f"{fault}: {reading:.4f} of the change"


# -- the core through the Pallas kernel (the TPU path, interpreted) -------------------

def _through_the_kernel(monkeypatch):
    """``_core`` as it runs on a TPU - the flash kernel, here interpreted, in
    blocks of 8 queries and 8 keys so that a row spans several of each -
    and the model's count of it: ``on_tpu`` answers for the chip the CPU
    tests cannot reach."""
    traced = []

    def kernel(q, k, v, **kw):
        traced.append(q.shape)
        return flash_attention_heads(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(deepseek_v3, "on_tpu", lambda: True)
    monkeypatch.setattr(deepseek_v3, "flash_attention_heads", kernel)
    monkeypatch.setattr(deepseek_v3, "CORE_BLOCKS", (8, 8))
    return traced


@pytest.fixture
def kernel_core(monkeypatch):
    return _through_the_kernel(monkeypatch)


def _mla_grads(p, s, cfg):
    """``_mla``'s output and the gradient of a fixed projection of it with
    respect to every leaf of the block and its input, traced anew."""
    weight = jax.random.normal(jax.random.key(3), s.shape)

    def loss(p, s):
        return jnp.sum(deepseek_v3._mla(p, s, cfg) * weight)

    return (deepseek_v3._mla(p, s, cfg),) + jax.grad(loss, (0, 1))(p, s)


def test_the_kernel_core_equals_the_xla_core(small, monkeypatch):
    """On the CPU at ``highest`` the interpreted kernel and XLA's blockwise
    attention differ in the order of their sums alone: the block's output,
    the gradient of every leaf of ``W_q``, ``W_kv_a``, the latent norm,
    ``W_kv_b``, ``W_o`` and of the input agree to float32 rounding."""
    module, variables, x, _ = small
    p = {name: leaf for name, leaf in variables["params"]["layer_01"].items()
         if name in ("q_proj", "kv_a_proj", "kv_norm_scale", "kv_b_proj",
                     "o_proj")}
    s = jax.random.normal(jax.random.key(2), (2, x.shape[1], 64))
    want = _mla_grads(p, s, module.cfg())
    traced = _through_the_kernel(monkeypatch)
    got = _mla_grads(p, s, module.cfg())
    assert traced and traced[0] == (2, 4, x.shape[1], 32)
    assert _rel(got[0], want[0]) < 1e-5
    for name in p:
        assert _rel(got[1][name], want[1][name]) < 1e-4, name
    assert _rel(got[2], want[2]) < 1e-4


def test_the_folded_round_through_the_kernel_and_its_counter(folded,
                                                             kernel_core):
    """The folded round with every core through the kernel lands where the
    XLA core's did (both float32 at ``highest``), and the counter reads the
    model's layers x the rows the round program stepped through."""
    dataset, module, init, idxs, _, got, _ = folded
    api, trained, _, ours = _fold_round0(dataset, module, init)
    assert kernel_core  # the round program was traced through the kernel
    assert list(trained) == list(idxs)
    assert _dist(ours, got) < 1e-4 * _dist(init, got)
    counters = api.timer.counters
    assert module.attention_cores_in_kernel() == 3
    assert counters["attention_cores_in_kernel"] == (
        3 * counters["rows_dispatched"])


def test_no_core_is_counted_off_the_chip(small):
    module = small[0]
    assert module.attention_cores_in_kernel() == 0


# -- the routing's loads are the dynamics', not the program's -------------------------

def _reference_held_loads(reference, hp, params, rows):
    """``[sparse layers, held]`` (token, choice) pairs on each held expert
    over ``rows``, by the reference's own equations: its layers carry the
    states, its router's scores plus the bias choose."""
    first, held = hp["experts_held"]
    states = [params["embedding"][row] for row in rows]
    loads = []
    for layer in hp["layers"]:
        p = params[f"layer_{layer:02d}"]
        if layer >= hp["num_dense_layers"]:
            total = np.zeros(held, np.int64)
            for x in states:
                x = x + reference._mla(p, reference._rms(
                    x, p["input_norm_scale"], hp["eps"]), hp, False, True)
                s = reference._rms(x, p["post_attention_norm_scale"],
                                   hp["eps"])
                prob = 1.0 / (1.0 + jnp.exp(-(s @ p["router"])))
                _, chosen = jax.lax.top_k(prob + p["expert_bias"],
                                          hp["top_k"])
                total += np.bincount(np.asarray(chosen).ravel(),
                                     minlength=hp["num_experts"])[
                                         first:first + held]
            loads.append(total)
        states = [reference._layer(p, x, layer, hp, False, True, True)
                  for x in states]
    return np.stack(loads)


def test_the_held_loads_after_three_rounds_are_the_references(reference):
    """Three folded FedAvg rounds of the program and three of the
    reference's from the same model, on silos of Zipf tokens as the cell's
    generator draws them: the held experts' loads, counted by the program's
    counter on its model and by the reference's equations on the
    reference's, agree before and after (measured: the same to the pair) -
    uneven loads are what the fixed bias, the content and local SGD give,
    whatever implements the layer (PR 39's review: on the chip one held
    expert of 16 takes most of the held pairs)."""
    from benchmark.generators import token_silos

    length = 48
    dataset, _ = token_silos.build(dict(
        sequence_length=length, vocab=VOCAB, train_rows=2, test_rows=1,
        zipf_s=1.1, follow_share=0.5), 8, 7)
    module = create_model("deepseek_v3", output_dim=VOCAB, **SMALL)
    init = jax.device_get(_seeded(module, jnp.zeros((1, length), jnp.int32),
                                  seed=9))
    rows = jnp.concatenate([jnp.asarray(dataset.test_data_local_dict[c][0])
                            for c in range(8)])
    hp = reference.hyperparameters(module)
    counter = jax.jit(lambda v: module.apply(v, rows).expert_load.sum(0))
    api = FedAvgAPI(dataset, module, task="lm_rows", config=FedAvgConfig(
        comm_round=4, client_num_per_round=4, prefetch_depth=0,
        fold_clients=True,
        train=TrainConfig(epochs=1, batch_size=1, lr=TRAIN["lr"])))
    api.variables = jax.tree.map(jnp.asarray, init)
    theirs = init
    before = np.asarray(counter(api.variables))
    np.testing.assert_array_equal(
        before, _reference_held_loads(reference, hp, init["params"], rows))
    for r in range(3):
        idxs, _ = api.run_round(r)
        theirs = jax.device_get(reference.run_round(
            module, "lm_rows", TRAIN, theirs, dataset, seed=api.config.seed,
            round_idx=r, clients=idxs, aggregate=True)["variables"])
    ours = np.asarray(counter(api.variables))
    want = _reference_held_loads(reference, hp, theirs["params"], rows)
    assert np.abs(ours - before).sum() > 0  # the routing moved
    # top-k is a step function of states that agree to rounding: a pair in
    # a hundred may land elsewhere
    assert np.abs(ours - want).sum() <= 0.01 * want.sum()
    peaks = SMALL["experts_held"][1] * want.max(-1) / want.sum(-1)
    assert np.all(peaks > 1.5)  # uneven in the reference as in the program
