"""The folded round (``FedAvgConfig.fold_clients``): a cohort trained client
by client inside one round program, each result folded into a running sum
by the in-place Pallas kernel - against the stacked round, and the things
that must not move when the fold is unset. Also the token bound of
``make_eval``'s batches and the sequence-row counters."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms import fedavg
from fedml_tpu.algorithms.fedavg import (FedAvgAPI, FedAvgConfig,
                                         make_folded_body, make_vmapped_body)
from fedml_tpu.core import pytree as pt
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.data.synthetic import make_blob_federated
from fedml_tpu.models import create_model
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.ops.aggregate import fold_weighted, tree_fold_pallas
from fedml_tpu.trainer import functional
from fedml_tpu.trainer.functional import (TrainConfig, make_eval,
                                          round_lr_scale)
from fedml_tpu.trainer.tasks import classification_head

VOCAB, LENGTH = 64, 24
# widths at which the matrices are ones the fold kernel takes (rows a
# multiple of 8, columns of 128) beside vectors and narrow ones it leaves
SMALL = dict(hidden_size=128, num_heads=4, num_kv_heads=2,
             intermediate_size=128, sliding_window=8,
             layer_ids=(0, 1, 16, 17, 18, 19), scan_chunk=8, scan_lanes=2,
             attn_block=8)


def _token_silos(silos=6, rows=(2, 2, 1, 2, 2, 2), seed=0):
    rs = np.random.RandomState(seed)
    train, test = {}, {}
    for c in range(silos):
        seq = rs.randint(0, VOCAB, (rows[c] + 1, LENGTH + 1)).astype(np.int32)
        train[c] = (seq[:-1, :-1], seq[:-1, 1:])
        test[c] = (seq[-1:, :-1], seq[-1:, 1:])
    return FederatedDataset.from_client_arrays(train, test, class_num=VOCAB)


def _api(dataset, module, task, **config):
    train = config.pop("train", TrainConfig(epochs=1, batch_size=1, lr=0.05))
    return FedAvgAPI(dataset, module, task=task, config=FedAvgConfig(
        comm_round=4, client_num_per_round=4, prefetch_depth=0, train=train,
        **config))


def _in_place(local_train):
    """``local_train`` deaf to ``shared_init``: under ``make_folded_body``
    the folded round as it was before the first step left the loop."""
    def train(*args, shared_init=False, **kwargs):
        return local_train(*args, **kwargs)

    return train


# -- the kernel -------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (24, 384), (520, 256),
                                   (16, 4096), (160, 5120), (3, 8, 128),
                                   (4, 16, 256)])
def test_fold_kernel_equals_the_plain_sum(shape):
    rs = np.random.RandomState(0)
    acc = jnp.asarray(rs.randn(*shape), jnp.float32)
    x = jnp.asarray(rs.randn(*shape), jnp.float32)
    got = jax.jit(lambda a, b: fold_weighted(a, b, 0.3, interpret=True))(
        acc, x)
    text = str(jax.make_jaxpr(
        lambda a, b: fold_weighted(a, b, 0.3, interpret=True))(acc, x))
    assert "pallas_call" in text
    np.testing.assert_allclose(got, acc + np.float32(0.3) * x, rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("shape", [(2560,), (64,), (5120, 16), (4, 5120),
                                   (5120, 192), (3, 4, 128), (2, 8, 64)])
def test_leaves_the_kernel_cannot_tile_go_to_xla(shape):
    acc, x = jnp.ones(shape), jnp.full(shape, 2.0)
    text = str(jax.make_jaxpr(
        lambda a, b: fold_weighted(a, b, 0.25, interpret=True))(acc, x))
    assert "pallas_call" not in text
    np.testing.assert_array_equal(
        fold_weighted(acc, x, 0.25, interpret=True), 1.5)


def test_folding_every_client_gives_the_weighted_mean():
    rs = np.random.RandomState(1)
    clients = [{"w": jnp.asarray(rs.randn(16, 128), jnp.float32),
                "b": jnp.asarray(rs.randn(7), jnp.float32)}
               for _ in range(3)]
    sizes = np.asarray([5.0, 1.0, 2.0], np.float32)
    acc = jax.tree.map(jnp.zeros_like, clients[0])
    for client, n in zip(clients, sizes):
        acc = tree_fold_pallas(acc, client, n / sizes.sum(), interpret=True)
    want = pt.tree_weighted_mean(
        jax.tree.map(lambda *leaves: jnp.stack(leaves), *clients),
        jnp.asarray(sizes))
    for got, mean in zip(jax.tree.leaves(acc), jax.tree.leaves(want)):
        np.testing.assert_allclose(got, mean, rtol=1e-6, atol=1e-6)


# -- the folded round against the stacked round -----------------------------------

@pytest.fixture(scope="module")
def hybrid():
    return _token_silos(), create_model("sambay", output_dim=VOCAB, **SMALL)


def test_folded_round_equals_the_stacked_round(hybrid):
    dataset, module = hybrid
    with jax.default_matmul_precision("highest"):
        api = _api(dataset, module, "lm_rows")
        _, (x, y, mask, keys, weights, _) = api._pack_round(0)[1:]
        assert sorted(np.asarray(weights).tolist()) == [1.0, 2.0, 2.0, 2.0]
        stacked_body = make_vmapped_body(api._local_train)
        folded_body = make_folded_body(api._local_train, interpret=True)

        @jax.jit
        def stacked(variables):
            clients, totals = stacked_body(variables, x, y, mask, keys)
            return pt.tree_weighted_mean(clients, weights), totals

        want, want_stats = stacked(api.variables)
        text = str(jax.make_jaxpr(folded_body)(api.variables, x, y, mask,
                                               keys, weights))
        got, got_stats = jax.jit(folded_body)(api.variables, x, y, mask,
                                              keys, weights)
    assert "pallas_call" in text
    assert set(got_stats) == {"loss_sum", "count", "correct_sum"}
    assert float(got_stats["count"]) == float(jnp.sum(weights)) == 7.0
    # the same sums in the same order; a client trained alone and the same
    # client under a vmap may round a matrix product differently
    for key in want_stats:
        np.testing.assert_allclose(got_stats[key], want_stats[key],
                                   rtol=1e-6)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), got,
                         want)
    assert max(jax.tree.leaves(moved)) < 1e-6
    assert jax.tree.structure(got) == jax.tree.structure(api.variables)


def test_two_folded_rounds_one_program_and_both_counters(hybrid):
    dataset, module = hybrid
    api = _api(dataset, module, "lm_rows", fold_clients=True)
    before = jax.device_get(api.variables)
    rounds = [api.run_round(r) for r in range(2)]
    assert api._round_fn._cache_size() == 1  # no recompilation
    for idxs, stats in rounds:
        rows = sum(dataset.train_data_local_num_dict[int(c)] for c in idxs)
        assert float(stats["count"]) == rows
        assert np.isfinite(float(stats["loss_sum"]))
    n_pad = 2  # the longest silo's rows at batch size 1
    assert api.timer.counters["clients_folded"] == 8
    assert api.timer.counters["rows_dispatched"] == 2 * 4 * n_pad
    assert api.timer.counters["tokens_dispatched"] == 2 * 4 * n_pad * LENGTH
    after = jax.device_get(api.variables)
    assert jax.tree.structure(after) == jax.tree.structure(before)
    assert any(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(after), jax.tree.leaves(before)))
    # the driver's own loop end to end: an evaluation of token rows
    rec = api.evaluate(1)
    assert rec["test_total"] == 6.0 and np.isfinite(rec["test_loss"])


def test_a_folded_api_run_equals_the_stacked_apis(hybrid):
    dataset, module = hybrid
    with jax.default_matmul_precision("highest"):
        folded = _api(dataset, module, "lm_rows", fold_clients=True)
        stacked = _api(dataset, module, "lm_rows")
        for r in range(2):
            (ci, si), (cj, sj) = folded.run_round(r), stacked.run_round(r)
            assert list(ci) == list(cj)
            np.testing.assert_allclose(float(si["loss_sum"]),
                                       float(sj["loss_sum"]), rtol=1e-6)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         folded.variables, stacked.variables)
    assert max(jax.tree.leaves(moved)) < 2e-6


def test_the_plain_references_round_equals_the_folded_round(hybrid):
    """What decides ``correct`` on the chip, at a small size: the folded
    driver's own round 0 against ``hybrid_lm_local_sgd.run_round`` over the
    same cohort, in the harness's own norm."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "references",
        "hybrid_lm_local_sgd.py")
    spec = importlib.util.spec_from_file_location("hybrid_ref", path)
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    dataset, module = hybrid
    train = {"batch_size": 1, "epochs": 1, "lr": 0.05,
             "client_optimizer": "sgd"}
    with jax.default_matmul_precision("highest"):
        api = _api(dataset, module, "lm_rows", fold_clients=True)
        init = jax.device_get(api.variables)
        idxs, stats = api.run_round(0)
        got = jax.device_get(api.variables)
    ref = reference.run_round(module, "lm_rows", train, init, dataset,
                              seed=api.config.seed, round_idx=0,
                              clients=idxs, aggregate=True)

    def dist(a, b):
        return np.sqrt(sum(float(np.sum((np.asarray(x, np.float64) - y) ** 2))
                           for x, y in zip(jax.tree.leaves(a),
                                           jax.tree.leaves(b))))

    change = dist(init, ref["variables"])
    assert change > 0
    assert dist(got, ref["variables"]) < 1e-3 * change
    assert ref["count"] == {int(c): float(
        dataset.train_data_local_num_dict[int(c)]) for c in idxs}
    np.testing.assert_allclose(sum(ref["loss_sum"].values()),
                               float(stats["loss_sum"]), rtol=1e-5)


def test_an_aggregate_hook_cannot_be_folded(hybrid):
    dataset, module = hybrid
    with pytest.raises(ValueError, match="aggregate_hook"):
        FedAvgAPI(dataset, module, task="lm_rows",
                  config=FedAvgConfig(fold_clients=True),
                  aggregate_hook=lambda v, stacked, w, k: v)


# -- nothing moves where the fold is unset -----------------------------------------

@pytest.mark.parametrize("decay", [1.0, 0.9])
def test_an_unfolded_driver_traces_the_parents_round_program(decay):
    ds = make_blob_federated(client_num=8, n_samples=8 * 25, seed=0,
                             partition_method="homo")
    cfg = TrainConfig(epochs=1, batch_size=8, lr=0.1, lr_decay_round=decay)
    api = _api(ds, LogisticRegression(num_classes=ds.class_num),
               "classification", train=cfg)
    assert api.config.fold_clients is False
    _, args = api._pack_round(1)[1:]

    def parents_round_fn(variables, x, y, mask, keys, weights, agg_key,
                         round_idx):
        # FedAvgAPI's round_fn as commit 66c8797 had it (the CPU's hook)
        stacked, totals = api._vmapped_body(
            variables, x, y, mask, keys, round_lr_scale(cfg, round_idx))
        return pt.tree_weighted_mean(stacked, weights), totals

    ours, parents = (str(jax.make_jaxpr(fn)(api.variables, *args,
                                            jnp.uint32(1)))
                     for fn in (api._round_fn_py, parents_round_fn))
    assert ours == parents
    assert "pallas_call" not in ours
    api.run_round(0)
    assert "clients_folded" not in api.timer.counters
    assert "tokens_dispatched" not in api.timer.counters  # rows of floats
    assert api.timer.counters["rows_dispatched"] > 0


def test_the_stacked_round_counts_token_rows_too(hybrid):
    dataset, module = hybrid
    api = _api(dataset, module, "lm_rows")
    api.run_round(0)
    assert api.timer.counters["tokens_dispatched"] == 4 * 2 * LENGTH
    assert "clients_folded" not in api.timer.counters


# -- make_eval's batches ------------------------------------------------------------

def test_classification_evaluation_is_the_parents_to_the_bit():
    model = LogisticRegression(num_classes=5)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1100, 12), jnp.float32)
    y = jnp.asarray(rs.randint(0, 5, 1100))
    mask = jnp.ones(1100)
    variables = model.init(jax.random.key(0), x[:1], train=False)

    def parents(variables, x, y, mask):
        # make_eval's evaluate as commit 66c8797 had it: 512 rows a batch
        pad = 3 * 512 - 1100
        xb = jnp.pad(x, [(0, pad), (0, 0)]).reshape(3, 512, 12)
        yb = jnp.pad(y, [(0, pad)]).reshape(3, 512)
        mb = jnp.pad(mask, (0, pad)).reshape(3, 512)

        def step(carry, batch):
            out = model.apply(variables, batch[0], train=False)
            return carry, classification_head(out, batch[1], batch[2])

        _, stats = jax.lax.scan(step, 0, (xb, yb, mb))
        return jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)

    got = jax.jit(make_eval(model, "classification"))(variables, x, y, mask)
    want = jax.jit(parents)(variables, x, y, mask)
    assert float(got["count"]) == 1100.0
    for key in want:
        np.testing.assert_array_equal(got[key], want[key])


@pytest.mark.parametrize("length, rows, batch", [
    (2048, 16, 4), (8192, 3, 1), (20000, 2, 1), (80, 700, 102), (80, 40, 40)])
def test_token_rows_are_evaluated_in_batches_bounded_in_tokens(
        length, rows, batch):
    assert functional.EVAL_BATCH_TOKENS == 8192
    seen = []

    class Probe:
        def apply(self, variables, x, train=False):
            seen.append(x.shape)
            return jnp.zeros(x.shape + (3,))

    x = jnp.zeros((rows, length), jnp.int32)
    stats = jax.eval_shape(make_eval(Probe(), "lm_rows"), {"params": {}}, x,
                           x, jnp.ones(rows))
    assert set(stats) == {"loss_sum", "count", "correct_sum"}
    assert seen == [(batch, length)]


def test_the_fold_is_one_config_field_and_off_by_default():
    fields = {f.name: f.default for f in dataclasses.fields(FedAvgConfig)}
    assert fields["fold_clients"] is False
    assert fedavg.make_folded_body is make_folded_body


# -- what PR 30 (the stacked mean leaf by leaf) must not have moved ---------------

def _digest(fn, *args) -> str:
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]


#: the first 16 hex digits of sha256(str(jaxpr)) as commit c3a6b52 (before
#: the stacked mean went leaf by leaf) traces these under this JAX. Since
#: PR 38 ``make_folded_body`` starts a silo out of place: a ``local_train``
#: deaf to ``shared_init`` still traces c3a6b52's round under it, and the
#: round with the first step before the loop is pinned as PR 38 traces it
PARENT = {"fold_weighted": "99ab2da55ece564a",
          "tree_fold_pallas": "9d91e92b7d7db19d",
          "make_folded_body": "628c1bd4a2e0ea6d",
          "make_folded_body.shared_init": "3ae6ecbd3044d48c"}


@pytest.mark.parametrize("what", sorted(PARENT))
def test_the_fold_traces_the_parents_program(what, hybrid):
    if what.startswith("make_folded_body"):
        dataset, module = hybrid
        api = _api(dataset, module, "lm_rows")
        _, (x, y, mask, keys, weights, _) = api._pack_round(0)[1:]
        train = (api._local_train if what.endswith("shared_init")
                 else _in_place(api._local_train))
        got = _digest(make_folded_body(train, interpret=True),
                      api.variables, x, y, mask, keys, weights)
    elif what == "tree_fold_pallas":
        tree = {"w": jnp.zeros((520, 256)), "narrow": jnp.zeros((5120, 16)),
                "b": jnp.zeros((7,))}
        got = _digest(lambda a, b, w: tree_fold_pallas(
            a, b, w, interpret=True), tree, tree, jnp.float32(0.25))
    else:
        acc = jnp.zeros((160, 5120))
        got = _digest(lambda a, b: fold_weighted(
            a, b.astype(jnp.bfloat16), 0.3, interpret=True), acc, acc)
    assert got == PARENT[what]


def test_the_sim_round_differs_from_the_parents_only_in_the_aggregation():
    """The round a TPU runs: the trainer's equations are the vmapped body's,
    the parent's (test_cohort_tiers holds that body to the parent's), and
    every equation after them is inside ``fedml.aggregate``."""
    from fedml_tpu.ops import tree_weighted_mean_pallas

    ds = make_blob_federated(client_num=8, n_samples=8 * 25, seed=0,
                             partition_method="homo")
    api = _api(ds, LogisticRegression(num_classes=ds.class_num),
               "classification",
               train=TrainConfig(epochs=1, batch_size=8, lr=0.1))
    _, (x, y, mask, keys, weights, _) = api._pack_round(1)[1:]

    def tpu_round(variables, x, y, mask, keys, weights):
        stacked, totals = api._vmapped_body(variables, x, y, mask, keys,
                                            None)
        return tree_weighted_mean_pallas(stacked, weights,
                                         interpret=True), totals

    ours = jax.make_jaxpr(tpu_round)(api.variables, x, y, mask, keys,
                                     weights).jaxpr
    body = jax.make_jaxpr(api._vmapped_body)(api.variables, x, y, mask,
                                             keys).jaxpr
    n = len(body.eqns)
    assert [str(e.primitive) for e in ours.eqns[:n]] == [
        str(e.primitive) for e in body.eqns]
    assert [[v.aval for v in e.outvars] for e in ours.eqns[:n]] == [
        [v.aval for v in e.outvars] for e in body.eqns]
    rest = ours.eqns[n:]
    assert rest and all("fedml.aggregate" in str(e.source_info.name_stack)
                        for e in rest)
    assert not any("fedml.aggregate" in str(e.source_info.name_stack)
                   for e in ours.eqns[:n])
