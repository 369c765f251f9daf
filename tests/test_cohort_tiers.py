"""Ragged cohorts in size-ordered tiers (algorithms/fedavg.py
``make_vmapped_body`` over trainer/functional.py's bounded step loop).

The contract under test: a tier's loop ends at the last real batch of its
longest client and nothing else changes. Every client's model and stats are
bit for bit what the whole-length ``scan`` gives, in any client order, under
one compiled round program; a uniform federation keeps the parent's program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms import fedavg
from fedml_tpu.algorithms.fedavg import (FedAvgAPI, FedAvgConfig,
                                         cohort_tiers, make_vmapped_body)
from fedml_tpu.core import pytree as pt
from fedml_tpu.data.synthetic import (make_blob_federated,
                                      make_powerlaw_blob_federated)
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.functional import TrainConfig, real_batches

BSZ = 8
TIER = 4
CLASSES = 5


@pytest.fixture(autouse=True)
def small_tiers(monkeypatch):
    # the constant is sized for a chip's cohort; a CPU federation has 16
    monkeypatch.setattr(fedavg, "TIER_CLIENTS", TIER)


def _ragged(clients=40, seed=2):
    return make_powerlaw_blob_federated(client_num=clients, dim=16,
                                        class_num=CLASSES, seed=seed)


def _api(ds, cohort=16, epochs=1, **config):
    return FedAvgAPI(ds, LogisticRegression(num_classes=CLASSES),
                     config=FedAvgConfig(**{**dict(
                         comm_round=4, client_num_per_round=cohort, seed=7,
                         frequency_of_the_test=10 ** 9, prefetch_depth=0,
                         train=TrainConfig(epochs=epochs, batch_size=BSZ,
                                           lr=0.1)), **config}))


def _bodies(api):
    """The tiered body the api runs and the untiered one over the same
    trainer."""
    return api._vmapped_body, make_vmapped_body(api._local_train)


def _equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _steps(ds, idxs):
    return [-(-ds.train_data_local_num_dict[int(c)] // BSZ) for c in idxs]


# -- the arithmetic ----------------------------------------------------------

@pytest.mark.parametrize("epochs", [1, 2])
@pytest.mark.parametrize("order", ["by_size", "as_sampled", "reversed"])
def test_tiered_clients_are_bit_equal_to_the_whole_length_scan(order, epochs):
    ds = _ragged()
    api = _api(ds, epochs=epochs)
    assert api._tier_clients == TIER
    idxs, (x, y, mask, keys, w, _) = api._pack_round(1)[1:]
    if order != "by_size":
        # exactness does not need the sort: permute every aligned input
        perm = (np.random.RandomState(0).permutation(len(idxs))
                if order == "as_sampled" else np.arange(len(idxs))[::-1])
        x, y, mask, keys, w = (a[perm] for a in (x, y, mask, keys, w))
    tiered, whole = (jax.jit(b)(api.variables, x, y, mask, keys)
                     for b in _bodies(api))
    assert _equal(tiered[0], whole[0])  # every client's model
    assert float(tiered[1]["count"]) == float(whole[1]["count"])
    np.testing.assert_allclose(float(tiered[1]["loss_sum"]),
                               float(whole[1]["loss_sum"]), rtol=1e-6)
    mean = [pt.tree_weighted_mean(s, w) for s in (tiered[0], whole[0])]
    assert _equal(*mean)


@pytest.mark.parametrize("epochs", [1, 2])
def test_per_client_stats_are_bit_equal(epochs):
    """``count`` and ``loss_sum`` of each client: the bounded loop against
    the scan, under the vmap with one bound for all."""
    ds = _ragged()
    api = _api(ds, epochs=epochs)
    _, (x, y, mask, keys, _, _) = api._pack_round(0)[1:]
    # the short half of the size-ordered cohort, at the cohort's length
    x, y, mask, keys = (a[8:] for a in (x, y, mask, keys))
    cfg = api.config.train
    bound = jnp.max(jax.vmap(lambda m: real_batches(m, cfg))(mask))

    def run(**kw):
        return jax.jit(jax.vmap(
            lambda xc, yc, mc, kc: api._local_train(
                api.variables, xc, yc, mc, kc, **kw)[1]))(x, y, mask, keys)

    bounded, whole = run(n_steps=bound), run()
    assert 0 < int(bound) < x.shape[1] // BSZ
    for name in whole:
        assert np.array_equal(np.asarray(bounded[name]),
                              np.asarray(whole[name])), name


def test_a_bound_past_the_padded_length_is_the_whole_loop():
    ds = _ragged()
    api = _api(ds)
    _, (x, y, mask, keys, _, _) = api._pack_round(0)[1:]
    got = [jax.jit(lambda n: api._local_train(
        api.variables, x[0], y[0], mask[0], keys[0], n_steps=n))(n)
        for n in (x.shape[1] // BSZ, 10 ** 6)]
    assert _equal(got[0], got[1])


@pytest.mark.parametrize("shuffle", [True, False])
def test_real_batches_reads_the_schedule_it_bounds(shuffle):
    cfg = TrainConfig(batch_size=4, shuffle=shuffle)
    mask = np.zeros(16, np.float32)
    assert int(real_batches(mask, cfg)) == 0
    mask[:5] = 1
    assert int(real_batches(mask, cfg)) == 2
    mask[:] = 1
    assert int(real_batches(mask, cfg)) == 4
    # a hole in an unshuffled client: the loop must reach the last real row
    mask[:] = 0
    mask[9] = 1
    assert int(real_batches(mask, cfg)) == (1 if shuffle else 3)


# -- one program -------------------------------------------------------------

def test_rounds_of_other_size_profiles_share_one_compiled_program():
    ds = _ragged()
    api = _api(ds, pack="global")
    profiles = set()
    for r in range(4):
        idxs, _ = api.run_round(r)
        profiles.add(tuple(np.reshape(_steps(ds, idxs),
                                      (-1, TIER)).max(axis=1)))
    assert len(profiles) > 1
    assert api._round_fn._cache_size() == 1


def test_a_uniform_federation_traces_the_parents_round_program():
    ds = make_blob_federated(client_num=16, n_samples=16 * 25, seed=0,
                             partition_method="homo")
    api = _api(ds)
    assert api._tier_clients is None
    idxs, args = api._pack_round(1)[1:]
    assert list(idxs) == list(fedavg.sample_clients(1, 16, 16))

    def parents_body(variables, x, y, mask, keys, lr_scale=None):
        # make_vmapped_body's body as commit 2e2024d had it
        stacked, stats = jax.vmap(
            lambda v, xc, yc, mc, kc: api._local_train(
                v, xc, yc, mc, kc, lr_scale=lr_scale),
            in_axes=(None, 0, 0, 0, 0))(variables, x, y, mask, keys)
        return stacked, jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)

    ours, parents = (str(jax.make_jaxpr(b)(api.variables, *args[:4]))
                     for b in (api._vmapped_body, parents_body))
    assert ours == parents
    assert "while" not in ours


@pytest.mark.parametrize("cohort, tiers", [(16, 4), (8, 2), (4, 1), (6, 1),
                                           (10, 1)])
def test_tiers_need_two_whole_tiers(cohort, tiers):
    assert cohort_tiers(cohort, TIER) == tiers
    assert cohort_tiers(cohort, None) == 1
    api = _api(_ragged(), cohort=cohort)
    _, args = api._pack_round(0)[1:]
    text = str(jax.make_jaxpr(api._vmapped_body)(api.variables, *args[:4]))
    assert ("while" in text) == (tiers > 1)


# -- the order and the counter -----------------------------------------------

def test_the_cohort_is_packed_longest_first_and_stays_aligned():
    ds = _ragged()
    api = _api(ds)
    sampled = fedavg.sample_clients(2, ds.client_num, 16)
    idxs, (x, y, mask, keys, w, _) = api._pack_round(2)[1:]
    sizes = [ds.train_data_local_num_dict[int(c)] for c in idxs]
    assert sorted(idxs) == sorted(sampled)
    assert sizes == sorted(sizes, reverse=True)
    assert np.asarray(mask).sum(axis=1).tolist() == sizes
    np.testing.assert_array_equal(np.asarray(w), ds.client_weights(idxs))
    # a client's key follows the client, not its slot
    _, want, _ = fedavg.round_keys(
        api._base_key, 2, jnp.asarray(np.asarray(sampled), jnp.uint32))
    at = {int(c): i for i, c in enumerate(sampled)}
    assert all(np.array_equal(jax.random.key_data(keys[i]),
                              jax.random.key_data(want[at[int(c)]]))
               for i, c in enumerate(idxs))
    # the prefetcher's producer packs the same order
    _, again, _ = api._pack_round(2)
    assert list(again) == list(idxs)


@pytest.mark.parametrize("cohort", [16, 6])
def test_rows_dispatched_counts_the_steps_the_tiers_run(cohort):
    ds = _ragged()
    api = _api(ds, cohort=cohort)
    expected = []
    for r in range(3):
        idxs, _ = api.run_round(r)
        n_pad = ds.cohort_padded_len(idxs, BSZ)
        if cohort == 16:
            steps = np.reshape(_steps(ds, idxs), (4, TIER))
            expected.append(int(steps.max(axis=1).sum()) * TIER * BSZ)
            assert expected[-1] < cohort * n_pad
        else:  # one tier: every slot's padded length
            expected.append(cohort * n_pad)
    assert [rec["counters"]["rows_dispatched"]
            for rec in api.timer.round_records()] == expected


def test_full_participation_keeps_the_ordered_pack_on_the_device():
    ds = _ragged(clients=16)
    api = _api(ds)
    first, args = api._pack_round(0)[1:]
    sizes = [ds.train_data_local_num_dict[int(c)] for c in first]
    assert sizes == sorted(sizes, reverse=True) and len(set(sizes)) > 1
    assert api._pack_cache[1] == tuple(int(c) for c in first)
    again, args2 = api._pack_round(1)[1:]
    assert list(again) == list(first)
    assert all(a is b for a, b in zip(args[:3], args2[:3]))  # a cache hit
    assert api.timer.counts["pack"] == 1


def test_training_in_tiers_follows_the_untiered_trajectory():
    ds = _ragged()
    tiered, plain = _api(ds), _api(ds)
    plain._tier_clients = None  # as sampled, the whole-length scan
    plain._vmapped_body = make_vmapped_body(plain._local_train)
    body = plain._vmapped_body

    def round_fn(variables, x, y, mask, keys, weights, agg_key, round_idx):
        stacked, totals = body(variables, x, y, mask, keys, None)
        return pt.tree_weighted_mean(stacked, weights), totals

    plain._round_fn = jax.jit(round_fn)
    for r in range(4):
        (_, a), (_, b) = tiered.run_round(r), plain.run_round(r)
        assert float(a["count"]) == float(b["count"])
        np.testing.assert_allclose(float(a["loss_sum"]),
                                   float(b["loss_sum"]), rtol=1e-5)
    err = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree.leaves(tiered.variables), jax.tree.leaves(plain.variables)))
    scale = max(float(jnp.max(jnp.abs(x)))
                for x in jax.tree.leaves(plain.variables))
    assert err <= 1e-6 * scale


# -- what PR 30 (the stacked mean leaf by leaf) must not have moved, and its counter

def test_the_spmd_round_traces_the_parents_program():
    """``make_spmd_round`` and its ``_weighted_psum_mean`` as commit c3a6b52
    traced them: the first 16 hex digits of sha256(str(jaxpr)) under this
    JAX."""
    import hashlib

    from jax.sharding import Mesh

    from fedml_tpu.parallel.spmd import _weighted_psum_mean, make_spmd_round

    ds = make_blob_federated(client_num=16, n_samples=16 * 25, seed=0,
                             partition_method="homo")
    api = _api(ds)
    _, (x, y, mask, keys, weights, _) = api._pack_round(1)[1:]
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("clients",))
    round_fn = make_spmd_round(api.module, "classification",
                               api.config.train, mesh)
    stacked = jax.tree.map(lambda v: jnp.stack([v] * 4), api.variables)
    mean = jax.shard_map(
        lambda s, w: _weighted_psum_mean(s, w, ("clients",)), mesh=mesh,
        in_specs=(jax.P("clients"), jax.P("clients")), out_specs=jax.P())
    got = {
        "make_spmd_round": jax.make_jaxpr(round_fn)(
            api.variables, x, y, mask, keys, weights),
        "_weighted_psum_mean": jax.make_jaxpr(mean)(stacked, weights[:4])}
    assert {k: hashlib.sha256(str(v).encode()).hexdigest()[:16]
            for k, v in got.items()} == {
                "make_spmd_round": "53250671e36c295c",
                "_weighted_psum_mean": "7d82c41a055e5550"}


def _spmd_programs(api, x, y, mask, keys, weights):
    """name -> thunk tracing one of ``parallel/spmd.py``'s other round
    builders on the spmd guard's federation (three rounds a fused scan, two
    edge rounds on a 2x2 mesh)."""
    import dataclasses

    from jax.sharding import Mesh

    from fedml_tpu.parallel import spmd

    cfg = api.config.train
    flat = Mesh(np.asarray(jax.devices()[:4]), ("clients",))
    two_tier = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("group", "clients"))
    ids = jnp.arange(16, dtype=jnp.uint32)
    block = [jnp.stack([a] * 3) for a in (x, y, mask, ids, weights)]
    build = lambda make, *a, **kw: make(api.module, "classification", *a,
                                        **kw)
    r0 = jnp.uint32(0)
    return {
        "make_spmd_round[lr_decay]": lambda: jax.make_jaxpr(build(
            spmd.make_spmd_round,
            dataclasses.replace(cfg, lr_decay_round=0.9), flat))(
                api.variables, x, y, mask, keys, weights, jnp.uint32(1)),
        "make_spmd_multiround": lambda: jax.make_jaxpr(build(
            spmd.make_spmd_multiround, cfg, flat, 3))(
                api.variables, x, y, mask, ids, weights, api._base_key, r0),
        "make_spmd_block_multiround": lambda: jax.make_jaxpr(build(
            spmd.make_spmd_block_multiround, cfg, flat))(
                api.variables, *block, api._base_key, r0),
        "make_hierarchical_spmd_round": lambda: jax.make_jaxpr(build(
            spmd.make_hierarchical_spmd_round, cfg, two_tier,
            group_comm_round=2))(api.variables, x, y, mask, keys, weights),
    }


@pytest.mark.parametrize("builder, digest", [
    ("make_spmd_round[lr_decay]", "f3dcfeeeb7ca7c81"),
    ("make_spmd_multiround", "3790c402e35efeac"),
    ("make_spmd_block_multiround", "5870ab137cb972eb"),
    ("make_hierarchical_spmd_round", "a9571107287d0c34")])
def test_the_other_spmd_rounds_trace_the_parents_programs(builder, digest):
    """The decayed, fused and two-tier mesh rounds as commit 9f93359 (before
    they shared ``make_vmapped_clients``) traced them: sha256(str(jaxpr))
    as in the guard above, a ``frozenset`` of axis names printed in sorted
    order (its own order follows the process's hash seed)."""
    import hashlib
    import re

    ds = make_blob_federated(client_num=16, n_samples=16 * 25, seed=0,
                             partition_method="homo")
    api = _api(ds)
    _, _, (x, y, mask, keys, weights, _) = api._pack_round(1)
    text = re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(
            sorted(m.group(1).split(", "))),
        str(_spmd_programs(api, x, y, mask, keys, weights)[builder]()))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("model, args, row, cohort, kernel, total, dead", [
    ("resnet18_gn", dict(output_dim=100, small_images=False), (24, 24, 3),
     104, 11_010_048, 11_227_812, 6_946_816),
    ("resnet18_gn", dict(output_dim=100, small_images=True), (24, 24, 3),
     104, 11_010_048, 11_220_132, 0),
    ("cnn", dict(output_dim=62), (28, 28, 1), 256, 1_179_648, 1_206_590, 0)])
def test_the_driver_counts_what_the_mean_kernel_takes(
        monkeypatch, model, args, row, cohort, kernel, total, dead):
    """98.1 % of ResNet-18-GN and 97.8 % of the CNN go through the kernel,
    counted where the Pallas mean is the driver's aggregation: on a TPU,
    with no hook of the caller's and no fold. And what the local step
    leaves out: the taps of the published stem's last stage (a 1x1 map at
    24x24 crops) that only ever meet padding, 61.9 % of that model."""
    import fedml_tpu.utils as utils
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.models import create_model

    images = {c: (np.zeros((2,) + row, np.float32), np.zeros(2, np.int32))
              for c in range(2)}
    ds = FederatedDataset.from_client_arrays(images, images,
                                             class_num=args["output_dim"])
    config = FedAvgConfig(client_num_per_round=cohort, prefetch_depth=0,
                          train=TrainConfig(epochs=1, batch_size=2, lr=0.1))
    module = create_model(model, **args)
    counters = FedAvgAPI(ds, module, config=config).timer.counters
    assert "agg_kernel_params" not in counters  # the CPU's mean is XLA's
    assert counters["conv_dead_tap_params"] == dead  # on any backend
    # the local loop carries the rest (SGD leaves a zero gradient alone)
    assert counters["local_carried_params"] == total - dead
    monkeypatch.setattr(utils, "on_tpu", lambda: True)
    counters = FedAvgAPI(ds, module, config=config).timer.counters
    assert counters["conv_dead_tap_params"] == dead
    assert counters["agg_kernel_params"] == kernel
    assert counters["agg_kernel_params"] + counters["agg_xla_params"] == total
    assert round(100 * kernel / total, 1) == (98.1 if model != "cnn" else 97.8)
    assert "agg_kernel_params" not in FedAvgAPI(
        ds, module, config=config,
        aggregate_hook=lambda v, s, w, k: v).timer.counters
