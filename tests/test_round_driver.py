"""The one round driver: ``FedAvgAPI`` owns the host half of a round
(sampling, order, pack, upload, keys, prefetcher, counters, the loop) and
``DistributedFedAvgAPI`` is that driver placed on a mesh. Both are held
here to the same contract, the mesh on CPU devices."""

import dataclasses

import flax.linen as nn
import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.data.synthetic import make_powerlaw_blob_federated
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                     DistributedFedAvgConfig, build_mesh)
from fedml_tpu.trainer.functional import TrainConfig

BSZ, CLASSES, MESH = 8, 5, 4
VOCAB, LENGTH = 32, 12


def _blobs(clients=12):
    return make_powerlaw_blob_federated(client_num=clients, dim=16,
                                        class_num=CLASSES, seed=3)


def _build(driver, ds, module, cohort, depth, task="classification",
           bsz=BSZ):
    knobs = dict(comm_round=8, client_num_per_round=cohort, seed=5,
                 frequency_of_the_test=10 ** 9, prefetch_depth=depth,
                 train=TrainConfig(epochs=1, batch_size=bsz, lr=0.1))
    if driver == "sim":
        return FedAvgAPI(ds, module, task=task, config=FedAvgConfig(**knobs))
    return DistributedFedAvgAPI(
        ds, module, task=task,
        mesh=build_mesh({"clients": MESH}, jax.devices()[:MESH]),
        config=DistributedFedAvgConfig(**knobs))


def _api(driver, ds, cohort, depth):
    return _build(driver, ds, LogisticRegression(num_classes=CLASSES),
                  cohort, depth)


def _host(tree):
    """Device arrays and typed keys as numpy, for a bit-for-bit compare."""
    return [np.asarray(jax.random.key_data(leaf)
                       if jax.dtypes.issubdtype(leaf.dtype,
                                                jax.dtypes.prng_key)
                       else leaf) for leaf in jax.tree.leaves(tree)]


def _spy_on_round_fn(api):
    """Record what ``run_round`` hands ``_round_fn`` after the model."""
    seen, inner = [], api._round_fn

    def spy(variables, *operands):
        seen.append(_host(operands))
        return inner(variables, *operands)

    api._round_fn = spy
    return seen


# -- (a) the mesh class is a placement, not a second driver ------------------

def test_the_mesh_driver_is_a_fedavg_driver():
    assert issubclass(DistributedFedAvgAPI, FedAvgAPI)


@pytest.mark.parametrize("name", [
    "run_round", "_host_round_inputs", "_round_prefetcher",
    "prefetch_stats", "release_prefetch", "_pack_round", "_pack_cohort",
    "_train_rounds"])
def test_the_mesh_driver_inherits_the_host_half(name):
    assert name not in vars(DistributedFedAvgAPI)
    assert getattr(DistributedFedAvgAPI, name) is getattr(FedAvgAPI, name)


@pytest.mark.parametrize("name", [
    "_put", "_pad_round", "_build_programs", "_round_inputs",
    "_round_operands", "_round_devices", "evaluate"])
def test_the_mesh_driver_overrides_the_seams(name):
    assert name in vars(DistributedFedAvgAPI) and name in vars(FedAvgAPI)


# -- (b) the prefetched and the serial path are one function -----------------

@pytest.mark.parametrize("driver", ["sim", "spmd"])
def test_prefetched_and_serial_rounds_get_identical_inputs(driver):
    ds = _blobs()
    serial, piped = _api(driver, ds, 6, 0), _api(driver, ds, 6, 2)
    got = []
    for api in (serial, piped):
        seen = _spy_on_round_fn(api)
        cohorts = [list(api.run_round(r)[0]) for r in range(4)]
        got.append((cohorts, seen))
        api.release_prefetch()
    assert serial.prefetch_stats() is None
    assert piped.prefetch_stats()["hits"] >= 2
    (cohorts_s, seen_s), (cohorts_p, seen_p) = got
    assert cohorts_s == cohorts_p
    assert len(seen_s) == len(seen_p) == 4
    for ops_s, ops_p in zip(seen_s, seen_p):
        assert len(ops_s) == len(ops_p) >= 5
        for a, b in zip(ops_s, ops_p):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    # and the rounds they ran left the same model, to the bit
    for a, b in zip(_host(serial.variables), _host(piped.variables)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("driver", ["sim", "spmd"])
def test_pack_round_is_what_a_round_dispatches(driver):
    """``_pack_round`` called directly (the audits, ``chip_smoke.py``) gives
    the operands ``run_round`` dispatched for that round."""
    ds = _blobs()
    api = _api(driver, ds, 6, 0)
    seen = _spy_on_round_fn(api)
    idxs, _ = api.run_round(2)
    dataset, again, args = api._pack_round(2)
    assert dataset is ds and list(again) == list(idxs)
    want = _host(api._round_operands(args, 2))
    assert len(want) == len(seen[0])
    assert all(np.array_equal(a, b) for a, b in zip(seen[0], want))


@pytest.mark.parametrize("driver", ["sim", "spmd"])
def test_full_participation_packs_once(driver):
    ds = _blobs(clients=8)
    api = _api(driver, ds, 8, 2)
    seen = _spy_on_round_fn(api)
    for r in range(3):
        api.run_round(r)
    assert api.prefetch_stats() is None  # the resident cohort, no pipeline
    records = api.timer.round_records()
    assert "pack" in records[0]["phases"]
    for rec in records[1:]:
        assert "pack" not in rec["phases"] and "upload" not in rec["phases"]
        assert "produce" in rec["phases"]
    assert api.timer.counts["pack"] == api.timer.counts["upload"] == 1
    # the same cohort on the device, new keys each round
    assert all(np.array_equal(a, b) for a, b in zip(seen[0][:3], seen[1][:3]))
    assert not np.array_equal(seen[0][3], seen[1][3])


@pytest.mark.parametrize("driver", ["sim", "spmd"])
def test_a_dataset_swap_drops_the_resident_cohort(driver):
    ds_a, ds_b = _blobs(clients=8), _blobs(clients=8)
    api = _api(driver, ds_a, 8, 0)
    api.run_round(0)
    assert api._pack_cache[0] is ds_a
    api.dataset = ds_b
    api.run_round(1)
    assert api._pack_cache[0] is ds_b
    assert api.timer.counts["pack"] == 2


# -- (c) the counters mean on a mesh what PERF.md says -----------------------

@pytest.mark.parametrize("cohort, slots", [(6, 8), (8, 8), (9, 12)])
def test_rows_dispatched_counts_the_mesh_padding(cohort, slots):
    ds = _blobs()
    api = _api("spmd", ds, cohort, 0)
    seen = _spy_on_round_fn(api)
    idxs, stats = api.run_round(0)
    assert len(idxs) == cohort  # the sampled cohort, not the slots
    x, _, mask, _, weights = seen[0]
    assert x.shape[0] == slots
    n_pad = ds.cohort_padded_len(idxs, BSZ)
    assert api.timer.counters["rows_dispatched"] == slots * n_pad
    assert "tokens_dispatched" not in api.timer.counters  # rows of floats
    # the duplicate slots weigh nothing and train nothing
    assert not mask[cohort:].any() and not weights[cohort:].any()
    assert float(stats["count"]) == sum(
        ds.train_data_local_num_dict[int(c)] for c in idxs)


class _TinyLM(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        return nn.Dense(VOCAB)(nn.Embed(VOCAB, 8)(x))


def _token_clients(clients=6, rows=3, seed=0):
    rs = np.random.RandomState(seed)
    train, test = {}, {}
    for c in range(clients):
        seq = rs.randint(0, VOCAB, (rows + 1, LENGTH + 1)).astype(np.int32)
        train[c] = (seq[:-1, :-1], seq[:-1, 1:])
        test[c] = (seq[-1:, :-1], seq[-1:, 1:])
    return FederatedDataset.from_client_arrays(train, test, class_num=VOCAB)


@pytest.mark.parametrize("driver, slots", [("sim", 3), ("spmd", 4)])
def test_tokens_dispatched_counts_on_both_drivers(driver, slots):
    api = _build(driver, _token_clients(), _TinyLM(), 3, 0, task="nwp",
                 bsz=1)
    api.run_round(0)
    n_pad = 3  # every client's rows at batch size 1
    assert api.timer.counters["rows_dispatched"] == slots * n_pad
    assert api.timer.counters["tokens_dispatched"] == slots * n_pad * LENGTH
    assert "clients_folded" not in api.timer.counters


# -- the loop -----------------------------------------------------------------

@pytest.mark.parametrize("driver", ["sim", "spmd"])
def test_train_is_the_one_loop(driver):
    ds = _blobs()
    api = _api(driver, ds, 6, 2)
    api.config = dataclasses.replace(api.config, comm_round=5,
                                     frequency_of_the_test=2)
    final = api.train()
    assert [rec["round"] for rec in api.history] == [0, 2, 4]
    assert final is api.history[-1]
    for rec in api.history:
        assert np.isfinite(rec["train_loss_local"]) and rec["wall_s"] > 0
        assert 0.0 <= rec["test_acc"] <= 1.0
        assert "phase_dispatch_ms" in rec and "phase_eval_ms" in rec
    # the comm_round clamp left no speculative slot behind
    pf = api._prefetch[0]
    assert pf.stats()["hits"] >= 3
