"""SPMD distributed round tests on the 8-virtual-device CPU mesh.

The key invariant: the distributed mesh round computes EXACTLY the same
aggregation as the vmapped standalone simulation (both re-express the
reference's weighted state_dict average) — so simulation results transfer to
hardware."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core import pytree as pt
from fedml_tpu.data.synthetic import make_blob_federated
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                     DistributedFedAvgConfig, build_mesh,
                                     make_hierarchical_spmd_round,
                                     make_spmd_round)
from fedml_tpu.trainer.functional import TrainConfig


@pytest.fixture(scope="module")
def mesh8():
    return build_mesh({"clients": 8})


class TestSpmdRound:
    def test_matches_vmapped_simulation_exactly(self, mesh8):
        ds = make_blob_federated(client_num=8, partition_method="hetero",
                                 seed=0)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=2, batch_size=16, lr=0.1)
        cfg = dict(comm_round=3, client_num_per_round=8,
                   frequency_of_the_test=100)
        sim = FedAvgAPI(ds, model, config=FedAvgConfig(train=tc, **cfg))
        dist = DistributedFedAvgAPI(
            ds, model, mesh=mesh8,
            config=DistributedFedAvgConfig(train=tc, **cfg))
        for r in range(3):
            sim.run_round(r)
            dist.run_round(r)
        diff = float(pt.tree_norm(pt.tree_sub(sim.variables, dist.variables)))
        assert diff < 1e-5, diff
        # the fresh init goes in committed to the mesh like every round's
        # output, so round 0 and the rest share ONE compiled program
        assert dist._round_fn._cache_size() == 1

    def test_round_padding_to_mesh_multiple(self, mesh8):
        # 5 clients/round on an 8-device mesh: 3 zero-weight pad slots
        ds = make_blob_federated(client_num=12, seed=1)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=1, batch_size=16, lr=0.1)
        dist = DistributedFedAvgAPI(
            ds, model, mesh=mesh8,
            config=DistributedFedAvgConfig(comm_round=2,
                                           client_num_per_round=5, train=tc))
        sim = FedAvgAPI(ds, model, config=FedAvgConfig(
            comm_round=2, client_num_per_round=5, frequency_of_the_test=100,
            train=tc))
        for r in range(2):
            dist.run_round(r)
            sim.run_round(r)
        diff = float(pt.tree_norm(pt.tree_sub(sim.variables, dist.variables)))
        assert diff < 1e-5, diff

    def test_end_to_end_learns(self, mesh8):
        ds = make_blob_federated(client_num=16, seed=2)
        model = LogisticRegression(num_classes=ds.class_num)
        dist = DistributedFedAvgAPI(
            ds, model, mesh=mesh8,
            config=DistributedFedAvgConfig(
                comm_round=15, client_num_per_round=8,
                frequency_of_the_test=14,
                train=TrainConfig(epochs=2, batch_size=32, lr=0.1)))
        final = dist.train()
        assert final["test_acc"] > 0.9, final


class TestHierarchicalRound:
    def test_hierarchical_equals_flat_when_one_group_round(self):
        # with group_comm_round=1, two-tier aggregation == flat FedAvg
        mesh = build_mesh({"group": 2, "clients": 4})
        flat_mesh = build_mesh({"clients": 8})
        ds = make_blob_federated(client_num=8, seed=0)
        model = LogisticRegression(num_classes=ds.class_num)
        # shuffle off: the hierarchical round folds an edge-round index into
        # each client key, so shuffled batch orders differ from flat's
        tc = TrainConfig(epochs=1, batch_size=16, lr=0.1, shuffle=False)

        x, y, mask = ds.pack_clients(np.arange(8), 16)
        weights = ds.client_weights(np.arange(8))
        keys = jax.random.split(jax.random.key(0), 8)
        variables = model.init(jax.random.key(1),
                               jnp.asarray(x[0, :1]), train=False)

        hier = make_hierarchical_spmd_round(model, "classification", tc, mesh,
                                            group_comm_round=1)
        flat = make_spmd_round(model, "classification", tc, flat_mesh)
        hv, _ = hier(variables, x, y, mask, keys, weights)
        fv, _ = flat(variables, x, y, mask, keys, weights)
        # exact identity: group-wise weighted means recombined with group
        # weights == the flat weighted mean, for arbitrary client weights
        diff = float(pt.tree_norm(pt.tree_sub(hv, fv)))
        assert diff < 1e-5, diff

    def test_multiple_group_rounds_run(self):
        mesh = build_mesh({"group": 2, "clients": 4})
        ds = make_blob_federated(client_num=8, seed=0)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=1, batch_size=16, lr=0.1)
        hier = make_hierarchical_spmd_round(model, "classification", tc, mesh,
                                            group_comm_round=3)
        x, y, mask = ds.pack_clients(np.arange(8), 16)
        keys = jax.random.split(jax.random.key(0), 8)
        variables = model.init(jax.random.key(1), jnp.asarray(x[0, :1]),
                               train=False)
        hv, stats = hier(variables, jnp.asarray(x), jnp.asarray(y),
                         jnp.asarray(mask), keys,
                         jnp.asarray(ds.client_weights(np.arange(8))))
        assert np.isfinite(float(pt.tree_norm(hv)))
        assert float(stats["count"]) > 0


class TestShardedEval:
    def test_matches_single_device_eval(self):
        from fedml_tpu.parallel.spmd import make_sharded_eval
        from fedml_tpu.trainer.functional import make_eval

        mesh = build_mesh({"clients": 8})
        ds = make_blob_federated(client_num=8, seed=2)
        model = LogisticRegression(num_classes=ds.class_num)
        variables = model.init(
            jax.random.key(0), jnp.asarray(ds.test_data_global[0][:1]),
            train=False)
        xt, yt = ds.test_data_global
        n = len(xt)
        n_pad = ((n + 7) // 8) * 8
        x = np.pad(np.asarray(xt), [(0, n_pad - n)] + [(0, 0)] * (xt.ndim - 1))
        y = np.pad(np.asarray(yt), [(0, n_pad - n)])
        m = np.concatenate([np.ones(n, np.float32),
                            np.zeros(n_pad - n, np.float32)])

        sharded = make_sharded_eval(model, "classification", mesh)
        ref = jax.jit(make_eval(model, "classification"))
        got = sharded(variables, jnp.asarray(x), jnp.asarray(y),
                      jnp.asarray(m))
        want = ref(variables, jnp.asarray(xt), jnp.asarray(yt),
                   jnp.ones(n, jnp.float32))
        for k in want:
            np.testing.assert_allclose(float(got[k]), float(want[k]),
                                       rtol=1e-5, atol=1e-5)


class TestCnnParityPerRound:
    def test_cnn_dropout_round_matches_sim_to_f32_rounding(self, mesh8):
        # CNN_DropOut parity sim==mesh holds to f32 rounding PER ROUND
        # (keys fold identically; the psum reduction order differs from the
        # vmap sum, so each round injects ~1e-7 relative noise). Over many
        # rounds non-convex training amplifies that noise exponentially —
        # measured on the femnist flagship shape: 5e-8 after 1 round,
        # 1.1e-7 after 4, 6.6e-3 after 12 — so multi-round CNN trajectories
        # are expected to diverge in the low decimals while remaining
        # statistically identical. LR (convex) stays at e-7 indefinitely
        # (flagship_mnist_lr_calibrated: 7.9e-7 after 200 rounds).
        from fedml_tpu.data.base import FederatedDataset
        from fedml_tpu.models import create_model

        rng = np.random.RandomState(0)
        train = {i: (rng.rand(20 + 5 * i, 28, 28, 1).astype(np.float32),
                     rng.randint(0, 10, 20 + 5 * i).astype(np.int32))
                 for i in range(8)}
        test = {i: (rng.rand(4, 28, 28, 1).astype(np.float32),
                    rng.randint(0, 10, 4).astype(np.int32))
                for i in range(8)}
        ds = FederatedDataset.from_client_arrays(train, test, 10)
        kw = dict(comm_round=1, client_num_per_round=5,
                  frequency_of_the_test=10**9, seed=0)
        tc = TrainConfig(epochs=1, batch_size=10, lr=0.1)
        sim = FedAvgAPI(ds, create_model("cnn", output_dim=10),
                        task="classification",
                        config=FedAvgConfig(train=tc, **kw))
        dist = DistributedFedAvgAPI(ds, create_model("cnn", output_dim=10),
                                    mesh=mesh8, task="classification",
                                    config=DistributedFedAvgConfig(
                                        train=tc, **kw))
        sim.train()
        dist.train()
        num = float(pt.tree_norm(pt.tree_sub(sim.variables,
                                             dist.variables)))
        den = float(pt.tree_norm(sim.variables))
        assert num / den < 1e-6, num / den


class TestRnnOnMesh:
    """Recurrent models under the shard_map round (regression: flax
    nn.RNN's internal scan carry is created unvarying inside the body, and
    jax's varying-manual-axes checker rejected it — check_vma=False on the
    spmd programs with correctness held by the sim==mesh parity below).
    Found by running the stackoverflow_nwp stress through the mesh
    driver."""

    def test_lstm_round_matches_vmapped_simulation(self, mesh8):
        from fedml_tpu.data.base import FederatedDataset
        from fedml_tpu.models.rnn import RNN_OriginalFedAvg

        rng = np.random.RandomState(0)
        V, S = 30, 12
        train_local = {}
        for c in range(8):
            w = rng.randint(1, V, (6, S + 1)).astype(np.int32)
            train_local[c] = (w[:, :-1], w[:, 1:])
        ds = FederatedDataset.from_client_arrays(
            train_local, {c: None for c in range(8)}, V)
        model = RNN_OriginalFedAvg(vocab_size=V, embedding_dim=4,
                                   hidden_size=8, seq_output=True)
        tc = TrainConfig(epochs=1, batch_size=4, lr=0.3)
        cfg = dict(comm_round=2, client_num_per_round=8,
                   frequency_of_the_test=100)
        sim = FedAvgAPI(ds, model, task="nwp",
                        config=FedAvgConfig(train=tc, **cfg))
        dist = DistributedFedAvgAPI(
            ds, model, task="nwp", mesh=mesh8,
            config=DistributedFedAvgConfig(train=tc, **cfg))
        sim.train()
        dist.train()
        num = float(pt.tree_norm(pt.tree_sub(sim.variables,
                                             dist.variables)))
        den = max(1e-30, float(pt.tree_norm(sim.variables)))
        assert num / den < 1e-5, num / den
