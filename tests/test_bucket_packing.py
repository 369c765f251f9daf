"""Cohort-shaped bucket packing (FedAvgConfig.pack="cohort").

The reference's flagship federations are power-law (LEAF MNIST: max client
size ≫ median, fedml_api/data_preprocessing/MNIST/data_loader.py:88), so
padding every sampled client to the dataset-wide max makes masked padding the
majority of per-round FLOPs. Cohort packing pads to the sampled cohort's
pow-2 bucket instead; these tests pin the three contract points: the bucket
math (never below the cohort's need, bounded distinct shapes), the ≥3x
padded-row reduction at the reference's 1000-client power-law scale, and
trajectory equivalence with global packing wherever shapes coincide.
"""

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core import pytree as pt
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data.synthetic import (make_blob_federated,
                                      make_powerlaw_blob_federated)
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.functional import TrainConfig


class TestCohortPaddedLen:
    def test_covers_cohort_and_respects_cap(self):
        ds = make_powerlaw_blob_federated(client_num=200, dim=8, seed=0)
        bsz = 10
        glob = ds.padded_len(bsz)
        rng = np.random.RandomState(0)
        for _ in range(20):
            idxs = rng.choice(200, 10, replace=False)
            n_pad = ds.cohort_padded_len(idxs, bsz)
            need = max(ds.train_data_local_num_dict[int(c)] for c in idxs)
            assert n_pad >= need
            assert n_pad % bsz == 0
            assert n_pad <= glob
            # pow-2 batch count unless capped at the global shape
            nb = n_pad // bsz
            assert nb & (nb - 1) == 0 or n_pad == glob

    def test_full_participation_equals_global_shape(self):
        ds = make_blob_federated(client_num=8, partition_method="hetero",
                                 seed=0)
        assert (ds.cohort_padded_len(np.arange(8), 16)
                == ds.padded_len(16))

    def test_distinct_shapes_logarithmically_bounded(self):
        ds = make_powerlaw_blob_federated(client_num=1000, dim=8, seed=1)
        bsz = 10
        shapes = {ds.cohort_padded_len(
            sample_clients(r, 1000, 10), bsz) for r in range(50)}
        max_nb = ds.padded_len(bsz) // bsz
        assert len(shapes) <= int(np.log2(max_nb)) + 2, shapes

    def test_powerlaw_padded_rows_reduced_3x(self):
        """The contract: at the reference MNIST scale (1000 clients,
        power-law sizes, 10 sampled/round) cohort packing does ≥3x fewer
        padded rows — a direct proxy for per-round FLOPs, which are linear
        in rows through the whole train scan."""
        ds = make_powerlaw_blob_federated(client_num=1000, dim=8, seed=2)
        bsz = 10
        glob = ds.padded_len(bsz)
        rows_global = rows_cohort = 0
        for r in range(50):
            idxs = sample_clients(r, 1000, 10)
            rows_global += glob * len(idxs)
            rows_cohort += ds.cohort_padded_len(idxs, bsz) * len(idxs)
        assert rows_global / rows_cohort >= 3.0, (rows_global, rows_cohort)


class TestCohortPackTrajectory:
    def test_full_participation_identical_to_global(self):
        """Same shapes => bit-identical program; the equivalence invariant
        (fedavg == centralized) is untouched by the new default."""
        ds = make_blob_federated(client_num=6, partition_method="hetero",
                                 seed=3)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=2, batch_size=16, lr=0.1)
        kw = dict(comm_round=3, client_num_per_round=6,
                  frequency_of_the_test=100, train=tc)
        a = FedAvgAPI(ds, model, config=FedAvgConfig(pack="cohort", **kw))
        b = FedAvgAPI(ds, model, config=FedAvgConfig(pack="global", **kw))
        for r in range(3):
            a.run_round(r)
            b.run_round(r)
        assert float(pt.tree_norm(pt.tree_sub(a.variables, b.variables))) == 0

    def test_partial_participation_learns_and_weights_match(self):
        """Cohort packing changes the shuffle permutation length, so the
        trajectory differs from global packing — but the optimization is the
        same problem: both reach the same accuracy on the blob."""
        ds = make_blob_federated(client_num=24, partition_method="hetero",
                                 seed=4, n_samples=4000)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=2, batch_size=16, lr=0.1)
        kw = dict(comm_round=12, client_num_per_round=6,
                  frequency_of_the_test=11, train=tc)
        a = FedAvgAPI(ds, model, config=FedAvgConfig(pack="cohort", **kw))
        b = FedAvgAPI(ds, model, config=FedAvgConfig(pack="global", **kw))
        fa, fb = a.train(), b.train()
        assert fa["test_acc"] > 0.85, fa
        assert fb["test_acc"] > 0.85, fb

    def test_unknown_policy_rejected(self):
        ds = make_blob_federated(client_num=4, seed=0)
        model = LogisticRegression(num_classes=ds.class_num)
        try:
            FedAvgAPI(ds, model, config=FedAvgConfig(pack="banana"))
        except ValueError as e:
            assert "pack" in str(e)
        else:
            raise AssertionError("bad pack policy accepted")


class TestCohortPackOtherAlgorithms:
    def test_fednova_full_participation_identical_across_policies(self):
        """FedNova under full participation: cohort and global packing
        produce the same shapes, so the trajectories must be IDENTICAL
        (a_i counts real batches only — a cohort-path regression that
        altered the normalization would break this equality)."""
        from fedml_tpu.algorithms.fednova import FedNovaAPI, FedNovaConfig
        ds = make_powerlaw_blob_federated(client_num=6, dim=8, seed=6,
                                          max_samples=120)
        model = LogisticRegression(num_classes=ds.class_num)
        finals = {}
        for pack in ("cohort", "global"):
            api = FedNovaAPI(ds, model, config=FedNovaConfig(
                comm_round=4, client_num_per_round=6,
                frequency_of_the_test=100, pack=pack, gmf=0.9,
                train=TrainConfig(epochs=1, batch_size=10, lr=0.1)))
            for r in range(4):
                _, stats = api.run_round(r)
            assert np.isfinite(float(stats["loss_sum"])), pack
            finals[pack] = api.variables
        assert float(pt.tree_norm(pt.tree_sub(finals["cohort"],
                                              finals["global"]))) == 0

    def test_fednova_sampled_cohort_trains(self):
        from fedml_tpu.algorithms.fednova import FedNovaAPI, FedNovaConfig
        ds = make_powerlaw_blob_federated(client_num=20, dim=8, seed=6,
                                          max_samples=120)
        model = LogisticRegression(num_classes=ds.class_num)
        api = FedNovaAPI(ds, model, config=FedNovaConfig(
            comm_round=6, client_num_per_round=6, frequency_of_the_test=100,
            train=TrainConfig(epochs=1, batch_size=10, lr=0.1)))
        for r in range(6):
            _, stats = api.run_round(r)
        assert np.isfinite(float(stats["loss_sum"]))

    def test_fednova_hierarchical_reject_bad_policy(self):
        from fedml_tpu.algorithms.fednova import FedNovaAPI, FedNovaConfig
        from fedml_tpu.algorithms.hierarchical import (HierarchicalConfig,
                                                       HierarchicalFedAvgAPI)
        ds = make_blob_federated(client_num=4, seed=6)
        model = LogisticRegression(num_classes=ds.class_num)
        for ctor, cfg in ((FedNovaAPI, FedNovaConfig(pack="chort")),
                          (HierarchicalFedAvgAPI,
                           HierarchicalConfig(pack="chort"))):
            try:
                ctor(ds, model, config=cfg)
            except ValueError as e:
                assert "pack" in str(e)
            else:
                raise AssertionError(f"{ctor.__name__} accepted a typo'd "
                                     "pack policy")

    def test_hierarchical_both_policies_learn(self):
        from fedml_tpu.algorithms.hierarchical import (HierarchicalConfig,
                                                       HierarchicalFedAvgAPI)
        ds = make_powerlaw_blob_federated(client_num=24, dim=8, seed=7,
                                          max_samples=120)
        model = LogisticRegression(num_classes=ds.class_num)
        for pack in ("cohort", "global"):
            api = HierarchicalFedAvgAPI(ds, model,
                                        config=HierarchicalConfig(
                                            global_comm_round=6,
                                            group_comm_round=2,
                                            group_num=2,
                                            client_num_per_round=8,
                                            frequency_of_the_test=5,
                                            pack=pack,
                                            train=TrainConfig(
                                                epochs=1, batch_size=10,
                                                lr=0.1)))
            final = api.train()
            assert final["test_acc"] > 0.8, (pack, final)


class TestDistributedCohortParity:
    def test_sim_equals_distributed_partial_cohort(self):
        """Partial participation (7 of 20 on an 8-device mesh): the mesh pad
        slots duplicate the last client and must not change the cohort
        bucket; sim and distributed trajectories stay identical."""
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 8})
        ds = make_powerlaw_blob_federated(client_num=20, dim=8, seed=5,
                                          max_samples=120)
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=1, batch_size=10, lr=0.1)
        kw = dict(comm_round=3, client_num_per_round=7)
        sim = FedAvgAPI(ds, model, config=FedAvgConfig(
            frequency_of_the_test=100, train=tc, **kw))
        dist = DistributedFedAvgAPI(ds, model, mesh=mesh,
                                    config=DistributedFedAvgConfig(
                                        train=tc, **kw))
        for r in range(3):
            sim.run_round(r)
            dist.run_round(r)
        diff = float(pt.tree_norm(pt.tree_sub(sim.variables,
                                              dist.variables)))
        assert diff < 1e-5, diff
