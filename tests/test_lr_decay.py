"""TrainConfig.lr_decay_round — the per-round client-LR schedule.

The reference has no LR schedule (its argparse carries a single --lr;
MyModelTrainer.py:26-31 rebuilds the torch optimizer at constant lr every
round), which produces the constant-LR late-round overfit tail documented
on the fed_cifar100 flagship. The schedule is exact, not approximate: the
client optimizer is fresh per round and lr is a final multiplicative
scale in optax's sgd/adam updates, so scaling a round's updates by
``decay**r`` IS running that round at ``lr * decay**r`` — tested here
against literally-rescaled-lr runs, across the host loop / fused scan /
mesh drivers, and guarded on the drivers that do not thread it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.algorithms.fedopt import FedOptAPI, FedOptConfig
from fedml_tpu.core import pytree as pt
from fedml_tpu.data.synthetic import make_blob_federated
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.functional import TrainConfig, round_lr_scale


def _ds():
    return make_blob_federated(client_num=8, partition_method="hetero",
                               seed=0)


def _api(ds, decay=1.0, lr=0.1, optimizer="sgd", rounds=4):
    model = LogisticRegression(num_classes=ds.class_num)
    return FedAvgAPI(ds, model, config=FedAvgConfig(
        comm_round=rounds, client_num_per_round=8,
        frequency_of_the_test=100,
        train=TrainConfig(epochs=2, batch_size=16, lr=lr,
                          client_optimizer=optimizer,
                          lr_decay_round=decay)))


class TestRoundLrScale:
    def test_off_returns_none(self):
        assert round_lr_scale(TrainConfig(), 3) is None
        assert round_lr_scale(TrainConfig(lr_decay_round=1.0), 7) is None

    def test_on_is_decay_pow_round(self):
        s = round_lr_scale(TrainConfig(lr_decay_round=0.9), 3)
        np.testing.assert_allclose(float(s), 0.9 ** 3, rtol=1e-6)
        # traced round index (the fused drivers' case)
        s = round_lr_scale(TrainConfig(lr_decay_round=0.5), jnp.uint32(4))
        np.testing.assert_allclose(float(s), 0.5 ** 4, rtol=1e-6)


class TestDecaySemantics:
    @pytest.mark.parametrize("optimizer", ["sgd", "adam"])
    def test_round_r_equals_literal_rescaled_lr(self, optimizer):
        """Round r under decay d == the same round run at lr*d**r.

        This is the exactness claim in TrainConfig's docstring: fresh
        per-round optimizer + multiplicative lr ⇒ update-scaling is
        lr-scaling."""
        ds = _ds()
        d, lr = 0.8, 0.1
        a = _api(ds, decay=d, lr=lr, optimizer=optimizer)
        for r in range(3):
            a.run_round(r)
        b = _api(ds, decay=1.0, lr=lr, optimizer=optimizer)
        for r in range(3):
            # re-point the constant-lr api at the literally-decayed lr for
            # this round; run_round(r) keeps sampling/keys aligned
            bb = _api(ds, decay=1.0, lr=lr * d ** r, optimizer=optimizer)
            bb.variables = b.variables
            bb.run_round(r)
            b = bb
        num = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        den = max(1e-30, float(pt.tree_norm(b.variables)))
        assert num / den < 1e-5, num / den

    def test_decay_changes_trajectory(self):
        ds = _ds()
        a = _api(ds, decay=0.5)
        c = _api(ds, decay=1.0)
        for r in range(3):
            a.run_round(r)
            c.run_round(r)
        assert float(pt.tree_norm(pt.tree_sub(a.variables,
                                              c.variables))) > 1e-4

    def test_round_zero_unaffected(self):
        # decay**0 == 1: the first round is identical with the schedule on
        ds = _ds()
        a = _api(ds, decay=0.5)
        c = _api(ds, decay=1.0)
        a.run_round(0)
        c.run_round(0)
        num = float(pt.tree_norm(pt.tree_sub(a.variables, c.variables)))
        assert num < 1e-6, num


class TestDecayDriverParity:
    def test_fused_matches_host_loop(self):
        ds = _ds()
        host = _api(ds, decay=0.9, rounds=4)
        for r in range(4):
            host.run_round(r)
        fused = _api(ds, decay=0.9, rounds=4)
        fused.fused_rounds().run_rounds(0, 4)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused.variables)))
        den = max(1e-30, float(pt.tree_norm(host.variables)))
        assert num / den < 1e-6, num / den

    def test_mesh_matches_sim(self):
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=2, batch_size=16, lr=0.1,
                         lr_decay_round=0.9)
        cfg = dict(comm_round=3, client_num_per_round=8,
                   frequency_of_the_test=100)
        sim = FedAvgAPI(ds, model, config=FedAvgConfig(train=tc, **cfg))
        dist = DistributedFedAvgAPI(
            ds, model, mesh=build_mesh({"clients": 8}),
            config=DistributedFedAvgConfig(train=tc, **cfg))
        for r in range(3):
            sim.run_round(r)
            dist.run_round(r)
        diff = float(pt.tree_norm(pt.tree_sub(sim.variables,
                                              dist.variables)))
        assert diff < 1e-5, diff

    def test_mesh_fused_matches_host_loop(self):
        """DistributedFedAvgAPI.run_rounds_fused under the schedule == the
        host loop (ADVICE r5: the fused mesh scan threads the traced
        round index into round_lr_scale — previously verified manually,
        untested)."""
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)
        tc = TrainConfig(epochs=2, batch_size=16, lr=0.1,
                         lr_decay_round=0.9)
        cfg = dict(comm_round=4, client_num_per_round=8,
                   frequency_of_the_test=100)
        host = _api(ds, decay=0.9, rounds=4)
        for r in range(4):
            host.run_round(r)
        dist = DistributedFedAvgAPI(
            ds, model, mesh=build_mesh({"clients": 8}),
            config=DistributedFedAvgConfig(train=tc, **cfg))
        dist.run_rounds_fused(0, 4)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             dist.variables)))
        den = max(1e-30, float(pt.tree_norm(host.variables)))
        assert num / den < 1e-5, num / den

    def test_secure_fedavg_matches_fedavg_with_decay(self):
        """SecureFedAvgAPI under the schedule == plain FedAvgAPI up to
        fixed-point round-off (ADVICE r5: the secure host-side aggregation
        path applies the same round_lr_scale — previously untested)."""
        from fedml_tpu.algorithms.turboaggregate import SecureFedAvgAPI

        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = dict(comm_round=3, client_num_per_round=8,
                   frequency_of_the_test=100,
                   train=TrainConfig(epochs=2, batch_size=16, lr=0.1,
                                     lr_decay_round=0.8))
        plain = FedAvgAPI(ds, model, config=FedAvgConfig(**cfg))
        secure = SecureFedAvgAPI(ds, model, config=FedAvgConfig(**cfg))
        for r in range(3):
            plain.run_round(r)
            secure.run_round(r)
        num = float(pt.tree_norm(pt.tree_sub(plain.variables,
                                             secure.variables)))
        den = max(1e-30, float(pt.tree_norm(plain.variables)))
        # secure-sum == weighted mean up to fixed-point quantization
        assert num / den < 1e-3, num / den
        # and the schedule actually bit: it diverges from constant-lr
        const = _api(ds, decay=1.0)
        for r in range(3):
            const.run_round(r)
        assert float(pt.tree_norm(pt.tree_sub(secure.variables,
                                              const.variables))) > 1e-4

    def test_fedopt_fused_matches_host_loop(self):
        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)

        def mk():
            return FedOptAPI(ds, model, config=FedOptConfig(
                comm_round=4, client_num_per_round=8,
                frequency_of_the_test=100, server_optimizer="adam",
                server_lr=0.01,
                train=TrainConfig(epochs=1, batch_size=16, lr=0.1,
                                  lr_decay_round=0.9)))

        host = mk()
        for r in range(4):
            host.run_round(r)
        fused = mk()
        fused.fused_rounds().run_rounds(0, 4)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused.variables)))
        den = max(1e-30, float(pt.tree_norm(host.variables)))
        assert num / den < 1e-6, num / den


class TestCrossSiloDecayParity:
    def test_cross_silo_matches_sim_with_decay(self, small_dataset):
        """The actor protocol under the schedule == the vmapped sim —
        both paths must scale by the bit-identical round_lr_scale factor
        (the silo computes it outside the device lock)."""
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            run_fedavg_cross_silo)

        ds = small_dataset
        tcfg = TrainConfig(epochs=1, batch_size=4, lr=0.1,
                           lr_decay_round=0.5)
        n_workers = ds.client_num  # full participation
        sim = FedAvgAPI(ds, LogisticRegression(num_classes=ds.class_num),
                        config=FedAvgConfig(
                            comm_round=3, client_num_per_round=n_workers,
                            train=tcfg))
        for r in range(3):
            sim.run_round(r)
        model, history = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=ds.class_num),
            worker_num=n_workers, comm_round=3, train_cfg=tcfg)
        num = float(pt.tree_norm(pt.tree_sub(model, sim.variables)))
        den = max(1e-30, float(pt.tree_norm(sim.variables)))
        assert num / den < 1e-5, num / den
        assert history and history[-1]["round"] == 2


class TestCrossSiloWarmupSharing:
    @pytest.mark.parametrize("decay", [1.0, 0.9])
    def test_silos_hit_the_warmed_jit_entry(self, small_dataset, decay,
                                            caplog):
        """The main-thread warmup must compile the ONE signature the silo
        actors later call — device-tree vs wire-decoded-numpy inputs (or a
        missing lr_scale operand under the schedule) would add a second
        trace, i.e. a second round-0 compile of the local-train program
        on a receive thread."""
        import logging

        from fedml_tpu.algorithms import fedavg_cross_silo as cs

        ds = small_dataset
        tcfg = TrainConfig(epochs=1, batch_size=4, lr=0.1,
                           lr_decay_round=decay)
        module = LogisticRegression(num_classes=ds.class_num)
        shared = cs._shared_local_train(module, "classification", tcfg)
        if getattr(shared, "_cache_size", None) is None:
            pytest.skip("jit._cache_size unavailable on this jax version")
        base = shared._cache_size()
        with caplog.at_level(logging.WARNING):
            cs.run_fedavg_cross_silo(ds, module, worker_num=ds.client_num,
                                     comm_round=2, train_cfg=tcfg)
        # the warmup block swallows its own exceptions by design (never a
        # launch blocker) — a silent warmup crash would shift the compile
        # onto a receive thread while the cache count below stays 1
        assert "warmup compile failed" not in caplog.text
        added = shared._cache_size() - base
        # flax modules hash by field values, so an identically-configured
        # run elsewhere in the session may have pre-traced this entry
        # (added == 0, a legitimate shared-cache hit); the regression
        # guarded here is a SECOND signature (warmup vs actors diverging)
        assert added <= 1, (
            f"cross-silo run added {added} trace entries to the shared "
            f"local_train jit (decay={decay}); warmup and actors must "
            f"share one signature")
        assert shared._cache_size() >= 1


class TestDecayGuards:
    def test_fednova_rejects(self):
        from fedml_tpu.algorithms.fednova import FedNovaAPI, FedNovaConfig
        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)
        with pytest.raises(NotImplementedError):
            FedNovaAPI(ds, model, config=FedNovaConfig(
                train=TrainConfig(lr_decay_round=0.9)))

    def test_hierarchical_rejects(self):
        from fedml_tpu.algorithms.hierarchical import (HierarchicalConfig,
                                                       HierarchicalFedAvgAPI)
        ds = _ds()
        model = LogisticRegression(num_classes=ds.class_num)
        with pytest.raises(NotImplementedError):
            HierarchicalFedAvgAPI(ds, model, config=HierarchicalConfig(
                train=TrainConfig(lr_decay_round=0.9)))

    def test_model_trainer_rejects(self):
        from fedml_tpu.trainer.flax_trainer import FlaxModelTrainer
        with pytest.raises(NotImplementedError):
            FlaxModelTrainer(LogisticRegression(num_classes=3),
                             cfg=TrainConfig(lr_decay_round=0.9))
