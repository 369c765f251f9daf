"""FusedRounds: R FedAvg rounds under one lax.scan (throughput mode).

Contract points: (1) full-participation fusion reproduces the host loop's
trajectory (the in-scan fold_in chain equals FedAvgAPI._pack_round's),
(2) the chunked train() loop learns, records history, and matches the host
loop's eval cadence, (3) partial cohorts default to BLOCK mode —
host-presampled R-cohort blocks packed at the block's cohort bucket,
trajectory-identical to the host loop, (4) device-side sampling
(jax-native stream, full federation resident) is the explicit opt-in
alternative for when per-block host packing is the bottleneck.
"""

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig, FusedRounds
from fedml_tpu.core import pytree as pt
from fedml_tpu.data.synthetic import make_blob_federated
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.functional import TrainConfig


def _api(ds, **kw):
    model = LogisticRegression(num_classes=ds.class_num)
    cfg = dict(comm_round=6, client_num_per_round=ds.client_num,
               frequency_of_the_test=100,
               train=TrainConfig(epochs=2, batch_size=16, lr=0.1))
    cfg.update(kw)
    return FedAvgAPI(ds, model, config=FedAvgConfig(**cfg))


class TestFusedFullParticipation:
    def test_matches_host_loop_trajectory(self):
        ds = make_blob_federated(client_num=6, partition_method="hetero",
                                 seed=0)
        host = _api(ds)
        fused_api = _api(ds)
        fused = FusedRounds(fused_api)
        for r in range(6):
            host.run_round(r)
        fused.run_rounds(0, 6)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_resuming_mid_stream_matches(self):
        # two scans of 3 == one scan of 6 (r0 threads the round index)
        ds = make_blob_federated(client_num=4, seed=1)
        a, b = _api(ds), _api(ds)
        fa, fb = FusedRounds(a), FusedRounds(b)
        fa.run_rounds(0, 6)
        fb.run_rounds(0, 3)
        fb.run_rounds(3, 3)
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff

    def test_chunked_train_learns(self):
        ds = make_blob_federated(client_num=8, seed=2)
        api = _api(ds, comm_round=12, frequency_of_the_test=4)
        final = FusedRounds(api).train()
        assert final["test_acc"] > 0.9, final
        # eval cadence matches the host loop: after rounds 0, 4, 8, 11
        assert [rec["round"] for rec in api.history] == [0, 4, 8, 11]
        assert np.isfinite(final["train_loss_local"])

    def test_eval_cadence_matches_host_loop(self):
        # same records at the same round indices as FedAvgAPI.train()
        ds = make_blob_federated(client_num=4, seed=2)
        host = _api(ds, client_num_per_round=4, comm_round=7,
                    frequency_of_the_test=3)
        fused_api = _api(ds, client_num_per_round=4, comm_round=7,
                         frequency_of_the_test=3)
        host.train()
        FusedRounds(fused_api).train()
        h = [rec["round"] for rec in host.history]
        f = [rec["round"] for rec in fused_api.history]
        assert h == f == [0, 3, 6]
        for hr, fr in zip(host.history, fused_api.history):
            assert abs(hr["test_acc"] - fr["test_acc"]) < 1e-6

    def test_max_rounds_per_dispatch_caps_scan(self):
        # the --fused_rounds value bounds the per-dispatch chunk without
        # changing the trajectory or the eval schedule (ADVICE r3)
        ds = make_blob_federated(client_num=4, seed=12)
        a = _api(ds, client_num_per_round=4, comm_round=9,
                 frequency_of_the_test=4)
        b = _api(ds, client_num_per_round=4, comm_round=9,
                 frequency_of_the_test=4)
        FusedRounds(a).train()
        FusedRounds(b).train(max_rounds_per_dispatch=2)
        assert ([r["round"] for r in a.history]
                == [r["round"] for r in b.history])
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff

    def test_stats_stacked_per_round(self):
        ds = make_blob_federated(client_num=4, seed=3)
        api = _api(ds)
        stats = FusedRounds(api).run_rounds(0, 5)
        assert stats["loss_sum"].shape == (5,)
        assert float(stats["count"][0]) > 0


class TestFedOptFused:
    def test_matches_host_loop_with_server_adam(self):
        """The server Adam state advances in-scan: R fused rounds equal R
        host-loop FedOpt rounds (params AND optimizer state)."""
        from fedml_tpu.algorithms.fedopt import (FedOptAPI, FedOptConfig,
                                                 FedOptFusedRounds)
        ds = make_blob_federated(client_num=6, partition_method="hetero",
                                 seed=9)
        model = LogisticRegression(num_classes=ds.class_num)
        kw = dict(comm_round=6, client_num_per_round=6,
                  frequency_of_the_test=100, server_optimizer="adam",
                  server_lr=0.01,
                  train=TrainConfig(epochs=2, batch_size=16, lr=0.1))
        host = FedOptAPI(ds, model, config=FedOptConfig(**kw))
        fused_api = FedOptAPI(ds, model, config=FedOptConfig(**kw))
        fused = FedOptFusedRounds(fused_api)
        for r in range(6):
            host.run_round(r)
        stats = fused.run_rounds(0, 6)
        assert stats["loss_sum"].shape == (6,)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)
        opt_diff = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a)
                                             - np.asarray(b)))),
            host.server_opt_state, fused_api.server_opt_state)
        assert max(jax.tree.leaves(opt_diff)) < 1e-6, opt_diff

    def test_mispairing_rejected(self):
        # plain FusedRounds on a FedOptAPI would silently drop the server
        # optimizer — must fail loudly; api.fused_rounds() pairs correctly
        from fedml_tpu.algorithms.fedopt import (FedOptAPI, FedOptConfig,
                                                 FedOptFusedRounds)
        ds = make_blob_federated(client_num=4, seed=9)
        api = FedOptAPI(ds, LogisticRegression(num_classes=ds.class_num),
                        config=FedOptConfig(
                            client_num_per_round=4,
                            train=TrainConfig(batch_size=16)))
        try:
            FusedRounds(api)
        except TypeError as e:
            assert "FedOptFusedRounds" in str(e)
        else:
            raise AssertionError("mispaired driver accepted")
        assert isinstance(api.fused_rounds(), FedOptFusedRounds)

    def test_device_sampling_learns(self):
        from fedml_tpu.algorithms.fedopt import (FedOptAPI, FedOptConfig,
                                                 FedOptFusedRounds)
        ds = make_blob_federated(client_num=12, seed=10, n_samples=2500)
        model = LogisticRegression(num_classes=ds.class_num)
        api = FedOptAPI(ds, model, config=FedOptConfig(
            comm_round=20, client_num_per_round=4,
            frequency_of_the_test=100, server_optimizer="yogi",
            server_lr=0.05,
            train=TrainConfig(epochs=1, batch_size=16, lr=0.1)))
        fused = FedOptFusedRounds(api, device_sampling=True)
        fused.run_rounds(0, 20)
        assert api.evaluate(19)["test_acc"] > 0.85


class TestMeshFusedRounds:
    def test_fused_mesh_rounds_match_host_loop(self):
        """R rounds under one shard_map scan == R host-loop mesh rounds
        (and both == the vmapped sim, transitively via test_spmd)."""
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 8})
        ds = make_blob_federated(client_num=8, partition_method="hetero",
                                 seed=7)
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = DistributedFedAvgConfig(
            comm_round=4, client_num_per_round=8,
            train=TrainConfig(epochs=2, batch_size=16, lr=0.1))
        host = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        fused = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        for r in range(4):
            host.run_round(r)
        stats = fused.run_rounds_fused(0, 4)
        assert stats["loss_sum"].shape == (4,)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_fused_mesh_sampled_block_matches_host_loop(self):
        """Sampled cohorts on the mesh run as host-drawn fused blocks:
        4-of-12 at 8 devices — cohorts pad to the mesh
        multiple with zero-weight slots, block packs at the cohort bucket,
        trajectory equals R run_round calls."""
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 8})
        ds = make_blob_federated(client_num=12, partition_method="hetero",
                                 seed=7)
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = DistributedFedAvgConfig(
            comm_round=6, client_num_per_round=4,
            train=TrainConfig(epochs=2, batch_size=16, lr=0.1))
        host = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        fused = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        for r in range(6):
            host.run_round(r)
        stats = fused.run_rounds_fused(0, 6)
        assert stats["loss_sum"].shape == (6,)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_fused_mesh_sampled_matches_sim_block(self):
        # the mesh block and the sim block are the same trajectory: the
        # sim==mesh invariant survives fusion in the sampled regime
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 4})
        ds = make_blob_federated(client_num=10, partition_method="hetero",
                                 seed=17)
        model = LogisticRegression(num_classes=ds.class_num)
        tcfg = TrainConfig(epochs=1, batch_size=16, lr=0.1)
        sim = _api(ds, client_num_per_round=4, train=tcfg)
        mesh_api = DistributedFedAvgAPI(
            ds, model, mesh=mesh, config=DistributedFedAvgConfig(
                client_num_per_round=4, train=tcfg))
        FusedRounds(sim).run_rounds(0, 5)
        mesh_api.run_rounds_fused(0, 5)
        num = float(pt.tree_norm(pt.tree_sub(sim.variables,
                                             mesh_api.variables)))
        den = float(pt.tree_norm(sim.variables))
        assert num / den < 1e-6, (num, den)

    def test_fused_mesh_sampled_resume_mid_stream(self):
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 4})
        ds = make_blob_federated(client_num=9, seed=18)
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = DistributedFedAvgConfig(
            client_num_per_round=3,
            train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        a = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        b = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        a.run_rounds_fused(0, 6)
        b.run_rounds_fused(0, 3)
        b.run_rounds_fused(3, 3)
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff

    def test_train_fused_matches_train_cadence(self):
        # api.train_fused produces the same history rounds and accuracies
        # as api.train (sampled regime included)
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 4})
        ds = make_blob_federated(client_num=8, seed=19)
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = DistributedFedAvgConfig(
            comm_round=7, client_num_per_round=4,
            frequency_of_the_test=3,
            train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        host = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        fused = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        host.train()
        fused.train_fused(max_rounds_per_dispatch=2)
        h = [rec["round"] for rec in host.history]
        f = [rec["round"] for rec in fused.history]
        assert h == f == [0, 3, 6]
        for hr, fr in zip(host.history, fused.history):
            assert abs(hr["test_acc"] - fr["test_acc"]) < 1e-6

    def test_fused_mesh_rejects_mp(self):
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig)
        import jax
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices()[:2]).reshape(1, 2)
        mesh = Mesh(devs, ("clients", "fsdp"))
        ds = make_blob_federated(client_num=4, seed=7)
        model = LogisticRegression(num_classes=ds.class_num)
        api = DistributedFedAvgAPI(
            ds, model, mesh=mesh,
            config=DistributedFedAvgConfig(
                client_num_per_round=4, model_parallel="fsdp", mp_size=2,
                train=TrainConfig(epochs=1, batch_size=16)))
        try:
            api.run_rounds_fused(0, 2)
        except ValueError as e:
            assert "clients" in str(e)
        else:
            raise AssertionError("model-parallel fused mesh accepted")

    def test_fused_mesh_resume_mid_stream(self):
        from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                             DistributedFedAvgConfig,
                                             build_mesh)
        mesh = build_mesh({"clients": 8})
        ds = make_blob_federated(client_num=8, seed=8)
        model = LogisticRegression(num_classes=ds.class_num)
        cfg = DistributedFedAvgConfig(
            client_num_per_round=8,
            train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        a = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        b = DistributedFedAvgAPI(ds, model, mesh=mesh, config=cfg)
        a.run_rounds_fused(0, 6)
        b.run_rounds_fused(0, 3)
        b.run_rounds_fused(3, 3)
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff


class TestFusedBlockSampling:
    """Block mode (default for partial cohorts): host-presampled R-cohort
    blocks packed at the block's cohort bucket — BOTH throughput levers in
    one dispatch, trajectory-identical to the host loop."""

    def test_block_matches_host_loop_trajectory(self):
        # 4-of-12 sampling: same cohorts (sample_clients stream), same
        # fold_in chain, bucketed block padding => same trajectory
        ds = make_blob_federated(client_num=12, partition_method="hetero",
                                 seed=4)
        host = _api(ds, client_num_per_round=4, comm_round=8)
        fused_api = _api(ds, client_num_per_round=4, comm_round=8)
        fused = FusedRounds(fused_api)
        assert fused.mode == "block"
        for r in range(8):
            host.run_round(r)
        stats = fused.run_rounds(0, 8)
        assert stats["loss_sum"].shape == (8,)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_block_resume_mid_stream(self):
        # two blocks of 3 == one block of 6 (cohorts derive from the
        # absolute round index, not the block offset)
        ds = make_blob_federated(client_num=10, seed=13)
        a = _api(ds, client_num_per_round=3)
        b = _api(ds, client_num_per_round=3)
        FusedRounds(a).run_rounds(0, 6)
        fb = FusedRounds(b)
        fb.run_rounds(0, 3)
        fb.run_rounds(3, 3)
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff

    def test_block_honors_delete_client(self):
        # leave-one-out runs fused now: sampling is host-side in block mode
        ds = make_blob_federated(client_num=8, seed=14)
        model = LogisticRegression(num_classes=ds.class_num)
        kw = dict(comm_round=5, client_num_per_round=4,
                  frequency_of_the_test=100,
                  train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        host = FedAvgAPI(ds, model, delete_client=2,
                         config=FedAvgConfig(**kw))
        fused_api = FedAvgAPI(ds, model, delete_client=2,
                              config=FedAvgConfig(**kw))
        for r in range(5):
            host.run_round(r)
        fused_api.fused_rounds().run_rounds(0, 5)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_block_fedopt_matches_host(self):
        # richer server state (Adam moments) advances in-scan under block
        # sampling too — the carry protocol composes with the new mode
        from fedml_tpu.algorithms.fedopt import FedOptAPI, FedOptConfig
        ds = make_blob_federated(client_num=10, partition_method="hetero",
                                 seed=15)
        model = LogisticRegression(num_classes=ds.class_num)
        kw = dict(comm_round=6, client_num_per_round=4,
                  frequency_of_the_test=100, server_optimizer="adam",
                  server_lr=0.01,
                  train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        host = FedOptAPI(ds, model, config=FedOptConfig(**kw))
        fused_api = FedOptAPI(ds, model, config=FedOptConfig(**kw))
        for r in range(6):
            host.run_round(r)
        fused_api.fused_rounds().run_rounds(0, 6)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)
        opt_diff = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a)
                                             - np.asarray(b)))),
            host.server_opt_state, fused_api.server_opt_state)
        assert max(jax.tree.leaves(opt_diff)) < 1e-6, opt_diff

    def test_block_respects_global_pack_policy(self):
        # pack="global" blocks pad to the dataset max and still match
        ds = make_blob_federated(client_num=10, partition_method="hetero",
                                 seed=16)
        a = _api(ds, client_num_per_round=4, pack="global")
        b = _api(ds, client_num_per_round=4, pack="cohort")
        FusedRounds(a).run_rounds(0, 4)
        FusedRounds(b).run_rounds(0, 4)
        diff = float(pt.tree_norm(pt.tree_sub(a.variables, b.variables)))
        assert diff < 1e-6, diff  # padding policy never changes the math


class TestFusedDeviceSampling:
    def test_delete_client_rejected(self):
        # leave-one-out semantics can't be honored in-scan; must refuse
        from fedml_tpu.models.lr import LogisticRegression as LR
        ds = make_blob_federated(client_num=6, seed=4)
        api = FedAvgAPI(ds, LR(num_classes=ds.class_num),
                        delete_client=2,
                        config=FedAvgConfig(
                            client_num_per_round=6,
                            train=TrainConfig(batch_size=16)))
        try:
            FusedRounds(api)
        except ValueError as e:
            assert "delete_client" in str(e)
        else:
            raise AssertionError("delete_client silently ignored")

    def test_sampled_rounds_learn(self):
        ds = make_blob_federated(client_num=16, seed=5, n_samples=3000)
        api = _api(ds, comm_round=20, client_num_per_round=4,
                   frequency_of_the_test=10)
        fused = FusedRounds(api, device_sampling=True)
        final = fused.train()
        assert final["test_acc"] > 0.85, final

    def test_sampled_cohorts_vary_across_rounds(self):
        # the per-round choice key is a sentinel fold (2**31-2, outside the
        # client-id range so no training key is reused); distinct rounds
        # draw distinct cohorts with overwhelming probability
        ds = make_blob_federated(client_num=16, seed=6)
        api = _api(ds, client_num_per_round=4)
        fused = FusedRounds(api, device_sampling=True)
        base = api._base_key
        draws = []
        for r in range(4):
            rk = jax.random.fold_in(base, r)
            idx = jax.random.choice(jax.random.fold_in(rk, 2**31 - 2),
                                    16, (4,), replace=False)
            draws.append(tuple(np.asarray(idx)))
            assert len(set(draws[-1])) == 4  # without replacement
        assert len(set(draws)) > 1
        fused.run_rounds(0, 4)  # and the fused program executes


class TestFusedPairings:
    def test_robust_hooks_fuse_with_rng_parity(self):
        """FedAvgRobustAPI's defenses live in the aggregate hook, which
        _round_fn_py carries into the scan — including the agg_key the
        weak-DP noise consumes, so stochastic defenses stay bit-compatible
        with the host loop."""
        from fedml_tpu.algorithms.fedavg_robust import (FedAvgRobustAPI,
                                                        FedAvgRobustConfig)
        ds = make_blob_federated(client_num=5, partition_method="hetero",
                                 seed=11)
        model = LogisticRegression(num_classes=ds.class_num)
        kw = dict(comm_round=4, client_num_per_round=5,
                  frequency_of_the_test=100,
                  defense_type="weak_dp", stddev=0.05,
                  train=TrainConfig(epochs=1, batch_size=16, lr=0.1))
        host = FedAvgRobustAPI(ds, model, config=FedAvgRobustConfig(**kw))
        fused_api = FedAvgRobustAPI(ds, model,
                                    config=FedAvgRobustConfig(**kw))
        fused = fused_api.fused_rounds()
        for r in range(4):
            host.run_round(r)
        fused.run_rounds(0, 4)
        num = float(pt.tree_norm(pt.tree_sub(host.variables,
                                             fused_api.variables)))
        den = float(pt.tree_norm(host.variables))
        assert num / den < 1e-6, (num, den)

    def test_secure_api_refuses_fusion(self):
        from fedml_tpu.algorithms.turboaggregate import SecureFedAvgAPI
        from fedml_tpu.algorithms.fedavg import FedAvgConfig
        ds = make_blob_federated(client_num=4, seed=11)
        api = SecureFedAvgAPI(ds,
                              LogisticRegression(num_classes=ds.class_num),
                              config=FedAvgConfig(
                                  client_num_per_round=4,
                                  train=TrainConfig(batch_size=16)))
        for ctor in (api.fused_rounds, lambda: FusedRounds(api)):
            try:
                ctor()
            except TypeError as e:
                assert "fused" in str(e) or "host-side" in str(e)
            else:
                raise AssertionError("secure API fused silently")
