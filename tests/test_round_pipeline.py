"""Async round pipeline (parallel/prefetch.py): pipelined-vs-serial
trajectory parity, donation/stale-slot safety, dataset-swap invalidation,
and the serial-path kill switches.

The contract under test: prefetching NEVER changes what a round computes —
only when its host work happens. Trajectories must be bit-identical to the
serial path for both drivers, sampled and full participation; a depth-0
config or $FEDML_TPU_PREFETCH=0 must provably restore today's serial path;
a mid-run dataset swap must invalidate in-flight slots exactly like the
drivers' _pack_cache.
"""

import threading
import time

import jax
import numpy as np
import pytest

from fedml_tpu.parallel.prefetch import (PREFETCH_ENV, RoundPrefetcher,
                                         resolve_prefetch_depth)


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# -- unit: the prefetcher itself --------------------------------------------
class TestResolveDepth:
    def test_config_value_passes_through(self, monkeypatch):
        monkeypatch.delenv(PREFETCH_ENV, raising=False)
        assert resolve_prefetch_depth(3) == 3
        assert resolve_prefetch_depth(0) == 0
        assert resolve_prefetch_depth(-2) == 0

    def test_env_overrides_config(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_ENV, "0")
        assert resolve_prefetch_depth(4) == 0
        monkeypatch.setenv(PREFETCH_ENV, "5")
        assert resolve_prefetch_depth(0) == 5

    def test_garbage_env_raises(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_ENV, "two")
        with pytest.raises(ValueError, match="FEDML_TPU_PREFETCH"):
            resolve_prefetch_depth(2)


class TestRoundPrefetcher:
    def test_sequential_gets_hit_after_first(self):
        calls = []

        def produce(r):
            calls.append((r, threading.current_thread().name))
            return r * 10

        pf = RoundPrefetcher(produce, depth=2, name="t-seq")
        try:
            out0, hit0 = pf.get(0)
            assert (out0, hit0) == (0, False)  # nothing speculated yet
            for r in (1, 2, 3):
                out, hit = pf.get(r)
                assert out == r * 10 and hit
            stats = pf.stats()
            assert stats["hits"] == 3 and stats["misses"] == 1
            # hits were produced on the worker thread, not the caller
            worker_calls = [t for r, t in calls if r in (1, 2, 3)]
            assert all(t == "t-seq" for t in worker_calls)
        finally:
            pf.close()

    def test_out_of_order_get_is_a_miss_and_reaims(self):
        pf = RoundPrefetcher(lambda r: r, depth=2)
        try:
            pf.get(0)
            out, hit = pf.get(7)  # resume at an arbitrary round
            assert out == 7 and not hit
            out, hit = pf.get(8)  # stream re-aimed at 7's successors
            assert out == 8 and hit
        finally:
            pf.close()

    def test_worker_exception_surfaces_on_caller(self):
        def produce(r):
            if r == 1:
                raise RuntimeError("boom in worker")
            return r

        pf = RoundPrefetcher(produce, depth=1)
        try:
            pf.get(0)  # schedules r=1 on the worker
            with pytest.raises(RuntimeError, match="boom in worker"):
                pf.get(1)
        finally:
            pf.close()

    def test_invalidate_discards_ready_slots(self):
        produced = []

        def produce(r):
            produced.append(r)
            return r

        pf = RoundPrefetcher(produce, depth=2)
        try:
            pf.get(0)
            # wait for speculation to land
            deadline = time.time() + 5
            while len(produced) < 3 and time.time() < deadline:
                time.sleep(0.01)
            pf.invalidate()
            out, hit = pf.get(1)
            assert out == 1 and not hit  # slot was dropped, not reused
            assert pf.stats()["invalidated"] >= 1
        finally:
            pf.close()

    def test_resident_slots_stay_bounded_under_mispredictions(self):
        # persistent misses (e.g. varying fused-block windows) must not
        # pin an unbounded set of orphaned payloads
        pf = RoundPrefetcher(lambda r: r, depth=2)
        try:
            for r in range(0, 100, 10):  # every get mispredicted
                pf.get(r)
            deadline = time.time() + 5
            while pf._inflight and time.time() < deadline:
                time.sleep(0.01)
            with pf._cond:
                assert len(pf._ready) <= 2
        finally:
            pf.close()

    def test_an_idle_worker_holds_neither_producer_nor_prefetcher(self):
        """The producer is a driver's bound method: a worker that kept it
        between requests kept the driver, its model on the device and this
        prefetcher (so its own shutdown) alive for good."""
        import gc
        import weakref

        class Owner:
            def produce(self, key):
                return key

        owner = Owner()
        owner.prefetch = pf = RoundPrefetcher(owner.produce, depth=1)
        assert pf.get(0)[0] == 0 and pf.get(1) == (1, True)
        time.sleep(0.05)  # the worker is back at its queue
        thread, alive = pf._thread, weakref.ref(owner)
        del owner, pf
        gc.collect()
        assert alive() is None
        thread.join(timeout=5.0)
        assert not thread.is_alive()

    def test_close_falls_back_to_inline_produce(self):
        pf = RoundPrefetcher(lambda r: r * 2, depth=2)
        pf.get(0)
        pf.close()
        out, hit = pf.get(1)
        assert out == 2 and not hit

    def test_upcoming_hint_overrides_prediction(self):
        # a driver that KNOWS its schedule speculates exactly those keys
        pf = RoundPrefetcher(lambda r: r, depth=2)
        try:
            pf.get(0, upcoming=[7])
            out, hit = pf.get(7)
            assert out == 7 and hit
        finally:
            pf.close()

    def test_empty_upcoming_speculates_nothing(self):
        # the end-of-run contract: an empty schedule must leave no
        # produced-but-never-consumed slots pinning memory
        produced = []
        pf = RoundPrefetcher(lambda r: produced.append(r) or r, depth=2)
        try:
            pf.get(5, upcoming=[])
            time.sleep(0.1)
            assert produced == [5]  # only the inline miss itself
            with pf._cond:
                assert not pf._ready and not pf._inflight
        finally:
            pf.close()


# -- driver parity: vmapped simulation (FedAvgAPI) --------------------------
def _make_blob():
    from fedml_tpu.data.synthetic import make_blob_federated
    return make_blob_federated(client_num=12, dim=8, class_num=4,
                               n_samples=480, seed=3)


def _make_sim_api(ds, depth, per_round=4, rounds=8):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    return FedAvgAPI(ds, LogisticRegression(num_classes=4),
                     config=FedAvgConfig(
                         comm_round=rounds, client_num_per_round=per_round,
                         frequency_of_the_test=10 ** 9,
                         prefetch_depth=depth,
                         train=TrainConfig(epochs=1, batch_size=16,
                                           lr=0.1)))


class TestSimPipelineParity:
    def test_sampled_trajectory_bit_identical(self):
        ds = _make_blob()
        serial, piped = _make_sim_api(ds, 0), _make_sim_api(ds, 2)
        for r in range(8):
            _, ss = serial.run_round(r)
            _, sp = piped.run_round(r)
            assert _trees_equal(ss, sp)  # per-round stats, not just final
        assert _trees_equal(serial.variables, piped.variables)
        stats = piped.prefetch_stats()
        assert stats["hits"] >= 6  # the pipeline actually engaged
        assert serial.prefetch_stats() is None  # depth 0 = serial path

    def test_full_participation_keeps_pack_cache_path(self):
        ds = _make_blob()
        api = _make_sim_api(ds, 2, per_round=12)
        for r in range(3):
            api.run_round(r)
        # full participation: the resident-cohort cache runs, not the
        # prefetcher (its second round must hit the cache)
        assert api.prefetch_stats() is None
        assert api._pack_cache is not None

    def test_env_kill_switch_restores_serial_path(self, monkeypatch):
        monkeypatch.setenv(PREFETCH_ENV, "0")
        ds = _make_blob()
        api = _make_sim_api(ds, 2)
        for r in range(3):
            api.run_round(r)
        assert api.prefetch_stats() is None
        assert "prefetch_wait" not in api.timer.totals

    def test_no_slots_left_after_final_round(self):
        # run_round clamps speculation to comm_round: after the last
        # round, no packed-but-unconsumed slot may stay device-resident
        ds = _make_blob()
        api = _make_sim_api(ds, 2, rounds=5)
        for r in range(5):
            api.run_round(r)
        pf = api._prefetch[0]
        deadline = time.time() + 5
        while pf._inflight and time.time() < deadline:
            time.sleep(0.01)
        with pf._cond:
            assert not pf._ready and not pf._inflight

    def test_upload_phase_and_counters_recorded(self):
        ds = _make_blob()
        api = _make_sim_api(ds, 2)
        for r in range(4):
            api.run_round(r)
        assert "upload" in api.timer.totals  # split out of pack
        counters = api.timer.counters
        assert counters["prefetch_hit"] + counters["prefetch_miss"] == 4
        assert "prefetch_wait" in api.timer.totals

    def test_leave_one_out_engages_pipeline_and_stays_exact(self):
        # delete_client cohorts never hit _pack_cache (per-round-seeded
        # permuted order), so the pipeline must engage there too
        ds = _make_blob()
        from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
        from fedml_tpu.models.lr import LogisticRegression
        from fedml_tpu.trainer.functional import TrainConfig

        def make(depth):
            return FedAvgAPI(ds, LogisticRegression(num_classes=4),
                             delete_client=3,
                             config=FedAvgConfig(
                                 comm_round=4, client_num_per_round=12,
                                 frequency_of_the_test=10 ** 9,
                                 prefetch_depth=depth,
                                 train=TrainConfig(epochs=1,
                                                   batch_size=16,
                                                   lr=0.1)))

        serial, piped = make(0), make(2)
        for r in range(4):
            _, ss = serial.run_round(r)
            _, sp = piped.run_round(r)
            assert _trees_equal(ss, sp)
        assert _trees_equal(serial.variables, piped.variables)
        assert piped.prefetch_stats()["hits"] >= 2

    def test_no_stale_slot_on_out_of_order_rounds(self):
        # a checkpoint-style resume jump must repack, never reuse a
        # speculated slot for a different round index
        ds = _make_blob()
        piped = _make_sim_api(ds, 3)
        for r in range(4):
            piped.run_round(r)
        serial = _make_sim_api(ds, 0)
        for r in range(4):
            serial.run_round(r)
        # jump backwards (out of the speculated window)
        _, sp = piped.run_round(1)
        _, ss = serial.run_round(1)
        assert _trees_equal(ss, sp)
        assert _trees_equal(serial.variables, piped.variables)


class TestFedOptPipelineParity:
    def test_fedopt_trajectory_bit_identical(self):
        # FedOpt overrides run_round's dispatch half but shares
        # _host_round_inputs — the pipeline must engage and stay exact
        from fedml_tpu.algorithms.fedopt import FedOptAPI, FedOptConfig
        from fedml_tpu.models.lr import LogisticRegression
        from fedml_tpu.trainer.functional import TrainConfig
        ds = _make_blob()

        def make(depth):
            return FedOptAPI(ds, LogisticRegression(num_classes=4),
                             config=FedOptConfig(
                                 comm_round=6, client_num_per_round=4,
                                 frequency_of_the_test=10 ** 9,
                                 prefetch_depth=depth,
                                 train=TrainConfig(epochs=1,
                                                   batch_size=16,
                                                   lr=0.1)))

        serial, piped = make(0), make(2)
        for r in range(6):
            _, ss = serial.run_round(r)
            _, sp = piped.run_round(r)
            assert _trees_equal(ss, sp)
        assert _trees_equal(serial.variables, piped.variables)
        assert piped.prefetch_stats()["hits"] >= 4


class TestDatasetSwapInvalidation:
    def test_mid_run_swap_matches_serial_and_invalidates(self):
        from fedml_tpu.data.synthetic import make_blob_federated
        ds_a = _make_blob()
        ds_b = make_blob_federated(client_num=12, dim=8, class_num=4,
                                   n_samples=480, seed=9)
        serial, piped = _make_sim_api(ds_a, 0), _make_sim_api(ds_a, 2)
        for r in range(3):
            serial.run_round(r)
            piped.run_round(r)
        serial.dataset = ds_b  # the _pack_cache swap contract
        piped.dataset = ds_b
        for r in range(3, 6):
            _, ss = serial.run_round(r)
            _, sp = piped.run_round(r)
            assert _trees_equal(ss, sp)
        assert _trees_equal(serial.variables, piped.variables)
        assert piped.prefetch_stats()["invalidated"] >= 1


# -- driver parity: device mesh (DistributedFedAvgAPI) ----------------------
def _make_mesh_api(ds, depth, per_round=4, rounds=6, freq=10 ** 9):
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                         DistributedFedAvgConfig)
    from fedml_tpu.trainer.functional import TrainConfig
    return DistributedFedAvgAPI(ds, LogisticRegression(num_classes=4),
                                config=DistributedFedAvgConfig(
                                    comm_round=rounds,
                                    client_num_per_round=per_round,
                                    frequency_of_the_test=freq,
                                    prefetch_depth=depth,
                                    train=TrainConfig(epochs=1,
                                                      batch_size=16,
                                                      lr=0.1)))


class TestMeshPipelineParity:
    def test_sampled_trajectory_bit_identical(self):
        # donation safety rides along: the mesh round donates the model
        # buffer every dispatch while prefetched data slots are in
        # flight — any use-after-donate or stale-slot reuse breaks the
        # exact equality
        ds = _make_blob()
        serial, piped = _make_mesh_api(ds, 0), _make_mesh_api(ds, 3)
        for r in range(6):
            _, ss = serial.run_round(r)
            _, sp = piped.run_round(r)
            assert _trees_equal(ss, sp)
        assert _trees_equal(serial.variables, piped.variables)
        assert piped.prefetch_stats()["hits"] >= 4

    def test_fused_block_windows_bit_identical(self):
        ds = _make_blob()
        serial, piped = (_make_mesh_api(ds, 0, rounds=9, freq=4),
                         _make_mesh_api(ds, 2, rounds=9, freq=4))
        serial.train_fused(max_rounds_per_dispatch=3)
        piped.train_fused(max_rounds_per_dispatch=3)
        assert _trees_equal(serial.variables, piped.variables)
        assert serial.history == piped.history
        # train_fused hands the prefetcher its REAL chunk schedule, so
        # the non-uniform eval-boundary windows ((0,1),(1,3),(4,1),...)
        # hit instead of mispredicting every boundary
        stats = piped.prefetch_stats()
        assert stats["hits"] >= 3 and stats["misses"] <= 1
        # and the last window speculated nothing: no leftover block slots
        pf = piped._block_prefetch[0]
        deadline = time.time() + 5
        while pf._inflight and time.time() < deadline:
            time.sleep(0.01)
        with pf._cond:
            assert not pf._ready and not pf._inflight

    def test_multi_round_pipelined_soak(self):
        # long pipelined stretch: every speculated slot consumed in
        # order, no drift against the serial trajectory after 24 rounds
        ds = _make_blob()
        serial, piped = (_make_mesh_api(ds, 0, rounds=24),
                         _make_mesh_api(ds, 2, rounds=24))
        for r in range(24):
            serial.run_round(r)
            piped.run_round(r)
        assert _trees_equal(serial.variables, piped.variables)
        stats = piped.prefetch_stats()
        assert stats["hits"] >= 20


# -- cross-silo: predicted-client prefetch ----------------------------------
class TestCrossSiloPrefetch:
    def test_protocol_parity_prefetch_on_vs_off(self):
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            run_fedavg_cross_silo)
        from fedml_tpu.core import pytree as pt
        from fedml_tpu.models.lr import LogisticRegression
        from fedml_tpu.trainer.functional import TrainConfig
        ds = _make_blob()
        cfg = TrainConfig(epochs=1, batch_size=16, lr=0.1)
        m_on, h_on = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=4), worker_num=3,
            comm_round=3, train_cfg=cfg, prefetch_depth=2)
        m_off, h_off = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=4), worker_num=3,
            comm_round=3, train_cfg=cfg, prefetch_depth=0)
        assert float(pt.tree_norm(pt.tree_sub(m_on, m_off))) == 0.0
        assert ([r["test_acc"] for r in h_on]
                == [r["test_acc"] for r in h_off])

    def test_prediction_matches_server_sampling(self):
        # the silo-side predictor must agree with the server's stream
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            FedAvgClientManager)
        from fedml_tpu.comm.inproc import InProcRouter
        from fedml_tpu.comm.registry import create_comm_manager
        from fedml_tpu.core.sampling import sample_clients
        from fedml_tpu.models.lr import LogisticRegression
        from fedml_tpu.trainer.functional import TrainConfig
        ds = _make_blob()
        router = InProcRouter()
        com = create_comm_manager("INPROC", 1, 4, router=router)
        mgr = FedAvgClientManager(1, 4, com, ds,
                                  LogisticRegression(num_classes=4),
                                  "classification",
                                  TrainConfig(batch_size=16),
                                  prefetch_depth=2)
        key = (0, int(sample_clients(0, ds.client_num, 3)[0]))
        for r in range(4):
            # successor prediction tracks the server's stream exactly
            nxt = mgr._predict_next(key)
            assert nxt == (r + 1,
                           int(sample_clients(r + 1, ds.client_num, 3)[0]))
            got_ds, payload = mgr._pack_client(key)
            assert got_ds is ds
            x, y, mask = ds.pack_clients([key[1]], 16,
                                         n_pad=ds.padded_len(16))
            np.testing.assert_array_equal(payload[0], x[0])
            np.testing.assert_array_equal(payload[2], mask[0])
            key = nxt
        # degenerate silo-outnumbers-pool prediction packs nothing
        assert mgr._pack_client((0, None))[1] is None
        mgr._prefetch.close()
