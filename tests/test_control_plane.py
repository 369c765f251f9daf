"""Elastic federation control plane: server checkpoint/failover, pace
steering, JOIN admission control, and the deadline-extension cap.

Oracle strategy mirrors tests/test_faults.py — control paths are only
trusted when EXERCISED:

- quantile tracker vs numpy's percentile; steerer convergence + clamps
  on synthetic latency traces; token bucket under a fake clock;
- snapshot save/restore round-trips, torn-write crash consistency
  (old-or-new COMPLETE, mirroring test_state_store.py);
- the acceptance core: a server that dies mid-schedule (cold receive-
  loop stop, no FINISH — SIGKILL as the fleet sees it) and a FRESH
  server that restores and completes, with the resumed run's
  round/cohort ledger AND final model BIT-EXACT against an unkilled
  reference, over inproc and tcp;
- control plane fully on but unexercised = bit-exact with the legacy
  path (the byte-identical-default guarantee);
- a permanently below-quorum round exhausts --max_deadline_extensions
  into a loud SchedulingStallError with the final state checkpointed.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg_cross_silo import (
    MSG_TYPE_C2S_JOIN, MSG_TYPE_S2C_JOIN_BACKPRESSURE,
    MSG_TYPE_S2C_SYNC_MODEL, MSG_ARG_KEY_RETRY_AFTER,
    MSG_ARG_KEY_ROUNDS_COMPLETED, FedAvgAggregator, FedAvgServerManager,
    run_fedavg_cross_silo)
from fedml_tpu.comm import Message
from fedml_tpu.control import (JoinAdmissionController, PaceSteerer,
                               SchedulingStallError,
                               ServerControlCheckpointer,
                               build_control_plane)
from fedml_tpu.control.failover_harness import (build_fixture,
                                                ledger_schedule,
                                                run_simulated_failover)
from fedml_tpu.control.pace import QUORUM_CEIL
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.utils.tracing import RoundTimer
from fedml_tpu.utils.watchdog import SlidingQuantileTracker


def tree_equal(a, b):
    fa, da = jax.tree.flatten(a)
    fb, db = jax.tree.flatten(b)
    assert da == db
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
class TestSlidingQuantileTracker:
    def test_quantiles_match_numpy_linear(self):
        rng = np.random.RandomState(7)
        vals = rng.exponential(2.0, size=100)
        t = SlidingQuantileTracker(window=256)
        for v in vals:
            t.observe(v)
        for q in (0.1, 0.25, 0.5, 0.9, 0.99):
            np.testing.assert_allclose(t.quantile(q),
                                       np.percentile(vals, q * 100),
                                       rtol=1e-12)

    def test_window_slides(self):
        t = SlidingQuantileTracker(window=4)
        for v in (100.0, 1.0, 2.0, 3.0, 4.0):
            t.observe(v)
        assert t.count() == 4
        assert t.quantile(1.0) == 4.0  # the 100.0 slid out

    def test_empty_and_roundtrip(self):
        t = SlidingQuantileTracker(window=8)
        assert t.quantile(0.5) is None and t.count() == 0
        t.observe(3.0)
        t.observe(1.0)
        t2 = SlidingQuantileTracker(window=8)
        t2.load(t.values())
        assert t2.values() == [3.0, 1.0]
        with pytest.raises(ValueError):
            SlidingQuantileTracker(window=0)


class TestPaceSteerer:
    def _tracker(self, values):
        t = SlidingQuantileTracker(window=256)
        for v in values:
            t.observe(v)
        return t

    def test_base_deadline_until_min_samples(self):
        p = PaceSteerer(base_deadline_s=10.0, min_samples=4)
        assert p.next_deadline(None) == 10.0
        assert p.next_deadline(self._tracker([1.0, 1.0, 1.0])) == 10.0
        assert p.next_quorum_frac() == 0.5  # floor until evidence

    def test_deadline_converges_to_p90_times_margin(self):
        # synthetic trace inside the clamp band: p90=4.0 -> 4.0*1.5=6.0
        p = PaceSteerer(base_deadline_s=5.0, quantile=0.9, margin=1.5)
        lat = self._tracker(np.linspace(0.4, 4.4, 101))
        expect = np.percentile(np.linspace(0.4, 4.4, 101), 90) * 1.5
        np.testing.assert_allclose(p.next_deadline(lat), expect,
                                   rtol=1e-12)

    def test_clamps_honored(self):
        p = PaceSteerer(base_deadline_s=8.0)  # band [2.0, 32.0]
        assert p.next_deadline(self._tracker([1e-4] * 32)) == 2.0
        assert p.next_deadline(self._tracker([1e4] * 32)) == 32.0
        pc = PaceSteerer(base_deadline_s=8.0, min_deadline_s=1.0,
                         max_deadline_s=3.0)
        assert pc.next_deadline(self._tracker([1e4] * 32)) == 3.0

    def test_quorum_tightens_on_full_participation(self):
        p = PaceSteerer(base_deadline_s=5.0, quorum_floor=0.5)
        for _ in range(10):
            p.observe_round(3, 3)
        np.testing.assert_allclose(p.next_quorum_frac(), 0.9)

    def test_quorum_relaxes_toward_floor_under_flap(self):
        p = PaceSteerer(base_deadline_s=5.0, quorum_floor=0.5)
        for _ in range(10):
            p.observe_round(2, 3)  # a third of the fleet flapping
        frac = p.next_quorum_frac()
        assert 0.5 <= frac <= 2.0 / 3.0
        # and never above the ceiling, no matter the evidence
        for _ in range(64):
            p.observe_round(3, 3)
        assert p.next_quorum_frac() <= QUORUM_CEIL

    def test_state_roundtrip(self):
        p = PaceSteerer(base_deadline_s=5.0)
        for r in range(6):
            p.observe_round(2 + r % 2, 3)
        q = PaceSteerer(base_deadline_s=5.0)
        q.load_state(p.state())
        assert q.next_quorum_frac() == p.next_quorum_frac()

    def test_invalid_config_raises(self):
        with pytest.raises(ValueError):
            PaceSteerer(base_deadline_s=None)
        with pytest.raises(ValueError):
            PaceSteerer(base_deadline_s=5.0, quantile=1.5)
        with pytest.raises(ValueError):
            PaceSteerer(base_deadline_s=5.0, min_deadline_s=9.0,
                        max_deadline_s=3.0)
        with pytest.raises(ValueError):
            build_control_plane(pace_steering=True)  # no base deadline


class TestJoinAdmission:
    def test_burst_then_throttle_fake_clock(self):
        now = [0.0]
        a = JoinAdmissionController(rate_per_s=2.0, burst=2,
                                    clock=lambda: now[0])
        assert a.try_acquire() and a.try_acquire()
        assert not a.try_acquire()  # bucket drained, clock frozen
        assert a.admitted == 2 and a.throttled == 1
        np.testing.assert_allclose(a.retry_after_s(), 0.5)  # 1 token / 2 per s
        now[0] += 0.5
        assert a.try_acquire()  # refilled exactly one token
        assert not a.try_acquire()

    def test_refill_caps_at_burst(self):
        now = [0.0]
        a = JoinAdmissionController(rate_per_s=10.0, burst=3,
                                    clock=lambda: now[0])
        now[0] += 100.0
        assert a.try_acquire() and a.try_acquire() and a.try_acquire()
        assert not a.try_acquire()

    def test_invalid_rate_raises(self):
        with pytest.raises(ValueError):
            JoinAdmissionController(rate_per_s=0.0)


# ---------------------------------------------------------------------------
class TestServerCheckpointer:
    def _state(self, r):
        return {"round_idx": r,
                "tree": {"w": np.full(4, r, np.float32)},
                "none": None, "flag": True,
                "nested": [{"round": r, "reported": [0, 1]}]}

    def test_save_load_roundtrip(self, tmp_path):
        ckp = ServerControlCheckpointer(str(tmp_path))
        assert ckp.load_latest() is None and ckp.latest_round() is None
        ckp.save(self._state(3))
        back = ckp.load_latest()
        assert back["round_idx"] == 3 and back["none"] is None
        np.testing.assert_array_equal(back["tree"]["w"],
                                      np.full(4, 3, np.float32))
        assert ckp.latest_round() == 3

    def test_keep_last_n_gc(self, tmp_path):
        ckp = ServerControlCheckpointer(str(tmp_path), keep_last_n=2)
        for r in range(5):
            ckp.save(self._state(r))
        blobs = [f for f in os.listdir(tmp_path) if f.endswith(".msgpack")]
        assert len(blobs) == 2
        assert ckp.load_latest()["round_idx"] == 4

    def test_torn_write_leaves_old_complete(self, tmp_path):
        """Crash-consistency contract (mirrors test_state_store.py): a
        blob without its sidecar, and stray .tmp files, are invisible —
        the previous complete snapshot stays authoritative."""
        ckp = ServerControlCheckpointer(str(tmp_path))
        ckp.save(self._state(1))
        # simulate a crash mid-save: the round-2 blob landed, the
        # sidecar never did; plus a stray tmp from an even earlier crash
        from flax import serialization as fser
        with open(tmp_path / "state_000000000007.msgpack", "wb") as f:
            f.write(fser.msgpack_serialize(
                dict(self._state(2), format=1)))
        with open(tmp_path / "state_000000000009.msgpack.123.tmp",
                  "wb") as f:
            f.write(b"torn")
        assert ckp.load_latest()["round_idx"] == 1
        # the next save GCs the orphans and becomes the newest snapshot
        ckp.save(self._state(3))
        assert ckp.load_latest()["round_idx"] == 3
        assert not any(f.endswith(".tmp") for f in os.listdir(tmp_path))

    def test_format_mismatch_raises(self, tmp_path):
        from flax import serialization as fser
        ckp = ServerControlCheckpointer(str(tmp_path))
        with open(tmp_path / "state_000000000000.msgpack", "wb") as f:
            f.write(fser.msgpack_serialize({"round_idx": 0, "format": 99}))
        with open(tmp_path / "state_000000000000.json", "w") as f:
            json.dump({"seq": 0, "round_idx": 0, "format": 99}, f)
        with pytest.raises(ValueError, match="format"):
            ckp.load_latest()

    def test_ledger_dedup_keeps_last_and_skips_torn_line(self, tmp_path):
        ckp = ServerControlCheckpointer(str(tmp_path))
        ckp.append_ledger({"round": 0, "cohort": [1], "reported": [0]})
        ckp.append_ledger({"round": 1, "cohort": [2], "reported": [0]})
        # a crash between ledger append and snapshot re-closes round 1:
        # the re-append is authoritative
        ckp.append_ledger({"round": 1, "cohort": [2], "reported": [0, 1]})
        with open(ckp.ledger_path, "a") as f:
            f.write('{"round": 2, "coh')  # kill mid-write
        rows = ckp.read_ledger()
        assert [r["round"] for r in rows] == [0, 1]
        assert rows[1]["reported"] == [0, 1]
        assert len(ckp.read_ledger(dedup=False)) == 3


# ---------------------------------------------------------------------------
class TestIncrementalSerializer:
    """The byte-splice serializer against the monolithic flax output —
    cached-field reuse must be byte-INVISIBLE (the torn-write and
    restore oracles read blobs, not field lists)."""

    def _state(self, r):
        return {"round_idx": r, "format": 1,
                "global_model": {"w": np.arange(8, dtype=np.float32) * r},
                "zeta": None, "alpha": [1, {"b": 2}]}

    def test_splice_is_byte_identical_and_caches(self):
        from flax import serialization as fser

        from fedml_tpu.control.checkpoint import IncrementalStateSerializer
        ser = IncrementalStateSerializer()
        s1 = self._state(1)
        blob = ser.serialize(s1, versions={"global_model": 0})
        assert blob == fser.msgpack_serialize(s1)
        assert ser.cache_misses == 1 and ser.cache_hits == 0
        # token unchanged -> cached bytes, still byte-identical
        blob2 = ser.serialize(dict(s1, round_idx=2),
                              versions={"global_model": 0})
        assert blob2 == fser.msgpack_serialize(dict(s1, round_idx=2))
        assert ser.cache_hits == 1
        assert ser.field_sha("global_model") is not None
        # token bumped -> fresh bytes for the new value
        s3 = dict(s1, round_idx=3,
                  global_model={"w": np.arange(8, dtype=np.float32) * 9})
        blob3 = ser.serialize(s3, versions={"global_model": 1})
        assert blob3 == fser.msgpack_serialize(s3)
        assert ser.cache_misses == 2

    def test_no_versions_means_monolithic(self):
        from flax import serialization as fser

        from fedml_tpu.control.checkpoint import IncrementalStateSerializer
        ser = IncrementalStateSerializer()
        s = self._state(4)
        assert ser.serialize(s, versions=None) == fser.msgpack_serialize(s)
        assert ser.cache_misses == 0

    def test_mismatch_falls_back_permanently(self, caplog):
        """A poisoned cache entry (stands in for a future msgpack/flax
        encoding change) must trip the one-time parity oracle: the call
        returns the CORRECT monolithic bytes and the splice is retired
        for the process."""
        from flax import serialization as fser

        from fedml_tpu.control.checkpoint import IncrementalStateSerializer
        ser = IncrementalStateSerializer()
        ser._cache["global_model"] = (0, b"\xc0", "bogus")
        s = self._state(5)
        import logging as _logging
        with caplog.at_level(_logging.WARNING):
            blob = ser.serialize(s, versions={"global_model": 0})
        assert blob == fser.msgpack_serialize(s)
        assert ser._fallback and not ser._cache
        assert ser.serialize(s, versions={"global_model": 0}) == blob

    def test_map_headers_match_packb_across_sizes(self):
        import msgpack

        from fedml_tpu.control.checkpoint import _msgpack_map_header
        for n in (0, 15, 16, 255, 0xFFFF, 0x10000):
            # the hand-written header must equal what packb itself
            # writes for an n-entry map (fixmap / map16 / map32)
            probe = msgpack.packb({str(i): None for i in range(n)})
            assert probe.startswith(_msgpack_map_header(n)), n


class TestAsyncCheckpointWriter:
    """The writer-thread layer's own contracts: coalescing under
    backpressure, the flush barrier, abort-as-SIGKILL, ledger group
    commit, and the ledger-before-snapshot durability ordering."""

    def _state(self, r):
        return {"round_idx": r, "tree": {"w": np.full(4, r, np.float32)}}

    def _gated(self, tmp_path, **kw):
        """An async writer whose inner save blocks until released —
        deterministic backpressure."""
        from fedml_tpu.control import AsyncCheckpointWriter
        inner = ServerControlCheckpointer(str(tmp_path), **kw)
        gate = threading.Event()
        orig = inner.save

        def gated_save(state, versions=None):
            # held until the test releases it; a writer killed meanwhile
            # (abort) publishes nothing, as a killed process would not -
            # a timeout that let the held write through raced abort's join
            deadline = time.monotonic() + 10
            while not gate.wait(0.01):
                if writer._stopped or time.monotonic() > deadline:
                    raise RuntimeError("the held write never went through")
            return orig(state, versions=versions)

        inner.save = gated_save
        writer = AsyncCheckpointWriter(inner)
        return writer, inner, gate, orig

    def test_flush_barrier_publishes_newest(self, tmp_path):
        from fedml_tpu.control import AsyncCheckpointWriter
        w = AsyncCheckpointWriter(ServerControlCheckpointer(str(tmp_path)))
        for r in range(3):
            w.save(self._state(r))
        assert w.flush()
        assert w.load_latest()["round_idx"] == 2
        w.close()

    def test_coalescing_under_backpressure(self, tmp_path):
        w, inner, gate, _ = self._gated(tmp_path)
        for r in range(5):
            w.save(self._state(r))
            time.sleep(0.02)  # let the writer pick up the FIRST save
        gate.set()
        assert w.flush()
        stats = w.stats()
        # first save in flight + newest-wins slot: intermediate
        # snapshots were coalesced away, the final publish is round 4
        assert stats["coalesced"] >= 1
        assert stats["published"] + stats["coalesced"] == 5
        assert w.load_latest()["round_idx"] == 4
        assert w.pop_coalesced() == stats["coalesced"]
        assert w.pop_coalesced() == 0
        w.close()

    def test_abort_mid_async_write_restores_older_boundary(self, tmp_path):
        """Simulated SIGKILL mid-async-write: the ledger tail is newer
        than the newest published snapshot and a stray .tmp sits in the
        directory — restore lands on the older complete boundary and
        the schedule replays forward (re-appended rows dedup by
        round)."""
        w, inner, gate, orig = self._gated(tmp_path)
        gate.set()
        w.append_ledger({"round": 0, "cohort": [1], "reported": [0]})
        w.append_ledger({"round": 1, "cohort": [2], "reported": [0]})
        w.save(self._state(1))
        assert w.flush()
        # round 2 closes: ledger appended, snapshot handed to the
        # writer... and the process dies mid-write
        gate.clear()
        w.append_ledger({"round": 2, "cohort": [3], "reported": [0]})
        w.save(self._state(2))
        with open(os.path.join(str(tmp_path),
                               "state_000000000099.msgpack.1.tmp"),
                  "wb") as f:
            f.write(b"torn mid-write")
        w.abort()
        gate.set()
        # a fresh process opens the directory
        ckp2 = ServerControlCheckpointer(str(tmp_path))
        restored = ckp2.load_latest()
        rows = ckp2.read_ledger()
        assert restored["round_idx"] == 1  # older than the ledger tail
        assert [r["round"] for r in rows] == [0, 1, 2]
        # replay forward: round 2 re-closes, re-appends, snapshots
        ckp2.append_ledger({"round": 2, "cohort": [3], "reported": [0]})
        ckp2.save(self._state(2))
        assert ckp2.load_latest()["round_idx"] == 2
        rows = ckp2.read_ledger()
        assert [r["round"] for r in rows] == [0, 1, 2]
        assert not any(f.endswith(".tmp") for f in os.listdir(tmp_path))
        ckp2.close()

    def test_post_close_save_degrades_inline(self, tmp_path):
        from fedml_tpu.control import AsyncCheckpointWriter
        w = AsyncCheckpointWriter(ServerControlCheckpointer(str(tmp_path)))
        w.close()
        w.save(self._state(7))  # no thread left — must still land
        assert w.load_latest()["round_idx"] == 7

    def test_ledger_group_commit_batches_fsyncs(self, tmp_path):
        ckp = ServerControlCheckpointer(str(tmp_path),
                                        group_commit_lines=4,
                                        group_commit_ms=0.0)
        for r in range(3):
            ckp.append_ledger({"round": r, "cohort": [], "reported": []})
        assert ckp.ledger_fsync_count == 0
        # every line is already readable (write+flush per line)
        assert [r["round"] for r in ckp.read_ledger()] == [0, 1, 2]
        ckp.append_ledger({"round": 3, "cohort": [], "reported": []})
        assert ckp.ledger_fsync_count == 1  # batch of 4 committed
        ckp.sync_ledger()
        assert ckp.ledger_fsync_count == 1  # nothing pending: no-op
        ckp.append_ledger({"round": 4, "cohort": [], "reported": []})
        ckp.close()  # flush-on-close commits the tail
        assert ckp.ledger_fsync_count == 2

    def test_writer_syncs_ledger_before_publish(self, tmp_path):
        """The one new invariant async checkpointing needs: snapshot
        durability never outruns ledger durability."""
        from fedml_tpu.control import AsyncCheckpointWriter
        inner = ServerControlCheckpointer(str(tmp_path),
                                          group_commit_lines=100,
                                          group_commit_ms=0.0)
        w = AsyncCheckpointWriter(inner)
        w.append_ledger({"round": 0, "cohort": [], "reported": []})
        assert inner.ledger_fsync_count == 0  # far from the batch size
        w.save(self._state(0))
        assert w.flush()
        assert inner.ledger_fsync_count >= 1  # pre-publish barrier
        w.close()

    def test_legacy_default_is_fsync_per_line(self, tmp_path):
        ckp = ServerControlCheckpointer(str(tmp_path))
        for r in range(3):
            ckp.append_ledger({"round": r, "cohort": [], "reported": []})
        assert ckp.ledger_fsync_count == 3
        ckp.close()


# ---------------------------------------------------------------------------
def _run_federation(ds, tcfg, **kw):
    timer = RoundTimer()
    model, history = run_fedavg_cross_silo(
        ds, LogisticRegression(num_classes=3), worker_num=3, comm_round=3,
        train_cfg=tcfg, timer=timer, **kw)
    return jax.tree.map(np.asarray, model), history, timer


class TestControlPlaneParity:
    """The byte-identical-default guarantee: snapshots are a pure
    observer, and healthy-fleet steering never changes the trajectory."""

    def test_checkpointing_is_a_pure_observer(self, tmp_path):
        ds, _, tcfg = build_fixture(3)
        clean, hist_c, timer_c = _run_federation(ds, tcfg)
        ck, hist_k, timer_k = _run_federation(
            ds, tcfg, server_checkpoint_dir=str(tmp_path / "ck"))
        tree_equal(clean, ck)
        assert hist_c == hist_k
        assert timer_k.counters["cp_checkpoints"] == 3
        assert timer_k.counters["cp_restores"] == 0
        # the cp_* family is always present, zeros included (like ft_*)
        for key in ("cp_checkpoints", "cp_restores",
                    "cp_deadline_adjustments", "cp_joins_throttled"):
            assert key in timer_c.counters
            assert timer_c.counters[key] == 0

    def test_steering_healthy_fleet_is_bit_exact(self, tmp_path):
        ds, _, tcfg = build_fixture(3)
        clean, hist_c, _ = _run_federation(ds, tcfg)
        # a generous base so the steered (clamped-to-base/4) deadline
        # still dwarfs sub-second rounds: no eviction ever fires and the
        # trajectory must be bit-identical to the static schedule
        steered, hist_s, timer = _run_federation(
            ds, tcfg, round_deadline_s=60.0, pace_steering=True,
            server_checkpoint_dir=str(tmp_path / "ck"))
        tree_equal(clean, steered)
        assert hist_c == hist_s
        assert timer.counters["cp_deadline_adjustments"] >= 1
        assert 0 < timer.gauges["cp_steered_deadline_s"] <= 60.0
        # the snapshot carries the steering evidence for the next life
        snap = ServerControlCheckpointer(str(tmp_path / "ck")).load_latest()
        assert snap["pace"] is not None
        assert len(snap["latency_window"]) >= 3

    def test_quorum_server_checkpoints_and_captures_extras(self, tmp_path):
        """The quorum flavor rides the same control plane: snapshots per
        round, subclass extras (partial_rounds + quorum) captured."""
        from fedml_tpu.algorithms.fedavg_async import run_fedavg_async
        ds, _, tcfg = build_fixture(3)
        timer = RoundTimer()
        _, history, server = run_fedavg_async(
            ds, LogisticRegression(num_classes=3), worker_num=3,
            mode="quorum", comm_round=3, quorum=2, round_deadline_s=20.0,
            train_cfg=tcfg, wire_codec=True, timer=timer,
            server_checkpoint_dir=str(tmp_path / "q"))
        assert server.round_idx == 3
        assert timer.counters["cp_checkpoints"] == 3
        snap = ServerControlCheckpointer(str(tmp_path / "q")).load_latest()
        assert snap["round_idx"] == 3
        assert snap["quorum"] == 2
        assert snap["evict_on_deadline"] is False
        assert isinstance(snap["partial_rounds"], list)


class TestServerFailoverResumeParity:
    """The acceptance core: kill the server mid-schedule, restart it,
    and the resumed trajectory must MATCH the unkilled run — ledger
    (round/cohort/reported) and final model, bit for bit."""

    @pytest.fixture(scope="class")
    def reference(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("ref")
        model, ledger, server = run_simulated_failover(
            str(d), rounds=6, crash_at_round=10**9)
        return model, ledger

    def test_kill_restore_resume_parity_inproc(self, tmp_path, reference):
        ref_model, ref_ledger = reference
        model, ledger, s2 = run_simulated_failover(
            str(tmp_path / "kill"), rounds=6, crash_at_round=3)
        assert s2.cp_counters["restores"] == 1
        assert ledger_schedule(ledger) == ledger_schedule(ref_ledger)
        assert [r["reported"] for r in ledger] \
            == [r["reported"] for r in ref_ledger]
        tree_equal(ref_model, model)

    def test_kill_restore_resume_parity_tcp(self, tmp_path, reference):
        ref_model, ref_ledger = reference
        model, ledger, s2 = run_simulated_failover(
            str(tmp_path / "kill_tcp"), rounds=6, crash_at_round=3,
            backend="TCP", port_base=40410)
        assert s2.cp_counters["restores"] == 1
        assert ledger_schedule(ledger) == ledger_schedule(ref_ledger)
        tree_equal(ref_model, model)

    def test_fedopt_snapshot_restores_server_optimizer(self):
        """FedOpt's persistent optimizer state (adam mu/nu) rides the
        snapshot: capture on one server, msgpack round-trip, restore
        into a FRESH server — optimizer state and model bit-equal."""
        import flax.serialization as fser
        import jax.numpy as jnp
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            FedOptServerManager)
        ds, module, _ = build_fixture(3)
        gm = module.init(jax.random.key(0),
                         jnp.asarray(ds.train_data_global[0][:1]),
                         train=False)

        def fedopt(agg):
            return FedOptServerManager(0, 4, _RecordingCom(), agg, 4,
                                       ds.client_num, gm,
                                       server_optimizer="adam",
                                       server_lr=0.05)

        s_a = fedopt(FedAvgAggregator(3))
        # advance the optimizer once so mu/nu are non-trivial
        s_a.aggregator.model_dict = {
            0: jax.tree.map(lambda x: np.asarray(x) + 0.1, gm)}
        s_a.aggregator.sample_num_dict = {0: 1.0}
        s_a.global_model = s_a._aggregate_round(partial=True)
        blob = fser.msgpack_serialize(s_a._capture_control_state())
        s_b = fedopt(FedAvgAggregator(3))
        s_b._restore_control_state(fser.msgpack_restore(blob))
        tree_equal(jax.tree.map(np.asarray, s_a.server_opt_state),
                   jax.tree.map(np.asarray, s_b.server_opt_state))
        tree_equal(jax.tree.map(np.asarray, s_a.global_model),
                   jax.tree.map(np.asarray, s_b.global_model))

    def test_restore_refuses_mismatched_schedule(self):
        server_a, _ = _stub_server()
        state = server_a._capture_control_state()
        import jax.numpy as jnp
        ds, module, _ = build_fixture(3)
        gm = module.init(jax.random.key(0),
                         jnp.asarray(ds.train_data_global[0][:1]),
                         train=False)
        other = FedAvgServerManager(0, 3, _RecordingCom(),
                                    FedAvgAggregator(2), 8, ds.client_num,
                                    gm)
        with pytest.raises(ValueError, match="refusing"):
            other._restore_control_state(state)


# ---------------------------------------------------------------------------
class _RecordingCom:
    """Stub comm manager: records every sent message."""

    def __init__(self):
        self.sent = []

    def add_observer(self, obs):
        pass

    def send_message(self, msg):
        self.sent.append(msg)

    def stop_receive_message(self):
        pass


def _stub_server(**kw):
    import jax.numpy as jnp
    ds, module, _ = build_fixture(3)
    gm = module.init(jax.random.key(0),
                     jnp.asarray(ds.train_data_global[0][:1]), train=False)
    com = _RecordingCom()
    server = FedAvgServerManager(0, 4, com, FedAvgAggregator(3), 8,
                                 ds.client_num, gm, round_deadline_s=30.0,
                                 **kw)
    return server, com


class TestJoinFloodThrottling:
    def _join(self, server, rank):
        msg = Message(MSG_TYPE_C2S_JOIN, rank, 0)
        msg.add(MSG_ARG_KEY_ROUNDS_COMPLETED, 0)
        server.handle_message_join(msg)

    def test_flood_is_token_bucketed_with_backpressure(self):
        now = [0.0]
        server, com = _stub_server(
            join_admission=JoinAdmissionController(rate_per_s=1.0, burst=2,
                                                   clock=lambda: now[0]))
        for w in range(3):
            server.liveness.evict(w)
        # a healed partition: every silo JOINs at once
        for rank in (1, 2, 3):
            self._join(server, rank)
        resyncs = [m for m in com.sent
                   if m.get_type() == MSG_TYPE_S2C_SYNC_MODEL]
        backpressure = [m for m in com.sent
                        if m.get_type() == MSG_TYPE_S2C_JOIN_BACKPRESSURE]
        assert len(resyncs) == 2  # burst
        assert len(backpressure) == 1
        assert backpressure[0].get(MSG_ARG_KEY_RETRY_AFTER) > 0
        assert server.cp_counters["joins_throttled"] == 1
        # the throttled silo stays evicted — it retries after the backoff
        assert not server.liveness.is_live(2)
        now[0] += 1.1  # a token refilled: the retry is admitted
        self._join(server, 3)
        assert server.liveness.is_live(2)
        assert server.cp_counters["joins_throttled"] == 1

    def test_no_admission_controller_admits_everything(self):
        server, com = _stub_server()
        for w in range(3):
            server.liveness.evict(w)
        for rank in (1, 2, 3):
            self._join(server, rank)
        assert server.liveness.live_workers() == {0, 1, 2}
        assert all(m.get_type() != MSG_TYPE_S2C_JOIN_BACKPRESSURE
                   for m in com.sent)

    def test_backpressured_silo_defers_join(self):
        """Client half: a BACKPRESSURE reply pushes the silo's next JOIN
        attempt past retry_after_s without silencing its heartbeats."""
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            FedAvgClientManager)
        ds, module, tcfg = build_fixture(3)
        com = _RecordingCom()
        silo = FedAvgClientManager(1, 4, com, ds, module, "classification",
                                   tcfg, heartbeat_s=0.0,
                                   prefetch_depth=0)
        msg = Message(MSG_TYPE_S2C_JOIN_BACKPRESSURE, 0, 1)
        msg.add(MSG_ARG_KEY_RETRY_AFTER, 5.0)
        silo._handle_join_backpressure(msg)
        assert silo._join_backoff_until > time.monotonic() + 4.0


# ---------------------------------------------------------------------------
class TestDeadlineExtensionCap:
    def test_permanent_under_quorum_raises_and_checkpoints(self, tmp_path):
        """A silo whose replies never arrive + a full-participation
        quorum target: the round extends, exhausts the cap, and fails
        LOUDLY with the final (mid-round, partial-laden) state durably
        checkpointed — instead of extending forever."""
        ds, _, tcfg = build_fixture(3)
        ckpt = str(tmp_path / "ck")
        with pytest.raises(SchedulingStallError, match="below quorum"):
            run_fedavg_cross_silo(
                ds, LogisticRegression(num_classes=3), worker_num=3,
                comm_round=4, train_cfg=tcfg,
                round_deadline_s=0.4, min_quorum_frac=1.0,
                max_deadline_extensions=2,
                server_checkpoint_dir=ckpt,
                # silo 3 trains but its replies vanish on the wire
                fault_plan="seed=3;drop:p=1.0,direction=send,sender=3,"
                           "msg_type=4",
                join_timeout_s=120.0)
        snap = ServerControlCheckpointer(ckpt).load_latest()
        assert snap is not None
        assert snap["round_idx"] == 0  # the round that could not close
        assert snap["extensions_this_round"] >= 3
        # the streaming fold absorbs the contiguous worker-index prefix
        # as it arrives: workers 0 and 1 live in the snapshot as fold
        # state (running sum + prefix bound), not as pending models
        fold = snap["agg_fold"]
        reported = sorted(set(range(int(fold["next"])))
                          | {int(w) for w in snap["pending_models"]})
        assert reported == [0, 1]
        assert int(fold["count"]) == 2  # both folded: the prefix was ready
        assert fold["acc"] is not None

    def test_steered_quorum_never_demands_every_live_silo(self):
        """ceil(0.9 * 3) == 3, so the steered fraction alone would
        require EVERY live silo — the effective requirement must be
        capped at live-1 while steering is active, or one silently hung
        silo (no send error -> never evicted) stalls the schedule into
        the extension cap. The static-flag path keeps exact legacy
        semantics: an explicit min_quorum_frac=1.0 means what it says."""
        from fedml_tpu.algorithms.fedavg_cross_silo import (
            MSG_TYPE_ROUND_TIMEOUT, MSG_ARG_KEY_ROUND)

        def timeout_msg():
            m = Message(MSG_TYPE_ROUND_TIMEOUT, 0, 0)
            m.add(MSG_ARG_KEY_ROUND, 0)
            return m

        def two_of_three_reported(server):
            gm = jax.tree.map(np.asarray, server.global_model)
            for w in (0, 1):
                server.aggregator.add_local_trained_result(w, gm, 1.0)

        steered, _ = _stub_server(
            min_quorum_frac=0.9,
            pace=PaceSteerer(base_deadline_s=30.0, quorum_floor=0.9))
        two_of_three_reported(steered)
        steered.handle_round_timeout(timeout_msg())
        assert steered.round_idx == 1  # closed partial at live-1
        assert steered.ft_counters["partial_rounds"] == 1
        assert steered.ft_counters["deadline_extensions"] == 0
        static, _ = _stub_server(min_quorum_frac=0.9)
        two_of_three_reported(static)
        static.handle_round_timeout(timeout_msg())
        assert static.round_idx == 0  # legacy: extend, don't cap
        assert static.ft_counters["deadline_extensions"] == 1
        static._cancel_deadline()

    def test_boundary_snapshot_resets_extension_budget(self, tmp_path):
        """The round-boundary snapshot must carry a FULL extension
        budget for the next round: a restored server otherwise starts
        pre-charged with the closed round's extensions and can hit the
        cap spuriously — diverging from the unkilled run exactly under
        the degraded-fleet conditions failover exists for."""
        server, _ = _stub_server(
            server_ckpt=ServerControlCheckpointer(str(tmp_path)))
        server._extensions_this_round = 7  # a rough closed round
        gm = jax.tree.map(np.asarray, server.global_model)
        for w in range(3):
            server.aggregator.add_local_trained_result(w, gm, 1.0)
        server._close_round()
        server._cancel_deadline()
        snap = ServerControlCheckpointer(str(tmp_path)).load_latest()
        assert snap["round_idx"] == 1
        assert snap["extensions_this_round"] == 0

    def test_extension_counter_still_counts_below_cap(self):
        server, _ = _stub_server(max_deadline_extensions=5)
        assert not server._note_deadline_extension()
        assert server.ft_counters["deadline_extensions"] == 1
        unbounded, _ = _stub_server(max_deadline_extensions=None)
        for _ in range(500):
            assert not unbounded._note_deadline_extension()


# ---------------------------------------------------------------------------
class TestServerKillScenario:
    def test_server_coma_plan_recovers_via_join_resync(self):
        """comm/faults.py server_kill_plan: the server endpoint goes
        completely dark mid-broadcast (the fleet's view of a crash,
        state intact — the restore path is the failover suite above).
        Recovery is the PR-5 protocol doing its job: silos that never
        got the round's broadcast JOIN-escalate after the silence and
        the server re-drives the round via resync — schedule completes."""
        from fedml_tpu.comm.faults import server_kill_plan
        plan = server_kill_plan(seed=5, after_broadcasts=1, down_ms=1500)
        assert plan.rules[0].op == "disconnect"
        ds, _, tcfg = build_fixture(3)
        timer = RoundTimer()
        _, history = run_fedavg_cross_silo(
            ds, LogisticRegression(num_classes=3), worker_num=3,
            comm_round=4, train_cfg=tcfg, fault_plan=plan,
            round_deadline_s=0.8, min_quorum_frac=0.5, heartbeat_s=0.25,
            timer=timer, join_timeout_s=120.0)
        assert history and history[-1]["round"] == 3
        assert timer.counters["ft_faults_injected"] >= 1
        # the dark window forced the round to be re-driven: either a
        # below-quorum extension, a JOIN resync, or both
        assert (timer.counters["ft_deadline_extensions"]
                + timer.counters["ft_join_resyncs"]) >= 1


@pytest.mark.slow
class TestSigkillChaosAcceptance:
    """ISSUE acceptance: seeded FaultPlan flapping a third of the silos +
    SIGKILL of the server PROCESS mid-round; the restarted server resumes
    from its snapshot, the full schedule completes, cp_restores >= 1, and
    the resumed run's round/cohort ledger matches an unkilled reference's."""

    def test_sigkill_mid_schedule_with_silo_flap(self, tmp_path):
        from fedml_tpu.control.failover_harness import run_failover_scenario
        ref_dir = str(tmp_path / "ref")
        _, ref_ledger, _ = run_simulated_failover(
            ref_dir, rounds=8, crash_at_round=10**9, backend="TCP",
            port_base=40510, deadline_s=2.0)
        res = run_failover_scenario(
            str(tmp_path / "kill"), rounds=8, kill_after_round=2,
            port_base=40530, deadline_s=2.0,
            # 1 of 3 silos (~30% of the fleet) randomly partitioned on
            # broadcasts throughout the run
            silo_fault_plan="seed=13;disconnect:direction=recv,"
                            "receiver=3,msg_type=2,p=0.3,"
                            "duration_ms=800")
        assert res["summary"]["done"] is True
        assert res["summary"]["rounds_completed"] == 8
        assert res["summary"]["cp_counters"].get("restores", 0) >= 1
        assert res["killed_at_round"] == 2
        assert ledger_schedule(res["ledger"]) == ledger_schedule(ref_ledger)
