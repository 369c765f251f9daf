"""Resource-lifecycle analysis (FT020–FT025) — the pass-level behavior
the corpus pairs cannot express: shutdown-graph extraction over planted
owners and the shipped tree, snapshot presence/drift/accept (FT025),
lock-hold dataflow edges (aliased locks, nested with, one call level),
close-idempotency, and runtime regression tests for the real findings
the first whole-tree run surfaced (leaked TCP listener, leaked smoke
peer listener, failover serve() releasing its endpoint outside a
finally).
"""

import json
import socket
import textwrap
import time
from pathlib import Path

import pytest

from fedml_tpu.analysis import lifecycle as lc
from fedml_tpu.analysis.lint import build_contexts, lint_contexts

REPO = Path(__file__).resolve().parent.parent


def _ctxs_from(tmp_path, source, name="owner.py"):
    p = tmp_path / "fedml_tpu" / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    ctxs, errs = build_contexts([p.parent], root=tmp_path)
    assert errs == []
    return ctxs


def _rules():
    return [lc.ThreadLifecycleRule(), lc.LeakOnRaiseRule(),
            lc.BlockingUnderLockRule(), lc.ShutdownReachabilityRule(),
            lc.SubmitAfterCloseRule()]


def _lint(tmp_path, source):
    return list(lint_contexts(_ctxs_from(tmp_path, source),
                              rules=_rules()))


_OWNER = """
    import socket
    import threading


    class Owner:
        def __init__(self, port):
            self._stop = threading.Event()
            self._server = socket.create_server(("127.0.0.1", port))
            self._writer = threading.Thread(target=self._loop,
                                            daemon=True)
            self._writer.start()

        def _loop(self):
            self._stop.wait(timeout=1.0)

        def close(self):
            self._stop.set()
            self._writer.join(timeout=5.0)
            self._server.close()
"""


class TestGraphExtraction:
    """The artifact is the reviewer's shutdown map: thread roots,
    release edges, close methods, and stop signals per owner."""

    def test_worker_and_release_edges(self, tmp_path):
        graph = lc.extract_shutdown_graph(_ctxs_from(tmp_path, _OWNER))
        assert len(graph["classes"]) == 1
        owner = graph["classes"][0]
        assert owner["class"] == "Owner"
        assert owner["close_methods"] == ["close"]
        (worker,) = owner["workers"]
        assert worker["kind"] == "thread"
        assert worker["attr"] == "_writer"
        assert worker["daemon"] is True
        assert worker["created_in"] == "__init__"
        assert "close" in worker["joined_in"]
        (res,) = owner["resources"]
        assert res["kind"] == "socket"
        assert res["attr"] == "_server"
        assert "close" in res["released_in"]
        assert any("_stop" in s for s in owner["stop_signals"])

    def test_test_paths_are_excluded(self, tmp_path):
        p = tmp_path / "tests" / "test_owner.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(_OWNER))
        ctxs, _ = build_contexts([p.parent], root=tmp_path)
        assert lc.extract_shutdown_graph(ctxs)["classes"] == []

    def test_shipped_tree_covers_known_owners(self):
        ctxs, errs = build_contexts([REPO / "fedml_tpu"], root=REPO)
        assert errs == []
        graph = lc.extract_shutdown_graph(ctxs)
        by_name = {(c["module"], c["class"]): c for c in graph["classes"]}
        tcp = by_name[("fedml_tpu.comm.tcp", "TcpCommManager")]
        (server,) = [r for r in tcp["resources"]
                     if r["attr"] == "_server"]
        # the round-18 regression: the listener's release edge must be
        # the owner's own stop path, not only the accept loop
        assert "stop_receive_message" in server["released_in"]
        peer = by_name[("fedml_tpu.comm.fanout_smoke", "_RawPeer")]
        assert "close" in peer["close_methods"]

    def test_idempotent_close_unguarded_shutdown_fires(self, tmp_path):
        src = """
            import socket


            class Half:
                def __init__(self, port):
                    self._sock = socket.create_connection(
                        ("127.0.0.1", port), timeout=1.0)

                def close(self):
                    self._sock.shutdown(socket.SHUT_RDWR)
                    self._sock.close()
        """
        findings = _lint(tmp_path, src)
        assert any(f.rule == "FT023" and "idempotent" in f.message
                   for f in findings)

    def test_guarded_shutdown_is_clean(self, tmp_path):
        src = """
            import socket


            class Half:
                def __init__(self, port):
                    self._sock = socket.create_connection(
                        ("127.0.0.1", port), timeout=1.0)

                def close(self):
                    try:
                        self._sock.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    self._sock.close()
        """
        assert [f for f in _lint(tmp_path, src)
                if f.rule == "FT023"] == []


class TestSnapshot:
    """FT025: missing is loud, drift is loud with owner detail, accept
    is explicit (--write-shutdown-graph), match is silent."""

    @pytest.fixture()
    def graph(self, tmp_path):
        return lc.extract_shutdown_graph(_ctxs_from(tmp_path, _OWNER))

    def test_missing_snapshot_is_loud(self, graph, tmp_path):
        findings = lc.snapshot_findings(graph, tmp_path / "nope.json")
        assert [f.rule for f in findings] == ["FT025"]
        assert "MISSING" in findings[0].message

    def test_unreadable_snapshot_is_loud(self, graph, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        findings = lc.snapshot_findings(graph, bad)
        assert [f.rule for f in findings] == ["FT025"]

    def test_matching_snapshot_is_clean(self, graph, tmp_path):
        snap = tmp_path / "snap.json"
        snap.write_text(json.dumps(lc.normalize_graph(graph)))
        assert lc.snapshot_findings(graph, snap) == []

    def test_drift_names_the_owner(self, graph, tmp_path):
        stale = json.loads(json.dumps(lc.normalize_graph(graph)))
        stale["classes"][0]["workers"] = []
        stale["fingerprint"] = "0" * 16
        snap = tmp_path / "snap.json"
        snap.write_text(json.dumps(stale))
        findings = lc.snapshot_findings(graph, snap)
        assert [f.rule for f in findings] == ["FT025"]
        assert "Owner" in findings[0].message

    def test_write_snapshot_accepts(self, graph, tmp_path):
        ctxs = _ctxs_from(tmp_path, _OWNER, name="again.py")
        snap = tmp_path / "ci" / "snap.json"
        art = tmp_path / "runs" / "graph.json"
        findings, written = lc.check_lifecycle(
            ctxs, snap, artifact_path=art, write_snapshot=True)
        assert findings == []
        assert art.exists()
        # and the accepted snapshot now drift-checks clean
        findings, _ = lc.check_lifecycle(ctxs, snap, artifact_path=art)
        assert findings == []

    def test_snapshot_is_line_free_and_shift_stable(self, tmp_path):
        g1 = lc.extract_shutdown_graph(_ctxs_from(tmp_path, _OWNER))
        shifted = "# a comment line\n# another\n" + textwrap.dedent(_OWNER)
        p = tmp_path / "fedml_tpu" / "owner.py"
        p.write_text(shifted)
        ctxs, _ = build_contexts([p.parent], root=tmp_path)
        g2 = lc.extract_shutdown_graph(ctxs)
        assert g1["classes"][0]["workers"][0]["line"] != \
            g2["classes"][0]["workers"][0]["line"]
        assert lc.normalize_graph(g1)["fingerprint"] == \
            lc.normalize_graph(g2)["fingerprint"]
        assert "line" not in json.dumps(lc.normalize_graph(g2))

    def test_shipped_snapshot_matches_tree(self):
        ctxs, _ = build_contexts([REPO / "fedml_tpu"], root=REPO)
        graph = lc.extract_shutdown_graph(ctxs)
        assert lc.snapshot_findings(
            graph, REPO / "ci" / "shutdown_graph.json") == []


class TestLockHoldDataflow:
    """FT022's lexical hold-tracking: aliases, nesting (innermost-gate
    semantics), and the one-call-level edge."""

    def test_aliased_lock_is_tracked(self, tmp_path):
        src = """
            import queue
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def pull(self):
                    lk = self._lock
                    with lk:
                        return self._q.get()
        """
        findings = _lint(tmp_path, src)
        assert any(f.rule == "FT022" for f in findings)

    def test_innermost_device_gate_is_exempt(self, tmp_path):
        src = """
            import threading

            import jax


            class Swapper:
                def __init__(self):
                    self._swap_lock = threading.Lock()
                    self._device_lock = threading.Lock()

                def install(self, tree):
                    with self._swap_lock:
                        with self._device_lock:
                            dev = jax.device_put(tree)
                            jax.block_until_ready(dev)
                    return dev
        """
        assert [f for f in _lint(tmp_path, src)
                if f.rule == "FT022"] == []

    def test_device_dispatch_under_plain_lock_fires(self, tmp_path):
        src = """
            import threading

            import jax


            class Swapper:
                def __init__(self):
                    self._lock = threading.Lock()

                def install(self, tree):
                    with self._lock:
                        return jax.device_put(tree)
        """
        findings = _lint(tmp_path, src)
        assert any(f.rule == "FT022" and "device" in f.message
                   for f in findings)

    def test_one_call_level_edge(self, tmp_path):
        src = """
            import queue
            import threading


            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def _pull_locked(self):
                    return self._q.get()

                def flush(self):
                    with self._lock:
                        return self._pull_locked()
        """
        findings = [f for f in _lint(tmp_path, src) if f.rule == "FT022"]
        assert len(findings) == 1
        assert "_pull_locked" in findings[0].message

    def test_fsync_under_plain_lock_fires(self, tmp_path):
        # the round/receive-thread durability hazard: a disk barrier is
        # a blocking device wait, and every peer of the shared lock
        # (heartbeats, counters, close) stalls behind it
        src = """
            import os
            import threading


            class Ledger:
                def __init__(self, path):
                    self._lock = threading.Lock()
                    self._fh = open(path, "a")

                def append(self, line):
                    with self._lock:
                        self._fh.write(line)
                        os.fsync(self._fh.fileno())

                def close(self):
                    self._fh.close()
        """
        findings = _lint(tmp_path, src)
        assert any(f.rule == "FT022" and "fsync" in f.message
                   for f in findings)

    def test_fsync_under_writer_lock_is_exempt(self, tmp_path):
        # the writer-thread pattern: a lock named for the dedicated
        # writer exists to serialize exactly this I/O (same standing as
        # device gates and send locks in the exemption table)
        src = """
            import os
            import threading


            class Ledger:
                def __init__(self, path):
                    self._writer_lock = threading.Lock()
                    self._ledger_wlock = threading.Lock()
                    self._fh = open(path, "a")

                def append(self, line):
                    with self._ledger_wlock:
                        self._fh.write(line)
                        os.fsync(self._fh.fileno())

                def barrier(self):
                    with self._writer_lock:
                        os.fsync(self._fh.fileno())

                def close(self):
                    self._fh.close()
        """
        assert [f for f in _lint(tmp_path, src)
                if f.rule == "FT022"] == []

    def test_unbounded_join_under_lock_fires(self, tmp_path):
        src = """
            import threading


            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._worker = threading.Thread(target=int,
                                                    daemon=True)

                def reap(self):
                    with self._lock:
                        self._worker.join()
        """
        findings = _lint(tmp_path, src)
        assert any(f.rule == "FT022" and "join" in f.message
                   for f in findings)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestListenerReleaseRegressions:
    """Runtime regressions for the findings the first whole-tree run
    surfaced and this round fixed in-tree."""

    def test_sender_only_tcp_manager_releases_port(self):
        # FT023 finding: a TcpCommManager that never ran
        # handle_receive_message (sender-only) must still release its
        # bound listener from stop_receive_message — pre-fix the close
        # edge lived only in the accept loop and the port leaked
        from fedml_tpu.comm.tcp import TcpCommManager
        port = _free_port()
        addresses = {0: ("127.0.0.1", port)}
        com = TcpCommManager(0, addresses)
        com.stop_receive_message()
        com.stop_receive_message()  # idempotent
        rebound = socket.create_server(("127.0.0.1", port))
        rebound.close()

    def test_raw_peer_close_without_connection(self):
        # FT021 finding: _RawPeer's listener was only released by its
        # serve thread AFTER a connection arrived; a stage failing
        # before the connect leaked the port for the process lifetime
        from fedml_tpu.comm.fanout_smoke import _RawPeer
        peer = _RawPeer(0)  # its own free port: no gap to lose it in
        port = peer._server.getsockname()[1]
        time.sleep(0.05)  # let the serve thread block in accept()
        peer.close()
        peer.close()  # idempotent
        assert not peer._thread.is_alive()
        rebound = socket.create_server(("127.0.0.1", port))
        rebound.close()

    def test_failover_serve_releases_endpoint_on_raise(self, tmp_path,
                                                       monkeypatch):
        # audit finding: serve() called stop_receive_message() on the
        # straight line only — a raise while building the server left
        # the supervisor's relaunch port bound (EADDRINUSE)
        from fedml_tpu.control import failover_harness as fh

        def boom(*args, **kwargs):
            raise RuntimeError("planted: server build failed")

        monkeypatch.setattr(fh, "_build_server", boom)
        port = _free_port()
        with pytest.raises(RuntimeError, match="planted"):
            fh.serve(1, 1, port, str(tmp_path), deadline_s=1.0)
        rebound = socket.create_server(("127.0.0.1", port))
        rebound.close()


class TestCliIntegration:
    def test_partial_walk_skips_snapshot(self, tmp_path):
        # explicit paths must not drift-check (a partial graph would
        # always differ) nor clobber the artifact — mirrored from the
        # CLI's full_walk gate; the library half: extraction alone
        ctxs = _ctxs_from(tmp_path, _OWNER)
        graph = lc.extract_shutdown_graph(ctxs)
        assert len(graph["classes"]) == 1

    def test_cli_reports_lifecycle_summary(self):
        import subprocess
        import sys
        r = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.analysis", "--no-audit",
             "--no-protocol", "--no-roundshape", "--no-flags",
             "--strict-pragmas", "--format", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        report = json.loads(r.stdout)
        assert report["lifecycle"]["classes"] > 0
        assert report["counts"]["active"] == 0

    def test_write_flag_validated(self):
        import subprocess
        import sys
        r = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.analysis",
             "--write-shutdown-graph", "--no-lifecycle"],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert r.returncode == 2
        assert "--write-shutdown-graph" in r.stderr
