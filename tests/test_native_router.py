"""Native C++ message router (native/router.cpp) + ROUTED backend.

The native component replaces the transport role of the reference's
mpi4py/MQTT stack; these tests build the shared library with g++ (baked into
the environment) and exercise it end-to-end.
"""

import socket
import struct
import threading
import time

import numpy as np
import pytest

pytest.importorskip("ctypes")

from fedml_tpu.native import NativeRouter, NativeUnavailable, build_lib

try:
    build_lib()
    _HAVE_NATIVE = True
except NativeUnavailable as exc:  # pragma: no cover - toolchain is baked in
    _HAVE_NATIVE = False
    _REASON = str(exc)

pytestmark = pytest.mark.skipif(not _HAVE_NATIVE,
                                reason="native toolchain unavailable")

_HELLO = struct.Struct("<II")
_HDR = struct.Struct("<IQ")
_MAGIC = 0x464D4C52


def _dial(port: int, rank: int) -> socket.socket:
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.sendall(_HELLO.pack(_MAGIC, rank))
    return s


def _send(s: socket.socket, dest: int, payload: bytes):
    s.sendall(_HDR.pack(dest, len(payload)) + payload)


def _recv(s: socket.socket):
    hdr = b""
    while len(hdr) < _HDR.size:
        chunk = s.recv(_HDR.size - len(hdr))
        assert chunk, "router closed"
        hdr += chunk
    src, length = _HDR.unpack(hdr)
    buf = b""
    while len(buf) < length:
        chunk = s.recv(min(1 << 20, length - len(buf)))
        assert chunk, "router closed mid-frame"
        buf += chunk
    return src, buf


class TestRouterCore:
    def test_route_between_ranks(self):
        with NativeRouter() as r:
            a, b = _dial(r.port, 1), _dial(r.port, 2)
            _send(a, 2, b"hello-from-1")
            src, payload = _recv(b)
            assert (src, payload) == (1, b"hello-from-1")
            _send(b, 1, b"reply")
            assert _recv(a) == (2, b"reply")
            # the router counts a frame after the write its reader has
            # already seen: give the counter its moment on a loaded host
            deadline = time.monotonic() + 5
            while r.frames_routed < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert r.frames_routed == 2
            assert r.bytes_routed == len(b"hello-from-1") + len(b"reply")
            a.close(), b.close()

    def test_buffering_before_destination_connects(self):
        with NativeRouter() as r:
            a = _dial(r.port, 1)
            _send(a, 5, b"early-frame")
            _send(a, 5, b"second")
            b = _dial(r.port, 5)  # flushes backlog in order
            assert _recv(b) == (1, b"early-frame")
            assert _recv(b) == (1, b"second")
            a.close(), b.close()

    def test_duplicate_rank_refused(self):
        with NativeRouter() as r:
            a = _dial(r.port, 7)
            _send(a, 7, b"loop")  # self-addressed, proves a is functional
            assert _recv(a) == (7, b"loop")
            dup = _dial(r.port, 7)
            # the router closes the duplicate: the next read returns EOF
            dup.settimeout(10)
            assert dup.recv(1) == b""
            a.close(), dup.close()

    def test_auth_token_gates_registration(self):
        _AUTH = struct.Struct("<III")
        with NativeRouter(token=b"sekrit") as r:
            # correct token: full route works (RoutedCommManager wire form)
            a = socket.create_connection(("127.0.0.1", r.port), timeout=10)
            a.sendall(_AUTH.pack(0x464D4C53, 3, 6) + b"sekrit")
            _send(a, 3, b"ok")
            assert _recv(a) == (3, b"ok")
            # wrong token: closed before registration
            bad = socket.create_connection(("127.0.0.1", r.port), timeout=10)
            bad.sendall(_AUTH.pack(0x464D4C53, 4, 5) + b"wrong")
            bad.settimeout(10)
            assert bad.recv(1) == b""
            # legacy token-less HELLO: also rejected when a token is set
            legacy = socket.create_connection(("127.0.0.1", r.port),
                                              timeout=10)
            legacy.sendall(_HELLO.pack(_MAGIC, 5))
            legacy.settimeout(10)
            assert legacy.recv(1) == b""
            a.close(), bad.close(), legacy.close()

    def test_auth_token_routed_backend(self):
        from fedml_tpu.comm.registry import create_comm_manager

        # binary token with an embedded NUL: must survive the FFI intact
        tok = b"\x00bin\x00tok"
        with NativeRouter(token=tok) as r:
            # the production path: registry -> RoutedCommManager(token=...);
            # __init__ performs the registration handshake, so constructing
            # successfully proves the HELLO was accepted
            m = create_comm_manager("ROUTED", 2, 2,
                                    addresses={"router": ("127.0.0.1",
                                                          r.port)},
                                    token=tok)
            m._sock.close()
            # wrong token surfaces as a clear ConnectionError at
            # construction, not a generic mid-round connection loss
            with pytest.raises(ConnectionError, match="token mismatch"):
                create_comm_manager("ROUTED", 3, 2,
                                    addresses={"router": ("127.0.0.1",
                                                          r.port)},
                                    token=b"\x00bin\x00WRONG")
            # token-less client against a tokened router: same clear error
            with pytest.raises(ConnectionError, match="token mismatch"):
                create_comm_manager("ROUTED", 4, 2,
                                    addresses={"router": ("127.0.0.1",
                                                          r.port)})

    def test_large_frame(self):
        with NativeRouter() as r:
            a, b = _dial(r.port, 0), _dial(r.port, 1)
            blob = np.random.default_rng(0).integers(
                0, 256, 8 << 20, dtype=np.uint8).tobytes()  # 8 MiB
            _send(a, 1, blob)
            src, payload = _recv(b)
            assert src == 0 and payload == blob
            a.close(), b.close()

    def test_routed_backend_raises_on_broker_death(self):
        from fedml_tpu.comm.routed import RoutedCommManager

        r = NativeRouter()
        m = RoutedCommManager(1, ("127.0.0.1", r.port))
        result = {}

        def runner():
            try:
                m.handle_receive_message()
                result["outcome"] = "clean-return"
            except ConnectionError as exc:
                result["outcome"] = f"raised: {exc}"

        t = threading.Thread(target=runner, daemon=True)
        t.start()
        import time
        time.sleep(0.3)  # let the loop start
        r.stop()  # broker dies mid-protocol
        t.join(timeout=10)
        assert not t.is_alive()
        assert result["outcome"].startswith("raised"), result

    def test_stop_unblocks_clients(self):
        r = NativeRouter()
        a = _dial(r.port, 3)
        done = threading.Event()

        def reader():
            try:
                _recv(a)
            # stop() may close the socket mid-recv as an RST instead of
            # a clean FIN under load (ConnectionResetError) — either way
            # the client IS unblocked, which is what this test asserts
            except (AssertionError, OSError):
                pass
            finally:
                done.set()

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        r.stop()
        assert done.wait(timeout=10), "client blocked after router stop"
        a.close()


class TestRoutedBackend:
    def test_message_round_trip(self):
        from fedml_tpu.comm.message import Message
        from fedml_tpu.comm.routed import RoutedCommManager

        with NativeRouter() as r:
            addr = ("127.0.0.1", r.port)
            m1 = RoutedCommManager(1, addr)
            m2 = RoutedCommManager(2, addr)
            got = []

            class Sink:
                def receive_message(self, msg_type, msg):
                    got.append((msg_type, msg))
                    m2.stop_receive_message()

            m2.add_observer(Sink())
            msg = Message(42, 1, 2)
            msg.add("weights", np.arange(1000, dtype=np.float32))
            m1.send_message(msg)
            m2.handle_receive_message()  # blocks until sink stops it
            assert got and got[0][0] == 42
            np.testing.assert_array_equal(
                got[0][1].get("weights"), np.arange(1000, dtype=np.float32))
            m1.stop_receive_message()

    def test_fedavg_federation_over_native_broker(self):
        """Full cross-silo FedAvg protocol with every rank dialing the C++
        broker — the reference's MQTT scenario, end to end."""
        import jax

        from fedml_tpu.algorithms.fedavg_cross_silo import \
            run_fedavg_cross_silo
        from fedml_tpu.data.synthetic import make_blob_federated
        from fedml_tpu.models.lr import LogisticRegression
        from fedml_tpu.trainer.functional import TrainConfig

        ds = make_blob_federated(client_num=3, dim=8, class_num=3,
                                 n_samples=120, seed=0)
        model = LogisticRegression(num_classes=3)
        with NativeRouter() as r:
            final, history = run_fedavg_cross_silo(
                ds, model, worker_num=3, comm_round=3,
                train_cfg=TrainConfig(epochs=1, batch_size=10, lr=0.5),
                backend="ROUTED",
                addresses={"router": ("127.0.0.1", r.port)})
            assert r.frames_routed > 0
        assert len(history) == 3
        assert history[-1]["test_acc"] >= history[0]["test_acc"] - 0.05
        jax.block_until_ready(final)
