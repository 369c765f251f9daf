"""CI fan-out smoke: the round hot path against a genuinely slow peer.

Two stages, both over REAL TCP sockets on localhost, exit non-zero on
any violated contract:

1. **Transport backpressure** — a 4-peer broadcast of one multi-MB
   shared payload where one peer accepts its connection but does not
   read for ``STALL_S`` seconds (kernel socket buffers fill; a
   sequential fan-out would sit in ``sendall`` for the whole stall).
   Required: the ``broadcast()`` call returns in a fraction of the
   stall, every FAST peer holds its complete frame while the slow peer
   is still stalled, the slow peer's frame fully drains only after the
   stall, the payload was encoded exactly ONCE, and all four frames
   decode to bit-identical payloads.

2. **Federation ledger parity** — a 4-silo federation (deadline rounds,
   so the server takes the parallel fan-out path) where the chaos
   harness (comm/faults.py) delays every model-broadcast delivery at
   one silo by ``DELAY_MS``. Required: the full schedule completes (the
   slow silo is never evicted), the server's round-open fan-out gauge
   stays far under the injected delay, the per-round reported sets
   match a fault-free reference run, and the final model is
   BIT-identical to the reference (the sorted-index fold makes arrival
   timing irrelevant).

Run: ``python -m fedml_tpu.comm.fanout_smoke [--port_base N]``
"""

from __future__ import annotations

import argparse
import socket
import struct
import sys
import threading
import time

import numpy as np

_HOST = "127.0.0.1"
STALL_S = 2.0        # stage 1: how long the slow peer refuses to read
PAYLOAD_MB = 8       # stage 1: big enough to overflow loopback buffers
DELAY_MS = 1200.0    # stage 2: chaos recv-delay at the slow silo


def _fail(msg: str) -> None:
    print(f"FANOUT SMOKE FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


# -- stage 1: transport backpressure ----------------------------------------
class _RawPeer:
    """A minimal frame sink: accepts one connection and records when its
    first frame finished arriving. ``stall_s`` delays the FIRST read —
    with the socket unread, the sender's TCP window closes and a
    blocking fan-out would wedge on this peer."""

    def __init__(self, port: int, stall_s: float = 0.0):
        self.stall_s = stall_s
        self.frames = []
        self.done_t: float | None = None
        self._server = socket.create_server((_HOST, port))
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        from fedml_tpu.comm.tcp import recv_frame
        try:
            conn, _ = self._server.accept()
        except OSError:
            # close() tore down the listener before any connect
            # arrived — nothing to sink
            return
        try:
            if self.stall_s:
                time.sleep(self.stall_s)
            self.frames.append(recv_frame(conn))
            self.done_t = time.monotonic()
        # ft: allow[FT007] smoke fixture teardown: a torn socket just leaves done_t unset and the main thread fails the stage on that
        except OSError:
            pass
        finally:
            conn.close()
            self._server.close()

    def close(self) -> None:
        """Idempotent: releases the listener (unblocking a
        never-connected ``accept()``) so the port can be rebound by the
        next stage immediately instead of leaking for the process
        lifetime."""
        try:
            # on Linux a close() alone does not wake an accept() already
            # blocked in the serve thread: the thread, and with it the
            # bound port, would outlive this call
            self._server.shutdown(socket.SHUT_RDWR)
        # ft: allow[FT007] second close(): the listener is already gone
        except OSError:
            pass
        self._server.close()
        self._thread.join(timeout=1.0)


def stage_transport(port_base: int) -> None:
    from fedml_tpu.comm.tcp import TcpCommManager

    n_peers = 4
    slow_rank = n_peers  # the last peer stalls
    addresses = {r: (_HOST, port_base + r) for r in range(n_peers + 1)}
    peers = {r: _RawPeer(port_base + r,
                         stall_s=STALL_S if r == slow_rank else 0.0)
             for r in range(1, n_peers + 1)}
    try:
        com = TcpCommManager(0, addresses)
        try:
            _stage_transport_run(com, peers, n_peers, slow_rank)
        finally:
            # a _fail() mid-stage must not strand the bound listener:
            # stage 2 rebinds the same port range in this process
            com.stop_receive_message()
    finally:
        for peer in peers.values():
            peer.close()


def _stage_transport_run(com, peers, n_peers: int, slow_rank: int) -> None:
    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.serialization import SharedPayload

    rng = np.random.default_rng(0)
    tree = {"w": rng.standard_normal(
        (PAYLOAD_MB * (1 << 20) // 4,)).astype(np.float32)}
    shared = SharedPayload(tree)
    msgs = []
    for r in range(1, n_peers + 1):
        msg = Message(2, 0, r)
        msg.add("model_params", shared)
        msg.add("round_idx", 0)
        msgs.append(msg)

    errors = []
    t0 = time.monotonic()
    stats = com.broadcast(msgs, on_error=lambda r, e: errors.append((r, e)))
    bcast_wall = time.monotonic() - t0

    # fast peers must finish while the slow peer is still stalled
    deadline = t0 + STALL_S * 0.75
    for r in range(1, n_peers):
        # ft: allow[FT015] smoke timing probe: the stall window IS the experiment — no schedule or RNG state derives from this wait
        while peers[r].done_t is None and time.monotonic() < deadline:
            time.sleep(0.005)
    # slow peer drains only after the stall
    slow_deadline = t0 + STALL_S + 30.0
    # ft: allow[FT015] liveness deadline on the stalled peer's drain — real time is the only signal kernel backpressure releases on
    while time.monotonic() < slow_deadline \
            and peers[slow_rank].done_t is None:
        time.sleep(0.01)

    if errors:
        _fail(f"stage 1: broadcast surfaced errors: {errors}")
    if stats["enqueued"] != n_peers:
        _fail(f"stage 1: enqueued {stats['enqueued']} != {n_peers}")
    # ft: allow[FT015] the smoke's whole contract is this wall-clock bound: broadcast() must return in a fraction of the injected stall
    if bcast_wall >= STALL_S / 4:
        _fail(f"stage 1: broadcast() took {bcast_wall:.3f}s — blocked on "
              f"the stalled peer (stall {STALL_S}s)")
    fast_done = [peers[r].done_t for r in range(1, n_peers)]
    if any(t is None for t in fast_done):
        _fail("stage 1: a fast peer never received its frame while the "
              "slow peer stalled — fan-out is serialized")
    # ft: allow[FT015] wall-clock assertion again: fast peers must drain inside the stall window or the fan-out is serialized
    if max(t - t0 for t in fast_done) >= STALL_S * 0.75:
        _fail("stage 1: fast peers drained only near/after the stall — "
              "fan-out is serialized behind the slow peer")
    if peers[slow_rank].done_t is None:
        _fail("stage 1: slow peer never drained")
    slow_took = peers[slow_rank].done_t - t0
    if slow_took < STALL_S - 0.1:
        _fail(f"stage 1: slow peer drained in {slow_took:.3f}s — the "
              f"stall never produced backpressure; the stage proves "
              f"nothing")
    if shared.encode_count != 1:
        _fail(f"stage 1: payload encoded {shared.encode_count}x, want 1")
    from fedml_tpu.comm.message import Message as M
    for r, peer in peers.items():
        got = M.from_bytes(peer.frames[0]).get("model_params")
        if got["w"].dtype != tree["w"].dtype \
                or not np.array_equal(np.asarray(got["w"]), tree["w"]):
            _fail(f"stage 1: peer {r} frame decoded to a different "
                  "payload")
    print(f"stage 1 OK: broadcast {n_peers}x{PAYLOAD_MB}MB returned in "
          f"{bcast_wall * 1e3:.1f} ms; fast peers drained in "
          f"{max(t - t0 for t in fast_done):.2f}s; slow peer in "
          f"{slow_took:.2f}s (stall {STALL_S}s); one encode")


# -- stage 2: federation ledger parity under a chaos-delayed silo -----------
def _run_federation(port_base: int, fault_plan=None):
    from fedml_tpu.algorithms.fedavg_cross_silo import (
        run_fedavg_cross_silo)
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    from fedml_tpu.utils.tracing import RoundTimer

    worker_num = 4
    ds = make_blob_federated(client_num=worker_num, dim=8, class_num=3,
                             n_samples=128, seed=11)
    addresses = {r: (_HOST, port_base + r)
                 for r in range(worker_num + 1)}
    timer = RoundTimer()
    ledger = []
    model, history = run_fedavg_cross_silo(
        ds, LogisticRegression(num_classes=3), worker_num=worker_num,
        comm_round=3, train_cfg=TrainConfig(epochs=1, batch_size=8,
                                            lr=0.1),
        backend="TCP", addresses=addresses, timer=timer,
        round_record_hook=ledger.append, fault_plan=fault_plan,
        round_deadline_s=30.0, min_quorum_frac=0.5)
    return model, history, ledger, timer


def stage_federation(port_base: int) -> None:
    import jax

    ref_model, ref_hist, ref_ledger, ref_timer = _run_federation(port_base)
    # every model broadcast to silo rank 4 is delivered DELAY_MS late
    plan = (f"seed=3;delay:p=1.0,delay_ms={DELAY_MS:.0f},msg_type=2,"
            f"receiver=4,direction=recv")
    model, hist, ledger, timer = _run_federation(port_base + 16,
                                                 fault_plan=plan)

    if len(hist) != len(ref_hist) or len(hist) != 3:
        _fail(f"stage 2: chaos run finished {len(hist)}/3 rounds")
    got_rep = [sorted(r.get("reported", [])) for r in ledger]
    ref_rep = [sorted(r.get("reported", [])) for r in ref_ledger]
    if got_rep != ref_rep:
        _fail(f"stage 2: reported-set ledger diverged: {got_rep} vs "
              f"{ref_rep} — the slow silo fell out of the round")
    fanout_ms = timer.gauges.get("bcast_fanout_ms")
    if fanout_ms is None:
        _fail("stage 2: no bcast_fanout_ms gauge — the fan-out path "
              "never ran")
    if fanout_ms >= DELAY_MS / 2:
        _fail(f"stage 2: round-open fan-out took {fanout_ms:.1f} ms "
              f"against a {DELAY_MS:.0f} ms slow peer — the round "
              f"thread waited out the straggler")
    if ref_timer.gauges.get("send_queue_depth", 0) < 1:
        _fail("stage 2: reference run never rode the per-peer send "
              "queues (send_queue_depth gauge empty)")
    faults = timer.counters.get("ft_faults_injected", 0)
    if faults < 2:
        _fail(f"stage 2: only {faults} faults injected — the chaos "
              "delay never fired; the parity claim is untested")
    la = jax.tree.leaves(jax.tree.map(np.asarray, ref_model))
    lb = jax.tree.leaves(jax.tree.map(np.asarray, model))
    if len(la) != len(lb) or not all(
            np.array_equal(a, b) for a, b in zip(la, lb)):
        _fail("stage 2: final model diverged from the fault-free "
              "reference — the fold is arrival-order sensitive")
    print(f"stage 2 OK: 3/3 rounds, ledger parity, bit-identical model; "
          f"round-open fan-out {fanout_ms:.1f} ms vs {DELAY_MS:.0f} ms "
          f"injected delay; {faults} faults injected")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--port_base", type=int, default=40610)
    args = ap.parse_args(argv)
    stage_transport(args.port_base)
    stage_federation(args.port_base + 32)
    print("FANOUT SMOKE PASS")


if __name__ == "__main__":
    main()
