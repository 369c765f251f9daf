"""WAN-churn CLI: the CI smoke and a trace inspector.

``python -m fedml_tpu.wan --smoke`` (~15 s, fronting ``ci/run_fast.sh``)
runs a small cross-silo federation over REAL TCP loopback endpoints
through a diurnal trough + flap burst and exits non-zero unless:

- the FULL schedule completes (churn degrades rounds, never stalls them);
- at least one silo was deadline-EVICTED and at least one REJOINED
  through the trace-gated JOIN path;
- every sampled cohort member was available in the trace at its round's
  sim time, with zero forced (fallback) cohorts;
- re-running the identical trace seed produces a **bit-identical
  round/cohort ledger** — the replay determinism the whole layer is
  built around.

``python -m fedml_tpu.wan curve --trace SPEC`` prints the availability
curve + per-round silo online matrix for a spec, which is how smoke and
test fixtures are designed (the world is a pure function — what this
prints is exactly what a run experiences).
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from typing import Dict, Optional

import numpy as np

#: smoke fixture constants — the scenario is deterministic by
#: construction; these were chosen with `python -m fedml_tpu.wan curve`
#: so the trough + flap evict some (never all) of the fleet
SMOKE_WORKERS = 4
SMOKE_ROUNDS = 8
SMOKE_POPULATION = 24
SMOKE_ROUND_S = 60.0
SMOKE_TRACE = ("seed=20;period_s=960;phase0_s=480;peak=0.98;trough=0.45;"
               "duty_jitter=0.05;slot_s=120;flap=60:120:0.5")
SMOKE_PROFILES = ("seed=5;compute_median_s=0.12;compute_sigma=0.5;"
                  "delay_cap_s=1.0")
SMOKE_DEADLINE_S = 2.0


def build_fixture(population: int = SMOKE_POPULATION):
    """Deterministic federation fixture: a blob population LARGER than
    the silo fleet, so cohort sampling has a real candidate pool to
    restrict by availability."""
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    ds = make_blob_federated(client_num=population, dim=8, class_num=3,
                             n_samples=population * 20, seed=3,
                             noise=5.0, partition_method="homo")
    return ds, LogisticRegression(num_classes=3), TrainConfig(
        epochs=1, batch_size=8, lr=0.08)


def smoke_world():
    from fedml_tpu.wan import WanWorld, parse_wan_profiles, parse_wan_trace
    return WanWorld(trace=parse_wan_trace(SMOKE_TRACE),
                    profiles=parse_wan_profiles(SMOKE_PROFILES),
                    round_s=SMOKE_ROUND_S, delay_wall_cap_s=0.8,
                    # shadow admission bucket (sim clock): the
                    # population JOIN wave is measured against a real
                    # rate — wan_mass_join_throttled in the roll-up
                    mass_join_rate=0.05)


def run_churn_leg(ckpt_dir: str, *, rounds: int = SMOKE_ROUNDS,
                  workers: int = SMOKE_WORKERS,
                  world=None, backend: str = "TCP",
                  port_base: Optional[int] = 40310,
                  pace_steering: bool = False,
                  deadline_s: float = SMOKE_DEADLINE_S,
                  min_quorum_frac: float = 0.25,
                  obs_dir: Optional[str] = None,
                  compression=None,
                  fault_plan=None,
                  join_timeout_s: float = 300.0) -> Dict:
    """One full federation under the world model. Returns the counters,
    ledger, and history the smoke (and the ``wan_churn`` bench) judge."""
    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.control import ServerControlCheckpointer
    from fedml_tpu.control.failover_harness import make_addresses
    from fedml_tpu.utils.tracing import RoundTimer

    ds, module, tcfg = build_fixture()
    timer = RoundTimer()
    addresses = (make_addresses(port_base, workers + 1)
                 if backend.upper() == "TCP" else None)
    t0 = time.perf_counter()
    round_walls: Dict[int, float] = {}

    def record(rec):
        # per-round wall offsets for the time-to-target figures
        round_walls[int(rec["round"])] = round(
            time.perf_counter() - t0, 4)

    _, history = run_fedavg_cross_silo(
        ds, module, worker_num=workers, comm_round=rounds,
        train_cfg=tcfg, backend=backend, addresses=addresses,
        round_deadline_s=deadline_s, min_quorum_frac=min_quorum_frac,
        heartbeat_s=0.2, server_checkpoint_dir=ckpt_dir,
        pace_steering=pace_steering, timer=timer, wan=world,
        obs_dir=obs_dir, compression=compression, fault_plan=fault_plan,
        round_record_hook=record, join_timeout_s=join_timeout_s)
    wall = time.perf_counter() - t0
    ledger = ServerControlCheckpointer(ckpt_dir).read_ledger()
    return {
        "history": history,
        "ledger": ledger,
        "wall_s": round(wall, 3),
        "rounds_per_sec": round(rounds / max(wall, 1e-9), 3),
        "round_walls": round_walls,
        "counters": {k: int(v) for k, v in timer.counters.items()},
        "gauges": {k: round(float(v), 6)
                   for k, v in timer.gauges.items()},
        "world": world,
    }


def cohorts_all_available(ledger, world) -> bool:
    """Replay oracle: every ledger cohort member must be available in
    the trace at its round's sim time (the sampling-restriction check —
    a pure recomputation from the seed)."""
    for row in ledger:
        cohort = np.asarray(row.get("cohort") or [], dtype=np.int64)
        if len(cohort) and not world.trace.available(
                cohort, world.t_of_round(int(row["round"]))).all():
            return False
    return True


def _ledger_key(ledger) -> str:
    return json.dumps(ledger, sort_keys=True)


def smoke(root: Optional[str]) -> int:
    import os
    import tempfile
    root = root or tempfile.mkdtemp(prefix="fedml_wan_smoke_")
    t0 = time.time()
    a = run_churn_leg(os.path.join(root, "leg_a"), port_base=40310,
                      world=smoke_world())
    b = run_churn_leg(os.path.join(root, "leg_b"), port_base=40330,
                      world=smoke_world())
    ca = a["counters"]
    replay_identical = _ledger_key(a["ledger"]) == _ledger_key(b["ledger"])
    checks = {
        "full_schedule": len(a["history"]) == SMOKE_ROUNDS
        and len(a["ledger"]) == SMOKE_ROUNDS,
        "evictions": ca.get("ft_evictions", 0) >= 1,
        "rejoins": ca.get("ft_rejoins", 0) >= 1,
        "partial_rounds": ca.get("ft_partial_rounds", 0) >= 1,
        "cohorts_trace_available": cohorts_all_available(a["ledger"],
                                                         a["world"]),
        "no_forced_cohorts": ca.get("wan_forced_cohorts", 0) == 0,
        "ledger_replay_identical": replay_identical,
    }
    ok = all(checks.values())
    print(json.dumps({
        "wan_churn_smoke": "ok" if ok else "FAILED",
        "elapsed_s": round(time.time() - t0, 1),
        "checks": checks,
        "evictions": ca.get("ft_evictions", 0),
        "rejoins": ca.get("ft_rejoins", 0),
        "partial_rounds": ca.get("ft_partial_rounds", 0),
        "offline_drops": ca.get("wan_offline_drops", 0),
        "delay_injected_ms": ca.get("wan_delay_injected_ms", 0),
        "cohort_rejections": ca.get("wan_cohort_rejections", 0),
        "rounds_per_sec": a["rounds_per_sec"],
    }))
    return 0 if ok else 1


def curve(trace_spec: str, rounds: int, round_s: float, workers: int,
          population: int) -> int:
    """Print the pure-function view of a trace: per-round availability
    fraction and the silo online matrix — the fixture-design tool."""
    from fedml_tpu.wan import WanWorld, parse_wan_trace
    world = WanWorld(trace=parse_wan_trace(trace_spec), round_s=round_s,
                     population=population)
    rows = []
    for r in range(rounds):
        silos = "".join(
            "#" if world.silo_online(rank, r) else "."
            for rank in range(1, workers + 1))
        frac = world.available_frac(r)
        joins, leaves, _ = world.mass_churn(r)
        rows.append({"round": r, "available_frac": round(frac, 3),
                     "silos": silos, "joins": joins, "leaves": leaves})
        print(f"r{r:3d}  frac={frac:5.3f}  silos[{silos}]  "
              f"+{joins} -{leaves}")
    print(json.dumps({"rows": rows}, indent=None))
    return 0


def main(argv=None) -> int:
    logging.basicConfig(level=logging.WARNING)
    p = argparse.ArgumentParser("python -m fedml_tpu.wan")
    p.add_argument("mode", nargs="?", choices=["smoke", "curve"],
                   default="smoke")
    p.add_argument("--smoke", action="store_true",
                   help="run the WAN churn CI smoke (diurnal trough + "
                        "flap burst over TCP; exits non-zero unless the "
                        "schedule completed with churn AND the ledger "
                        "replays bit-identically)")
    p.add_argument("--root", type=str, default=None,
                   help="smoke working directory (default: a tmpdir)")
    p.add_argument("--trace", type=str, default=SMOKE_TRACE,
                   help="curve mode: the --wan_trace spec to inspect")
    p.add_argument("--rounds", type=int, default=16)
    p.add_argument("--round_s", type=float, default=SMOKE_ROUND_S)
    p.add_argument("--workers", type=int, default=SMOKE_WORKERS)
    p.add_argument("--population", type=int, default=SMOKE_POPULATION)
    args = p.parse_args(argv)
    if args.mode == "curve":
        return curve(args.trace, args.rounds, args.round_s, args.workers,
                     args.population)
    return smoke(args.root)


if __name__ == "__main__":
    sys.exit(main())
