"""CLI: ``python -m fedml_tpu.serve --smoke`` — the serving smoke.

The ci/run_fast.sh front for the serving tier (~10 s on a CPU host):
launch a small cross-silo federation WITH a serving endpoint attached,
hammer the endpoint with closed-loop traffic while (and after) training
runs, then assert the zero->aha contract:

- at least one hot swap landed (the endpoint is serving a trained
  round, not an init artifact);
- ZERO requests were shed (the coalescer kept up with the smoke load);
- the SLO report is populated (latency quantiles measured, served
  round/staleness tracked).

Prints the SLO report as one JSON object on stdout; exit 0 iff every
assertion holds. ``--requests`` / ``--rounds`` / ``--workers`` scale
the smoke; defaults match the CI budget.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import tempfile
import threading
import time


def _build_fixture(workers: int):
    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.trainer.functional import TrainConfig
    ds = make_blob_federated(client_num=workers, dim=8, class_num=3,
                             n_samples=24 * workers, seed=5)
    return ds, LogisticRegression(num_classes=3), TrainConfig(
        epochs=1, batch_size=8, lr=0.1)


def run_smoke(rounds: int = 4, workers: int = 3, requests: int = 50,
              root: str = "") -> int:
    import os

    from fedml_tpu.algorithms.fedavg_cross_silo import run_fedavg_cross_silo
    from fedml_tpu.serve import build_serving, drive_traffic
    from fedml_tpu.utils.tracing import RoundTimer

    own_root = not root
    root = root or tempfile.mkdtemp(prefix="fedml_serve_smoke_")
    os.makedirs(root, exist_ok=True)
    ds, module, tcfg = _build_fixture(workers)
    timer = RoundTimer()
    tier = build_serving(module, "classification",
                         ds.train_data_global[0][:1], max_batch=8,
                         timer=timer, port=0,
                         checkpoint_dir=os.path.join(root, "ctrl"))
    ok = True
    try:
        trainer = threading.Thread(
            target=lambda: run_fedavg_cross_silo(
                ds, module, worker_num=workers, comm_round=rounds,
                train_cfg=tcfg, seed=3,
                server_checkpoint_dir=os.path.join(root, "ctrl"),
                serving=tier),
            daemon=True, name="serve-smoke-trainer")
        t0 = time.time()
        trainer.start()
        # first swap = the INIT broadcast's publish; traffic only makes
        # sense once something serves
        while tier.rollout.served_round < 0 and time.time() - t0 < 120:  # ft: allow[FT015] smoke startup budget — a wall-clock cap on waiting for the first swap
            time.sleep(0.02)
        if tier.rollout.served_round < 0:
            print(json.dumps({"error": "no model served within 120s"}))
            return 1
        traffic = drive_traffic(tier.port, ds.test_data_global[0][:8],
                                requests=requests, concurrency=4)
        trainer.join(timeout=300)
        tier.rollout.drain()
        report = tier.slo_report()
        out = {"traffic": traffic, "slo": report,
               "swaps": int(tier.endpoint.swaps),
               "gauges": {k: round(float(v), 3)
                          for k, v in timer.gauges.items()},
               "wall_s": round(time.time() - t0, 2)}
        problems = []
        if tier.endpoint.swaps < 1:
            problems.append("no hot swap landed")
        if traffic["shed"] or report.get("shed"):
            problems.append(f"load shed during the smoke "
                            f"(traffic={traffic['shed']}, "
                            f"tier={report.get('shed')})")
        if traffic["ok"] != requests:
            problems.append(f"only {traffic['ok']}/{requests} requests "
                            "answered ok")
        if report.get("latency_p50_ms") is None:
            problems.append("SLO report has no latency quantiles")
        if report.get("served_round", -1) < 0:
            problems.append("SLO report has no served round")
        out["problems"] = problems
        ok = not problems
        print(json.dumps(out, indent=2))
        return 0 if ok else 1
    finally:
        tier.close()
        if own_root:
            import shutil
            shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    from fedml_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    logging.basicConfig(level=logging.WARNING)
    parser = argparse.ArgumentParser(
        "python -m fedml_tpu.serve",
        description="federated serving smoke (see module docstring)")
    parser.add_argument("--smoke", action="store_true",
                        help="run the serving smoke: train + serve + "
                             "traffic, assert zero sheds and a "
                             "populated SLO report")
    parser.add_argument("--rounds", type=int, default=4,
                        help="training rounds for the smoke federation")
    parser.add_argument("--workers", type=int, default=3,
                        help="silos in the smoke federation")
    parser.add_argument("--requests", type=int, default=50,
                        help="synthetic requests to drive")
    parser.add_argument("--root", type=str, default="",
                        help="work dir (default: a fresh temp dir, "
                             "removed afterwards)")
    args = parser.parse_args(argv)
    if not args.smoke:
        parser.error("nothing to do: pass --smoke")
    return run_smoke(rounds=args.rounds, workers=args.workers,
                     requests=args.requests, root=args.root)


if __name__ == "__main__":
    sys.exit(main())
