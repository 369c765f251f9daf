"""Client-virtualization stress at the reference's largest federation.

The reference's biggest data point is StackOverflow NWP: 342,477 resident
clients with 50 sampled per round (stackoverflow_nwp/data_loader.py,
benchmark/README.md:57). What this stresses is not FLOPs but the
*virtualization machinery*: seeded cohort sampling over ~342k clients,
per-cohort gather/pack at a padded bucket, dispatch, and memory residency
of a multi-GB federation across rounds.

This runner drives raw rounds through the sim (vmapped) and optionally
mesh drivers, BLOCKING after each round so every record carries an honest
per-round wall-clock, plus RSS and the pack/dispatch phase means — the
stability-over-rounds evidence ``runs/stackoverflow_nwp_stress/`` holds.

Usage::

    python -m fedml_tpu.experiments.virtualization_stress \
        --dataset stackoverflow_nwp_gen --rounds 8 \
        --out runs/stackoverflow_nwp_stress
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import time


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main(argv=None):
    p = argparse.ArgumentParser("fedml_tpu virtualization_stress")
    p.add_argument("--dataset", default="stackoverflow_nwp_gen")
    p.add_argument("--clients", type=int, default=None,
                   help="default: the full registry scale (342,477)")
    p.add_argument("--rounds", type=int, default=8)
    p.add_argument("--client_num_per_round", type=int, default=50)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--lr", type=float, default=0.3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--drivers", type=str, default="sim")
    p.add_argument("--eval_subsample", type=int, default=1000,
                   help="one final eval over a seeded subsample (0 = skip)")
    p.add_argument("--out", type=str, required=True)
    args = p.parse_args(argv)

    from fedml_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    import jax

    from fedml_tpu.data.registry import DEFAULT_MODEL_AND_TASK, load_data
    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.functional import TrainConfig

    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    ds = load_data(args.dataset, "",
                   client_num_in_total=args.clients)
    model_name, task = DEFAULT_MODEL_AND_TASK[args.dataset]
    load_s = round(time.time() - t0, 1)
    tcfg = TrainConfig(epochs=1, batch_size=args.batch_size, lr=args.lr)
    summary = {
        "dataset": args.dataset,
        "clients": ds.client_num,
        "train_samples": ds.train_data_num,
        "model": model_name,
        "client_num_per_round": args.client_num_per_round,
        "batch_size": args.batch_size,
        "corpus_load_s": load_s,
        "rss_after_load_mb": round(_rss_mb(), 1),
        "host": jax.devices()[0].device_kind,
    }

    for kind in args.drivers.split(","):
        model = create_model(model_name, output_dim=ds.class_num)
        if kind == "sim":
            from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
            api = FedAvgAPI(ds, model, task=task, config=FedAvgConfig(
                comm_round=args.rounds,
                client_num_per_round=args.client_num_per_round,
                frequency_of_the_test=10**9, seed=args.seed,
                eval_train_subsample=args.eval_subsample or 1,
                eval_test_subsample=args.eval_subsample or 1,
                train=tcfg))
        else:
            from fedml_tpu.parallel.spmd import (DistributedFedAvgAPI,
                                                 DistributedFedAvgConfig)
            api = DistributedFedAvgAPI(
                ds, model, task=task, config=DistributedFedAvgConfig(
                    comm_round=args.rounds,
                    client_num_per_round=args.client_num_per_round,
                    frequency_of_the_test=10**9, seed=args.seed,
                    eval_test_subsample=args.eval_subsample or 1,
                    train=tcfg))
        hist_path = os.path.join(args.out, f"{kind}_rounds.jsonl")
        recs = []
        with open(hist_path, "w") as f:
            for r in range(args.rounds):
                t1 = time.time()
                api.run_round(r)
                jax.block_until_ready(api.variables)
                rec = {"round": r,
                       "wall_s": round(time.time() - t1, 3),
                       "rss_mb": round(_rss_mb(), 1),
                       "phase_ms": {k: round(v * 1e3, 3)
                                    for k, v in api.timer.means().items()}}
                recs.append(rec)
                f.write(json.dumps(rec) + "\n")
                f.flush()
                print(f"[{kind}] {rec}", flush=True)
        steady = recs[1:] or recs  # round 0 pays the compile
        walls = [r["wall_s"] for r in steady]
        stats = {
            "rounds": args.rounds,
            "rounds_per_sec_steady": round(
                len(walls) / max(1e-9, sum(walls)), 4),
            "wall_s_min": min(walls), "wall_s_max": max(walls),
            "rss_mb_round1": steady[0]["rss_mb"],
            "rss_mb_final": recs[-1]["rss_mb"],
            "rss_growth_mb": round(recs[-1]["rss_mb"]
                                   - steady[0]["rss_mb"], 1),
        }
        if args.eval_subsample:
            t1 = time.time()
            if kind == "sim":
                ev = api.evaluate(args.rounds - 1)
            else:
                from fedml_tpu.algorithms.fedavg import _normalized
                raw = api._eval_global()
                ev = _normalized(raw, "test") if raw is not None else {}
            stats["final_eval"] = {k: float(v) for k, v in ev.items()
                                   if isinstance(v, (int, float))}
            stats["eval_wall_s"] = round(time.time() - t1, 2)
        summary[kind] = stats
        print(f"[{kind}] {stats}", flush=True)

    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps({k: v for k, v in summary.items()
                      if not isinstance(v, dict)}), flush=True)
    return summary


if __name__ == "__main__":
    main()
