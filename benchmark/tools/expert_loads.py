#!/usr/bin/env python3
"""The held experts' loads of a cell whose model routes (``RoutedTiedHead``):
layer by layer with the global model that enters round 0 and the one that
enters round ``--rounds``, and the load peak of every round between from the
round's own stat totals.

    python3 benchmark/tools/expert_loads.py --workload <cell> --seed <n> \
        [--manifest FILE] [--rounds 16] [--out FILE]

The driver is built as the harness builds it (on the chip at the cell's size,
or here with ``--manifest`` at a tiny one). An ``entering_round`` line counts,
over the first training row of each silo of that round's cohort, the (token,
choice) pairs on every held expert of every sparse layer; a ``round`` line is
``experts held x moe_top_expert_assignments / moe_assignments`` of that round
(what ``expert_load_peak`` / ``small_expert_load_peak`` sum over a window).
One JSON line each on standard output and in ``--out``. PR 39 read with it
that its cell's loads are uneven from round 0 on (the configuration's
``measured.loads``).
"""
import argparse
import json
import os
import sys
import time

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    from benchmark.run import enable_compile_cache
    from fedml_tpu.models import create_model

    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.manifest or spec.MANIFEST)
    config, traffic = cell.config, cell.traffic
    model, train, data = config["model"], config["train"], config["data"]
    dataset, _ = cell.module("generators", data["generator"]).build(
        data, cell.clients, args.seed)
    module = create_model(model["create_model"],
                          output_dim=int(model["output_dim"]),
                          **model.get("kwargs", {}))
    held = int(model["kwargs"]["experts_held"][1])
    top_k = int(model["kwargs"]["num_experts_per_tok"])
    n_experts = int(model["kwargs"].get("n_routed_experts")
                    or model["kwargs"]["num_experts"])
    driver = cell.module("drivers", traffic["driver"])
    cohort_n = int(traffic["cohort"])
    api = driver.build(dataset, module, model["task"], train=train,
                       cohort=cohort_n, eval_every=int(traffic["eval_every"]),
                       rounds=int(traffic["round_bound"]), seed=args.seed,
                       devices=jax.devices()[:cell.chips])
    print(f"[loads +{time.time() - T0:6.1f}s] driver built", flush=True)

    forward = jax.jit(lambda v, t: module.apply(v, t).expert_load)
    lines = []

    def emit(line):
        lines.append(line)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    def layer_loads(round_idx):
        """Held loads a sparse layer of the model that enters ``round_idx``,
        over the first training row of each silo of that round's cohort."""
        cohort = [int(c) for c in cell_mod.sample_cohort(
            round_idx, cell.clients, cohort_n)]
        total = 0
        for c in cohort:
            row = np.asarray(dataset.train_data_local_dict[c][0][:1])
            total = total + np.asarray(jax.device_get(forward(
                api.variables, row)))[0]
        tokens = len(cohort) * row.shape[1]
        emit({"entering_round": round_idx, "cohort": cohort,
              "tokens": tokens, "pairs": tokens * top_k,
              "even_load_a_held_expert": tokens * top_k / n_experts,
              "held_loads_by_sparse_layer": total.astype(int).tolist(),
              "held_share_of_pairs": [
                  float(x) / (tokens * top_k) for x in total.sum(-1)],
              "peak_over_mean_by_layer": [
                  float(held * row.max() / max(row.sum(), 1.0))
                  for row in total]})

    layer_loads(0)
    for r in range(args.rounds):
        _, stats = api.run_round(r)
        stats = {k: float(v) for k, v in jax.device_get(stats).items()}
        emit({"round": r, "moe_assignments": stats["moe_assignments"],
              "moe_top_expert_assignments":
                  stats["moe_top_expert_assignments"],
              "load_peak": held * stats["moe_top_expert_assignments"]
              / max(stats["moe_assignments"], 1.0),
              "train_loss_local": stats["loss_sum"] / max(stats["count"], 1),
              "t": round(time.time() - T0, 1)})
    layer_loads(args.rounds)
    api.release_prefetch()
    return 0


if __name__ == "__main__":
    sys.exit(main())
