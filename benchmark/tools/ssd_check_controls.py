#!/usr/bin/env python3
"""The ``timed`` check's controls for a cell whose model runs the chunked
state-space recurrence (``fedml_tpu/ops/ssd.py``), on the chip at the cell's
own size, and the sweep that chooses its learning rate.

    python3 benchmark/tools/ssd_check_controls.py --workload <cell> \
        --seed <n> [--manifest FILE] [--out FILE] [--lr-sweep RATE ...]

The controls: the cell's round 0 with a fault put in, through the harness's
own comparison (``harness/cell.py::compare_parameters`` under the
configuration's ``check.timed``) against the reference's round, as
``timed_check_controls.py`` runs its faults (a fault here is a patch of the
program, which that tool's options - other model arguments, a rounded fold -
do not reach). A sound round has to come out correct and every fault not:

* ``ssd_no_carried_state`` - every chunk of the recurrence from a zero state
  (the chunks treated as separate rows);
* ``residual_multiplier_1`` - the model built with ``residual_multiplier``
  1;
* ``bf16_fold`` - the folded round's running FedAvg sum rounded to bfloat16
  after every fold with ``lax.reduce_precision`` (the form the TPU compiler
  keeps: PERF.md section 7).

``--lr-sweep``: no control; for each rate, rounds 0-20 of a driver built like
the cell's at that rate, the round's mean local loss printed a round, and the
held-out loss after round 20. One JSON line a control or a rate on standard
output and in ``--out``; the exit code is 0 if the sound round is correct and
no fault is (a sweep: 0).
"""

import argparse
import functools
import gc
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--manifest", default=None)
    parser.add_argument("--out")
    parser.add_argument("--lr-sweep", type=float, nargs="+", default=[])
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    from benchmark.run import enable_compile_cache
    from fedml_tpu.models import create_model
    from fedml_tpu.ops import aggregate, ssd

    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.manifest or spec.MANIFEST)
    log = functools.partial(cell_mod._log, T_START)
    config, traffic = cell.config, cell.traffic
    model, train = config["model"], config["train"]
    task = model["task"]
    dataset, _ = cell.module("generators", config["data"]["generator"]).build(
        config["data"], cell.clients, args.seed)
    driver = cell.module("drivers", traffic["driver"])
    cohort = [int(c) for c in cell_mod.sample_cohort(
        0, cell.clients, int(traffic["cohort"]))]
    build_args = dict(train=train, cohort=len(cohort),
                      eval_every=int(traffic["eval_every"]),
                      rounds=int(traffic["round_bound"]), seed=args.seed,
                      devices=jax.devices()[:cell.chips])

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    def module_with(**kwargs):
        return create_model(model["create_model"],
                            output_dim=int(model["output_dim"]),
                            **{**model.get("kwargs", {}), **kwargs})

    def release(api):
        api.release_prefetch()
        del api
        gc.collect()  # the driver is in reference cycles, and holds a model
        jax.clear_caches()

    if args.lr_sweep:
        for lr in args.lr_sweep:
            api = driver.build(dataset, module_with(), task, **{
                **build_args, "train": {**train, "lr": lr}})
            losses = []
            for r in range(21):
                _, stats = api.run_round(r)
                losses.append(float(stats["loss_sum"])
                              / max(1.0, float(stats["count"])))
                log(f"lr {lr}: round {r} local loss {losses[-1]:.4f}")
            held_out = driver.evaluate(api, 20)["test_loss"]
            release(api)
            emit({"lr": lr, "workload": cell.name, "seed": args.seed,
                  "local_loss": losses, "held_out_loss_after_21": held_out})
        return 0

    def round0(module, init=None):
        """Round 0 of a driver built like the cell's, from ``init`` or from
        the driver's own initial parameters: host copies ``(initial, after
        the round)``."""
        api = driver.build(dataset, module, task, **build_args)
        if init is not None:
            api.variables = jax.tree.map(jnp.asarray, init)
        start = jax.device_get(api.variables)
        trained, _ = api.run_round(0)
        assert sorted(int(c) for c in trained) == sorted(cohort)
        got = jax.device_get(api.variables)
        release(api)
        return start, got

    sound = module_with()
    init, got = round0(sound)
    reference = cell.module("references", config["reference"])
    want = jax.device_get(reference.run_round(
        sound, task, train, init, dataset, seed=args.seed, round_idx=0,
        clients=cohort, aggregate=True)["variables"])
    change = cell_mod.tree_rel_err(init, want)
    lines = []

    def report(name, got, expect_correct):
        failures = cell_mod.compare_parameters(
            "timed", init, got, want, config["check"]["timed"], log)
        err = cell_mod.tree_rel_err(want, got)
        lines.append({
            "control": name, "workload": cell.name, "seed": args.seed,
            "correct": not failures, "expected_correct": expect_correct,
            "param_err": err, "change": change,
            "fraction_of_change": err / change,
            "param_fraction": config["check"]["timed"]["param_fraction"],
            "failures": failures})
        emit(lines[-1])

    report("sound", got, True)

    chunk, fold = ssd._chunk, aggregate.tree_fold_pallas
    ssd._chunk = lambda a, state, x: chunk(a, jnp.zeros_like(state), x)
    try:
        report("ssd_no_carried_state", round0(sound, init)[1], False)
    finally:
        ssd._chunk = chunk
    report("residual_multiplier_1",
           round0(module_with(residual_multiplier=1.0), init)[1], False)
    # bfloat16's 8 exponent and 7 mantissa bits; the compiler drops a
    # conversion there and back (PR 33)
    aggregate.tree_fold_pallas = lambda *a, **kw: jax.tree.map(
        lambda leaf: jax.lax.reduce_precision(leaf, 8, 7), fold(*a, **kw))
    try:
        report("bf16_fold", round0(sound, init)[1], False)
    finally:
        aggregate.tree_fold_pallas = fold
    return 0 if all(line["correct"] == line["expected_correct"]
                    for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
