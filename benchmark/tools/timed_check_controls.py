#!/usr/bin/env python3
"""The ``timed`` check's controls, on the chip at the cell's own size: the
cell's round 0 with a fault put in, through the harness's own comparison
(``harness/cell.py::compare_parameters`` under the configuration's
``check.timed``) against the reference's round. A sound round has to come out
correct and every fault not; the readings are what a configuration's
``check.why`` quotes beside its limits.

    python3 benchmark/tools/timed_check_controls.py --workload <cell> \
        --seed <n> [--manifest FILE] [--out FILE] [--bf16-fold] \
        [--kwargs <name>='{"model kwarg": value, ...}' ...]

One process: the federation, the reference's round (once, it is the slow
part), the sound round, then one round a fault, each on a driver built like
the cell's from the same initial parameters. ``--kwargs`` builds the model
with other arguments (a routing of one expert fewer, a share that lacks a
held expert). Where the faulty model holds less of a leaf than the sound one,
the part it lacks counts as left at its initial value - what a program that
skipped it would hand back, and the only form of that fault the harness can
meet, since it refuses a model of another size - and the line also gives the
reading over the part both hold (``fraction_over_shared``). ``--bf16-fold``
rounds the folded round's running sum to bfloat16 after every fold. One JSON
line a control on standard output and in ``--out``; the exit code is 0 if the
sound round is correct and no fault is.
"""

import argparse
import gc
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _held_by_both(tree, like):
    """``tree`` cut, leaf by leaf, to the shapes of ``like``."""
    import jax

    return jax.tree.map(
        lambda a, b: a[tuple(slice(0, n) for n in b.shape)], tree, like)


def _left_as_initialised(init, got):
    """``init`` with ``got`` written over the part ``got`` holds."""
    import jax
    import numpy as np

    def fill(a, b):
        out = np.array(a)
        out[tuple(slice(0, n) for n in b.shape)] = b
        return out

    return jax.tree.map(fill, init, got)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--manifest", default=None)
    parser.add_argument("--out")
    parser.add_argument("--bf16-fold", action="store_true")
    parser.add_argument("--kwargs", action="append", default=[],
                        metavar="NAME=JSON")
    args = parser.parse_args(argv)

    import functools

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    from benchmark.run import enable_compile_cache
    from fedml_tpu.models import create_model
    from fedml_tpu.ops import aggregate

    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.manifest or spec.MANIFEST)
    log = functools.partial(cell_mod._log, T_START)
    config, traffic = cell.config, cell.traffic
    model, train, task = config["model"], config["train"], \
        config["model"]["task"]
    dataset, _ = cell.module("generators", config["data"]["generator"]).build(
        config["data"], cell.clients, args.seed)
    driver = cell.module("drivers", traffic["driver"])
    cohort = [int(c) for c in cell_mod.sample_cohort(
        0, cell.clients, int(traffic["cohort"]))]
    build_args = dict(train=train, cohort=len(cohort),
                      eval_every=int(traffic["eval_every"]),
                      rounds=int(traffic["round_bound"]), seed=args.seed,
                      devices=jax.devices()[:cell.chips])

    def module_with(kwargs):
        return create_model(model["create_model"],
                            output_dim=int(model["output_dim"]),
                            **{**model.get("kwargs", {}), **kwargs})

    def round0(module, init=None):
        """Round 0 of a driver built like the cell's; from ``init`` cut to
        what ``module`` holds, or from the driver's own initial parameters.
        Returns host copies ``(initial, after the round)``."""
        api = driver.build(dataset, module, task, **build_args)
        if init is not None:
            api.variables = jax.tree.map(
                jnp.asarray, _held_by_both(init, api.variables))
        start = jax.device_get(api.variables)
        trained, _ = api.run_round(0)
        assert sorted(int(c) for c in trained) == sorted(cohort)
        got = jax.device_get(api.variables)
        api.release_prefetch()
        del api
        gc.collect()  # the driver is in reference cycles, and holds a model
        jax.clear_caches()
        log(f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB of "
            "arrays live after the round")
        return start, got

    lines = []

    def report(name, got, expect_correct, **more):
        """``got`` against the reference's round from ``init``."""
        failures = cell_mod.compare_parameters(
            "timed", init, got, want, config["check"]["timed"], log)
        err = cell_mod.tree_rel_err(want, got)
        line = {"control": name, "workload": cell.name, "seed": args.seed,
                "correct": not failures, "expected_correct": expect_correct,
                "param_err": err, "change": change,
                "fraction_of_change": err / change,
                "param_fraction": config["check"]["timed"]["param_fraction"],
                "failures": failures, **more}
        lines.append(line)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    def faulty(name, module):
        start, got = round0(module, init)
        more = {}
        if jax.tree.map(jnp.shape, got) != jax.tree.map(jnp.shape, init):
            shared = _held_by_both(want, got)
            more["fraction_over_shared"] = (
                cell_mod.tree_rel_err(shared, got)
                / cell_mod.tree_rel_err(start, shared))
            got = _left_as_initialised(init, got)
        report(name, got, False, **more)

    sound = module_with({})
    init, got = round0(sound)
    reference = cell.module("references", config["reference"])
    want = jax.device_get(reference.run_round(
        sound, task, train, init, dataset, seed=args.seed, round_idx=0,
        clients=cohort, aggregate=True)["variables"])
    change = cell_mod.tree_rel_err(init, want)
    report("sound", got, True)
    for item in args.kwargs:
        name, _, text = item.partition("=")
        faulty(name, module_with(json.loads(text)))
    if args.bf16_fold:
        fold = aggregate.tree_fold_pallas
        # bfloat16's 8 exponent and 7 mantissa bits; a conversion there and
        # back read the sound round's figure to the last digit on the chip
        # (PR 33): the compiled program kept none of it
        aggregate.tree_fold_pallas = lambda *a, **kw: jax.tree.map(
            lambda leaf: jax.lax.reduce_precision(leaf, 8, 7),
            fold(*a, **kw))
        try:
            faulty("bf16_fold", sound)
        finally:
            aggregate.tree_fold_pallas = fold
    return 0 if all(line["correct"] == line["expected_correct"]
                    for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
