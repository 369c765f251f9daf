#!/usr/bin/env python3
"""The ``timed`` check's controls for a cell whose model has latent attention
and shared experts beside routed ones (``fedml_tpu/models/deepseek_v3.py``),
on the chip at the cell's own size.

    python3 benchmark/tools/mla_check_controls.py --workload <cell> \
        --seed <n> [--manifest FILE] [--out FILE] [--only NAME ...]

The cell's round 0 with a fault put in, through the harness's own comparison
(``harness/cell.py::compare_parameters`` under the configuration's
``check.timed``) against the reference's round, as
``ssd_check_controls.py`` runs its faults (a fault here is a patch of the
program or another model argument). A sound round has to come out correct
and every fault not:

* ``no_shared_experts`` - the shared experts add nothing;
* ``rope_off_shared_key`` - the one rope key all the heads share is left
  unrotated (the queries' rope channels still turn);
* ``top_k_less_one`` - the model built with one expert a token fewer;
* ``bf16_fold`` - the folded round's running FedAvg sum rounded to bfloat16
  after every fold with ``lax.reduce_precision`` (the form the TPU compiler
  keeps: PERF.md section 7).

One JSON line a control on standard output and in ``--out``; the exit code
is 0 if the sound round is correct and no fault is.
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

CONTROLS = ("no_shared_experts", "rope_off_shared_key", "top_k_less_one",
            "bf16_fold")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--manifest", default=None)
    parser.add_argument("--out")
    parser.add_argument("--only", nargs="+", choices=CONTROLS,
                        default=list(CONTROLS))
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    from benchmark.run import enable_compile_cache
    from fedml_tpu.models import create_model, deepseek_v3
    from fedml_tpu.ops import aggregate

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

    def module_with(**kwargs):
        return create_model(model["create_model"],
                            output_dim=int(model["output_dim"]),
                            **{**model.get("kwargs", {}), **kwargs})

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
        api.release_prefetch()
        del api
        gc.collect()  # the driver is in reference cycles, and holds a model
        jax.clear_caches()
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
        print(json.dumps(lines[-1]), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(lines[-1]) + "\n")

    def patched(owner, name, value):
        """Round 0 from ``init`` with ``owner.name`` replaced by ``value``."""
        kept = getattr(owner, name)
        setattr(owner, name, value)
        try:
            return round0(sound, init)[1]
        finally:
            setattr(owner, name, kept)

    report("sound", got, True)
    rope, fold = deepseek_v3._rope, aggregate.tree_fold_pallas
    faults = {
        "no_shared_experts": lambda: patched(
            deepseek_v3, "_shared_experts", lambda p, s: jnp.zeros_like(s)),
        # the shared key is the one input with a single head
        "rope_off_shared_key": lambda: patched(
            deepseek_v3, "_rope",
            lambda x, cfg: x if x.shape[1] == 1 else rope(x, cfg)),
        "top_k_less_one": lambda: round0(module_with(
            num_experts_per_tok=int(
                model["kwargs"]["num_experts_per_tok"]) - 1), init)[1],
        # bfloat16's 8 exponent and 7 mantissa bits; the compiler drops a
        # conversion there and back (PR 33)
        "bf16_fold": lambda: patched(
            aggregate, "tree_fold_pallas", lambda *a, **kw: jax.tree.map(
                lambda leaf: jax.lax.reduce_precision(leaf, 8, 7),
                fold(*a, **kw))),
    }
    for name in args.only:
        report(name, faults[name](), False)
    return 0 if all(line["correct"] == line["expected_correct"]
                    for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
