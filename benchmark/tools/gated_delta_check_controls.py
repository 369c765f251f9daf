#!/usr/bin/env python3
"""The ``timed`` check's controls for a cell whose model has Gated DeltaNet
layers beside gated attention and routed experts beside a gated shared one
(``fedml_tpu/models/qwen3_next.py``), on the chip at the cell's own size.

    python3 benchmark/tools/gated_delta_check_controls.py --workload <cell> \
        --seed <n> [--more-seeds N ...] [--manifest FILE] [--out FILE] \
        [--only NAME ...] [--lr-sweep RATE ...]

The cell's round 0 with a fault put in, through the harness's own comparison
(``harness/cell.py::compare_parameters`` under the configuration's
``check.timed``) against the reference's round, as ``mla_check_controls.py``
runs its faults (a fault here is a patch of the program or another model
argument). A sound round has to come out correct and every fault not:

* ``gate_before_norm`` - the linear block's output gated by ``silu(z)``
  before its norm (Mamba-2's order);
* ``no_decay`` - the delta rule run with ``g = 0``;
* ``beta_one`` - the delta rule run with ``beta = 1``;
* ``no_attention_gate`` - the full-attention heads without their sigmoid
  gate;
* ``sigmoid_router`` - the routed experts chosen and weighed by sigmoid
  scores in place of the softmax;
* ``no_shared_gate`` - the shared expert without its sigmoid gate;
* ``top_k_less_one`` - the model built with one expert a token fewer;
* ``bf16_fold`` - the folded round's running FedAvg sum rounded to bfloat16
  after every fold with ``lax.reduce_precision`` (the form the TPU compiler
  keeps: PERF.md section 7);
* ``reference_bf16`` - no program: the reference's round result held in
  bfloat16, the nearest precision below the float32 the configuration
  states (nothing compiled).

``--more-seeds``: the sound round and the reference's again at each of
these seeds (the programs compiled once serve them all), for the bound's
calibration. At the cell's size give them a process of their own
(``--only`` with no name): after the controls' compilations the seeds'
host copies passed the 40 GiB of a one-chip host. ``--lr-sweep``: no control; for each rate, rounds 0-20 of a
driver built like the cell's at that rate, the round's mean local loss
printed a round, and the held-out loss after round 20.

One JSON line a control, a seed or a rate on standard output and in
``--out``; the exit code is 0 if every sound round is correct and no fault
is (a sweep: 0).
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

CONTROLS = ("gate_before_norm", "no_decay", "beta_one", "no_attention_gate",
            "sigmoid_router", "no_shared_gate", "top_k_less_one", "bf16_fold",
            "reference_bf16")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--more-seeds", type=int, nargs="+", default=[])
    parser.add_argument("--manifest", default=None)
    parser.add_argument("--out")
    parser.add_argument("--only", nargs="*", choices=CONTROLS,
                        default=list(CONTROLS))
    parser.add_argument("--lr-sweep", type=float, nargs="+", default=[])
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec
    from benchmark.run import enable_compile_cache
    from fedml_tpu.models import create_model, qwen3_next
    from fedml_tpu.models.common import rms_norm
    from fedml_tpu.ops import aggregate

    enable_compile_cache()
    cell = spec.load_cell(args.workload, args.manifest or spec.MANIFEST)
    log = functools.partial(cell_mod._log, T_START)
    config, traffic = cell.config, cell.traffic
    model, train = config["model"], config["train"]
    task = model["task"]
    driver = cell.module("drivers", traffic["driver"])
    reference = cell.module("references", config["reference"])
    cohort = [int(c) for c in cell_mod.sample_cohort(
        0, cell.clients, int(traffic["cohort"]))]

    def federation(seed):
        dataset, _ = cell.module("generators", config["data"]["generator"]
                                 ).build(config["data"], cell.clients, seed)
        return dataset, dict(train=train, cohort=len(cohort),
                             eval_every=int(traffic["eval_every"]),
                             rounds=int(traffic["round_bound"]), seed=seed,
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

    dataset, build_args = federation(args.seed)
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

    def round0(module, dataset, build_args, init=None):
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
    lines = []

    def sound_round(seed, dataset, build_args):
        """``(init, the reference's round)`` at ``seed``, the sound round
        reported."""
        init, got = round0(sound, dataset, build_args)
        want = jax.device_get(reference.run_round(
            sound, task, train, init, dataset, seed=seed, round_idx=0,
            clients=cohort, aggregate=True)["variables"])
        report("sound", seed, init, got, want, True)
        return init, want

    def report(name, seed, init, got, want, expect_correct):
        failures = cell_mod.compare_parameters(
            "timed", init, got, want, config["check"]["timed"], log)
        err = cell_mod.tree_rel_err(want, got)
        change = cell_mod.tree_rel_err(init, want)
        lines.append({
            "control": name, "workload": cell.name, "seed": seed,
            "correct": not failures, "expected_correct": expect_correct,
            "param_err": err, "change": change,
            "fraction_of_change": err / change,
            "param_fraction": config["check"]["timed"]["param_fraction"],
            "failures": failures})
        emit(lines[-1])

    init, want = sound_round(args.seed, dataset, build_args)

    def patched(owner, name, value):
        """Round 0 from ``init`` with ``owner.name`` replaced by ``value``."""
        kept = getattr(owner, name)
        setattr(owner, name, value)
        try:
            return round0(sound, dataset, build_args, init)[1]
        finally:
            setattr(owner, name, kept)

    rule, routed, fold = (qwen3_next.gated_delta_rule,
                          qwen3_next.routed_experts,
                          aggregate.tree_fold_pallas)
    faults = {
        "gate_before_norm": lambda: patched(
            qwen3_next, "_norm_then_gate",
            lambda out, z, scale, eps: rms_norm(out * jax.nn.silu(z), scale,
                                                eps)),
        "no_decay": lambda: patched(
            qwen3_next, "gated_delta_rule",
            lambda q, k, v, g, beta: rule(q, k, v, jnp.zeros_like(g), beta)),
        "beta_one": lambda: patched(
            qwen3_next, "gated_delta_rule",
            lambda q, k, v, g, beta: rule(q, k, v, g, jnp.ones_like(beta))),
        "no_attention_gate": lambda: patched(
            qwen3_next, "_output_gate", lambda out, gate: out),
        "sigmoid_router": lambda: patched(
            qwen3_next, "routed_experts",
            lambda *a, **kw: routed(*a, **{**kw, "score": "sigmoid"})),
        "no_shared_gate": lambda: patched(
            qwen3_next, "_shared_expert", lambda p, s: qwen3_next._swiglu(
                s, p["shared_w1"], p["shared_w3"], p["shared_w2"])),
        "top_k_less_one": lambda: round0(module_with(
            num_experts_per_tok=int(
                model["kwargs"]["num_experts_per_tok"]) - 1),
            dataset, build_args, init)[1],
        # bfloat16's 8 exponent and 7 mantissa bits; the compiler drops a
        # conversion there and back
        "bf16_fold": lambda: patched(
            aggregate, "tree_fold_pallas", lambda *a, **kw: jax.tree.map(
                lambda leaf: jax.lax.reduce_precision(leaf, 8, 7),
                fold(*a, **kw))),
        "reference_bf16": lambda: jax.tree.map(
            lambda leaf: np.asarray(leaf).astype(jnp.bfloat16).astype(
                np.float32), want),
    }
    for name in args.only:
        report(name, args.seed, init, faults[name](), want, False)
    init = want = faults = None  # the seeds' host copies need the room
    gc.collect()
    for seed in args.more_seeds:
        sound_round(seed, *federation(seed))
    return 0 if all(line["correct"] == line["expected_correct"]
                    for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
