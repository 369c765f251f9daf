#!/usr/bin/env python3
"""Compile a folded cell's round program for a described TPU v5e, without a
chip: ``compile_v5e.py`` for the drivers that fold their cohort
(``FedAvgConfig.fold_clients``; ``make_local_train`` under
``make_folded_body`` with the Pallas fold, as ``FedAvgAPI`` assembles them
on a TPU).

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_fold_v5e.py \
        [--output-dim N] [--hlo FILE] <workload> ...

It prints what ``compiled.memory_analysis()`` says the device needs for one
round (``peak_memory_in_bytes`` is what the buffer assignment needs and what
the chip then reserves; the sum of the sizes counts every temporary as if
none shared a byte) and whether the fold kernel is there; ``--output-dim`` tries another
share of the vocabulary than the configuration's, ``--hlo`` writes the
compiled program's text (operation names as a trace shows them). Nothing
runs; it gives no time. What else the process holds on the chip (the
harness's copy of the initial model through warm-up) is not in the figure.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def lower_round(cell, topo, output_dim=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from fedml_tpu.algorithms.fedavg import make_folded_body
    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.functional import TrainConfig, make_local_train

    model, data = cell.config["model"], cell.config["data"]
    train = cell.config["train"]
    module = create_model(model["create_model"],
                          output_dim=int(output_dim or model["output_dim"]),
                          **model.get("kwargs", {}))
    cohort, bsz = int(cell.traffic["cohort"]), int(train["batch_size"])
    n_pad = -(-int(data["train_rows"]) // bsz) * bsz
    row = (int(data["sequence_length"]),)
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1,) + row, jnp.int32), train=False))
    key = jax.eval_shape(lambda: jax.random.key(0))
    body = make_folded_body(make_local_train(module, model["task"],
                                             TrainConfig(**train)))

    def round_fn(variables, x, y, mask, keys, weights):
        return body(variables, x, y, mask, keys, weights)

    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (jax.tree.map(lambda a: arg(a.shape, a.dtype), variables),
            arg((cohort, n_pad) + row, jnp.int32),
            arg((cohort, n_pad) + row, jnp.int32),
            arg((cohort, n_pad), jnp.float32),
            arg((cohort,), key.dtype),
            arg((cohort,), jnp.float32))
    params = sum(a.size for a in jax.tree.leaves(variables))
    return jax.jit(round_fn, donate_argnums=(0,)).lower(*args), params


def main(argv) -> int:
    import argparse

    from jax.experimental import topologies

    from benchmark.harness import spec

    parser = argparse.ArgumentParser()
    parser.add_argument("--output-dim", type=int)
    parser.add_argument("--hlo")
    parser.add_argument("workloads", nargs="+")
    args = parser.parse_args(argv)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in args.workloads:
        cell = spec.load_cell(name)
        t0 = time.time()
        lowered, params = lower_round(cell, topo, args.output_dim)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        if args.hlo:
            with open(args.hlo, "w") as f:
                f.write(text)
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: {params} parameters ({4 * params / 1e9:.3f} GB a "
              f"copy), compiled in {time.time() - t0:.1f} s; arguments "
              f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{mem.output_size_in_bytes / 1e9:.3f}, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f}, aliased "
              f"{mem.alias_size_in_bytes / 1e9:.3f}, summed "
              f"{need / 1e9:.3f} GB; peak "
              f"{mem.peak_memory_in_bytes / 1e9:.3f} GB "
              f"({mem.peak_memory_in_bytes / 2**30:.3f} GiB); Pallas calls "
              f"{text.count('tpu_custom_call')}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
