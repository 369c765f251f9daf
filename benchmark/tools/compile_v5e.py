#!/usr/bin/env python3
"""Compile a cell's round program for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_v5e.py \
        [--cohort N] <workload> ...

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described and not attached (``on-chip-measurement`` guide, section 2).
This is how the cohort sizes in the traffic files were chosen and how a
change to them is checked before it costs chip time: it prints what
``compiled.memory_analysis()`` says each device needs for one round at the
cell's cohort and padded length, which collectives the compiler put in, and
whether the aggregation kernel is there. ``--cohort`` tries another cohort
than the traffic file's. Nothing runs; it gives no time.

The program's drivers ask ``jax.default_backend()`` which aggregation to
build and would take the CPU's here, so the round is assembled from the
same parts the drivers assemble on a TPU: ``make_local_train`` under
``make_vmapped_body`` with the Pallas weighted mean (sim), or
``make_spmd_round`` on the described mesh (spmd).
"""

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def lower_round(cell, topo, cohort):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from benchmark.harness import cell as cell_mod
    from fedml_tpu.trainer.functional import TrainConfig

    data, train = cell.config["data"], cell.config["train"]
    task = cell.config["model"]["task"]
    bsz = int(train["batch_size"])
    dataset, _ = cell.module("generators", data["generator"]).build(
        data, cell.clients, 0)
    # the largest bucket any cohort can need, and one row's shape
    n_pad = dataset.cohort_padded_len(range(cell.clients), bsz)
    x0, y0 = dataset.train_data_local_dict[0]
    row = tuple(x0.shape[1:])
    module = cell_mod.make_model(cell.config)
    cfg = TrainConfig(**train)
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1,) + row, x0.dtype), train=False))
    key = jax.eval_shape(lambda: jax.random.key(0))

    if cell.traffic["driver"] == "sim":
        from fedml_tpu.algorithms.fedavg import make_vmapped_body
        from fedml_tpu.ops import tree_weighted_mean_pallas
        from fedml_tpu.trainer.functional import make_local_train

        body = make_vmapped_body(make_local_train(module, task, cfg))

        def round_fn(variables, x, y, mask, keys, weights):
            stacked, totals = body(variables, x, y, mask, keys, None)
            return tree_weighted_mean_pallas(stacked, weights), totals

        fn = jax.jit(round_fn, donate_argnums=(0,))
        whole = sharded = SingleDeviceSharding(topo.devices[0])
    else:
        from fedml_tpu.parallel.spmd import make_spmd_round

        mesh = Mesh(np.asarray(topo.devices[:cell.chips]), ("clients",))
        fn = make_spmd_round(module, task, cfg, mesh, donate=True)
        whole = NamedSharding(mesh, PartitionSpec())
        sharded = NamedSharding(mesh, PartitionSpec("clients"))

    def arg(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (jax.tree.map(lambda a: arg(a.shape, a.dtype, whole), variables),
            arg((cohort, n_pad) + row, x0.dtype, sharded),
            arg((cohort, n_pad) + tuple(y0.shape[1:]), y0.dtype, sharded),
            arg((cohort, n_pad), jnp.float32, sharded),
            arg((cohort,), key.dtype, sharded),
            arg((cohort,), jnp.float32, sharded))
    return fn.lower(*args), n_pad


def main(argv) -> int:
    import argparse

    from jax.experimental import topologies

    from benchmark.harness import spec

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    parser = argparse.ArgumentParser()
    parser.add_argument("--cohort", type=int)
    parser.add_argument("workloads", nargs="+")
    args = parser.parse_args(argv)
    for name in args.workloads:
        cell = spec.load_cell(name)
        cohort = args.cohort or int(cell.traffic["cohort"])
        t0 = time.time()
        lowered, n_pad = lower_round(cell, topo, cohort)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        collectives = sorted(set(re.findall(
            r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\b", text)))
        print(f"{name}: cohort {cohort} x {n_pad} rows on {cell.chips} "
              f"chip(s), compiled in {time.time() - t0:.1f} s; per device "
              f"arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"outputs {mem.output_size_in_bytes / 2**30:.3f}, temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.3f}, aliased "
              f"{mem.alias_size_in_bytes / 2**30:.3f}, in all "
              f"{need / 2**30:.3f} GiB; collectives {collectives}; Pallas "
              f"kernel {'tpu_custom_call' in text}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
