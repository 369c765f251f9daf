"""Subprocess worker for the 2-process multihost rendezvous smoke tests.

Run as: python multihost_worker.py <coordinator> <num_procs> <proc_id> [mode]

Each process presents 4 virtual CPU devices, so the 2-process job forms an
8-device global mesh — the same shape the reference exercises with
``mpirun -np N -hostfile`` on localhost (run_fedavg_distributed_pytorch.sh:19-22),
but through jax.distributed's real rendezvous + DCN collectives instead of
mpi4py sends.

Modes:
- ``collectives`` (default): mesh + cross-host sums through the multihost
  helpers. Prints MULTIHOST_OK <sum>.
- ``fedavg``: one REAL FedAvg SPMD round (make_spmd_round) over the global
  mesh, each host feeding only its local client rows
  (multihost.local_client_slice + host_local_to_global — the multi-host
  data contract). Prints FEDAVG_OK <param_l2_norm> so the test can check
  both hosts computed the identical replicated model.
"""

import os
import sys

# must precede jax import: each process is a fake 4-device host
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()


def _federated_inputs(multihost, dim: int, class_num: int):
    """Shared multi-host data contract: global mesh, seeded federation,
    host-local pack + host_local_to_global stacking, fold_in key chain,
    replicated init, and the compiled spmd round fn. Used by BOTH the
    correctness round and the weak-scaling bench so they exercise the
    identical protocol."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.data.synthetic import make_blob_federated
    from fedml_tpu.models.lr import LogisticRegression
    from fedml_tpu.parallel.spmd import make_spmd_round
    from fedml_tpu.trainer.functional import TrainConfig

    mesh = multihost.global_client_mesh()
    n_clients = mesh.shape["clients"]

    # every host derives the SAME federation (seeded), feeds only its rows
    ds = make_blob_federated(client_num=n_clients, dim=dim,
                             class_num=class_num,
                             n_samples=32 * n_clients, seed=11)
    model = LogisticRegression(num_classes=ds.class_num)
    cfg = TrainConfig(epochs=1, batch_size=16, lr=0.1, shuffle=False)

    lo, hi = multihost.local_client_slice(mesh, n_clients)
    x, y, mask = ds.pack_clients(list(range(lo, hi)), cfg.batch_size)
    weights = ds.client_weights(list(range(lo, hi)))[:, None]
    xg, yg, mg, wg = multihost.host_local_to_global(
        mesh, (x, y, mask, weights.astype(np.float32)), n_clients)

    keys_local = np.stack([
        np.asarray(jax.random.key_data(
            jax.random.fold_in(jax.random.key(0), c)))
        for c in range(lo, hi)])
    kg = multihost.host_local_to_global(mesh, keys_local, n_clients)
    keys = jax.vmap(jax.random.wrap_key_data)(kg)

    variables = model.init(jax.random.key(1), jnp.zeros((1, dim)),
                           train=False)
    round_fn = make_spmd_round(model, "classification", cfg, mesh)
    return round_fn, variables, (xg, yg, mg, keys, wg[:, 0])


def run_fedavg_round(multihost) -> None:
    """One spmd FedAvg round with host-local data feeding."""
    import jax
    import jax.numpy as jnp

    round_fn, variables, args = _federated_inputs(multihost, dim=8,
                                                  class_num=4)
    new_vars, stats = round_fn(variables, *args)
    jax.block_until_ready(new_vars)
    assert float(stats["count"]) > 0

    norm = float(jnp.sqrt(sum(jnp.sum(a ** 2)
                              for a in jax.tree.leaves(new_vars))))
    # replicated output must agree across hosts
    assert multihost.all_hosts_agree(int(norm * 1e6))
    print(f"FEDAVG_OK {norm:.6f}", flush=True)


def run_fedavg_bench(multihost, timed_rounds: int = 20) -> None:
    """Weak-scaling measurement: repeated REAL FedAvg SPMD rounds over the
    global mesh (4 virtual devices per process, one client per device —
    per-host work fixed, total work grows with process count). Proc 0
    prints ``BENCH_OK <rounds_per_sec> <ms_per_round>``.

    On a 1-core host every process time-shares the same core, so absolute
    rounds/s falls with P by construction; the number this measures is
    the multi-process protocol (rendezvous + DCN collective) overhead
    trend, which feeds the BASELINE.md v5e-256 projection."""
    import time as _time

    import jax

    round_fn, variables, args = _federated_inputs(multihost, dim=64,
                                                  class_num=10)
    variables, _ = round_fn(variables, *args)
    jax.block_until_ready(variables)  # compile
    t0 = _time.perf_counter()
    for _ in range(timed_rounds):
        variables, _ = round_fn(variables, *args)
    jax.block_until_ready(variables)
    dt = _time.perf_counter() - t0
    if jax.process_index() == 0:
        print(f"BENCH_OK {timed_rounds / dt:.4f} "
              f"{dt / timed_rounds * 1e3:.3f}", flush=True)


def main() -> None:
    coordinator, num_procs, proc_id = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
    mode = sys.argv[4] if len(sys.argv) > 4 else "collectives"

    from fedml_tpu.parallel import multihost

    pid, count = multihost.initialize(
        coordinator_address=coordinator,
        num_processes=num_procs,
        process_id=proc_id,
    )
    assert (pid, count) == (proc_id, num_procs), (pid, count)

    if mode == "fedavg":
        run_fedavg_round(multihost)
        return
    if mode == "bench":
        run_fedavg_bench(multihost)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    assert len(jax.devices()) == 4 * num_procs, len(jax.devices())

    mesh = multihost.global_client_mesh()
    n_clients = mesh.shape["clients"]

    # every host feeds only its local rows (the multi-host data contract)
    lo, hi = multihost.local_client_slice(mesh, n_clients)
    local = np.arange(lo, hi, dtype=np.float32)[:, None]  # client idx as data
    stacked = multihost.host_local_to_global(mesh, local, n_clients)

    @jax.jit
    def global_sum(x):
        return jnp.sum(x)

    total = float(global_sum(stacked))
    expect = float(sum(range(n_clients)))
    assert total == expect, (total, expect)

    assert multihost.all_hosts_agree(7)

    # cross-host weighted aggregation through the mesh (the FedAvg psum path)
    weights = multihost.host_local_to_global(
        mesh, np.full((hi - lo, 1), proc_id + 1.0, np.float32), n_clients)
    wsum = float(jax.jit(lambda w, x: jnp.sum(w * x))(weights, stacked))
    per_host = n_clients // num_procs
    expect_w = sum((h + 1.0) * i for h in range(num_procs)
                   for i in range(h * per_host, (h + 1) * per_host))
    assert wsum == expect_w, (wsum, expect_w)

    print(f"MULTIHOST_OK {total}", flush=True)


if __name__ == "__main__":
    main()
