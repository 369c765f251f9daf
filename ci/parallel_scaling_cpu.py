"""CPU scaling curve for the parallelism layer — correctness/overhead
evidence on the 8-virtual-device mesh.

This host has ONE physical core, so virtual-device sharding cannot show a
wall-clock speedup; what this curve pins is that the sharded federated
round programs (sequence-parallel ring attention, Megatron TP) stay
numerically healthy and within a constant-factor overhead of the unsharded
program as the model axis grows 1 -> 2 -> 4 -> 8. On a real slice the same
programs ride ICI (tests + dryrun_multichip validate placement).

Writes runs/parallel_scaling_cpu.json.
Run: JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python ci/parallel_scaling_cpu.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from fedml_tpu.models.transformer import TransformerLM  # noqa: E402
from fedml_tpu.parallel.sequence import make_seq_federated_round  # noqa: E402
from fedml_tpu.parallel.tensor import make_tp_federated_round  # noqa: E402
from fedml_tpu.trainer.functional import TrainConfig  # noqa: E402


def measure(kind: str, n_model: int, S: int = 128) -> float:
    devs = jax.devices()
    n_cl = len(devs) // n_model
    P = n_cl
    vocab, width, heads = 128, 32, 2
    n_pad, bsz, steps = 2, 2, 3
    cfg = TrainConfig(epochs=1, batch_size=bsz, lr=0.1)
    rng = np.random.RandomState(0)
    mesh = Mesh(np.asarray(devs[:n_cl * n_model]).reshape(n_cl, n_model),
                ("clients", kind))
    lm = TransformerLM(vocab_size=vocab, width=width, depth=1,
                       num_heads=heads, max_len=S)
    x = rng.randint(0, vocab, (P, n_pad, S)).astype(np.int32)
    y = np.roll(x, -1, axis=-1).astype(np.int32)
    mask = np.ones((P, n_pad), np.float32)
    weights = np.full((P,), float(n_pad), np.float32)
    keys = jax.random.split(jax.random.key(0), P)
    variables = lm.init(jax.random.key(1), jnp.asarray(x[0, :1]),
                        train=False)
    if kind == "seq":
        round_fn = make_seq_federated_round(lm, cfg, mesh)
    else:
        round_fn, shard_params = make_tp_federated_round(lm, "nwp", cfg,
                                                         mesh)
        variables = shard_params(variables)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), keys,
            jnp.asarray(weights))
    v, stats = round_fn(variables, *args)
    jax.block_until_ready(v)
    assert np.isfinite(float(stats["loss_sum"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        v, _ = round_fn(v, *args)
    jax.block_until_ready(v)
    return round(steps * P * n_pad * S / (time.perf_counter() - t0), 1)


def main():
    out = {"host": "single-core CPU, 8 virtual devices",
           "note": "overhead curve, not a speedup claim (1 physical core)",
           "seq": {}, "tp": {}}
    for kind in ("seq", "tp"):
        for n_model in (1, 2, 4, 8):
            tps = measure(kind, n_model)
            out[kind][str(n_model)] = tps
            print(f"{kind} x{n_model}: {tps} tokens/s", flush=True)
    os.makedirs("runs", exist_ok=True)
    with open(os.path.join("runs", "parallel_scaling_cpu.json"), "w") as f:
        json.dump(out, f, indent=2)
    print("wrote runs/parallel_scaling_cpu.json")


if __name__ == "__main__":
    main()
