"""Federated long-context rounds: ('clients', 'seq') mesh parity."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.parallel.sequence import (make_seq_federated_round,
                                         ring_attention)
from fedml_tpu.trainer.functional import TrainConfig, make_local_train


def test_clients_x_seq_round_matches_single_device():
    """FedAvg round on a ('clients','seq') 4x2 mesh — every client's
    sequences ring-attended across 2 shards — equals the unsharded round."""
    vocab, width, S = 32, 16, 16
    P_clients, n_pad = 4, 4
    cfg = TrainConfig(epochs=1, batch_size=2, lr=0.1, shuffle=False)

    rng = np.random.RandomState(0)
    x = rng.randint(0, vocab, (P_clients, n_pad, S)).astype(np.int32)
    y = np.roll(x, -1, axis=-1).astype(np.int32)
    mask = np.ones((P_clients, n_pad), np.float32)
    weights = np.full((P_clients,), float(n_pad), np.float32)
    keys = jax.random.split(jax.random.key(0), P_clients)

    # oracle: plain attention, single device, vmapped round
    lm_plain = TransformerLM(vocab_size=vocab, width=width, depth=1,
                             num_heads=2, max_len=S)
    variables = lm_plain.init(jax.random.key(1), jnp.asarray(x[0, :1]),
                              train=False)
    local = make_local_train(lm_plain, "nwp", cfg)

    def oracle(v, x, y, m, k):
        from fedml_tpu.core import pytree as pt
        stacked, stats = jax.vmap(local, in_axes=(None, 0, 0, 0, 0))(
            v, x, y, m, k)
        totals = jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)
        return pt.tree_weighted_mean(stacked, jnp.asarray(weights)), totals

    want, want_stats = jax.jit(oracle)(
        variables, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), keys)

    # sequence-parallel: same weights, ring attention across the seq axis
    lm_ring = TransformerLM(
        vocab_size=vocab, width=width, depth=1, num_heads=2, max_len=S,
        attn_fn=functools.partial(ring_attention, axis_name="seq"))
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                ("clients", "seq"))
    round_fn = make_seq_federated_round(lm_ring, cfg, mesh)
    got, got_stats = round_fn(
        variables, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), keys,
        jnp.asarray(weights))

    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(got_stats["count"]),
                               float(want_stats["count"]))
    np.testing.assert_allclose(float(got_stats["loss_sum"]),
                               float(want_stats["loss_sum"]), rtol=1e-4)


@pytest.mark.slow
class TestSeqVsTpRatioGuard:
    """Regression guards for the r5 bench's 577.8 tokens/s seq row
    (a record predating this installation): the seq round's jit caches on input *sharding* — the
    first call (uncommitted lm.init params) compiles one signature, its
    mesh-committed output makes the second call a cache miss, and that
    second compile landed inside the bench's timed region. The tp twin
    pre-places params via ``shard_params``, which is why only the seq row
    was 4 orders of magnitude off. Guards: (a) the root cause — after
    warming BOTH signatures the steady state never recompiles; (b) the
    symptom — at identical CPU smoke shapes, the timed seq round stays
    within a wide band of its tp twin (the regression was ~4000x)."""

    def _build(self):
        from fedml_tpu.parallel.tensor import make_tp_federated_round

        S, vocab, width, heads = 64, 64, 32, 2
        P_cl, n_pad, bsz = 4, 2, 2
        cfg = TrainConfig(epochs=1, batch_size=bsz, lr=0.1)
        rng = np.random.RandomState(0)
        x = rng.randint(0, vocab, (P_cl, n_pad, S)).astype(np.int32)
        y = np.roll(x, -1, axis=-1).astype(np.int32)
        mask = np.ones((P_cl, n_pad), np.float32)
        weights = np.full((P_cl,), float(n_pad), np.float32)
        keys = jax.random.split(jax.random.key(0), P_cl)
        args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), keys,
                jnp.asarray(weights))
        devs = np.asarray(jax.devices()[:8])

        lm_seq = TransformerLM(
            vocab_size=vocab, width=width, depth=1, num_heads=heads,
            max_len=S,
            attn_fn=functools.partial(ring_attention, axis_name="seq"))
        seq_fn = make_seq_federated_round(
            lm_seq, cfg, Mesh(devs.reshape(4, 2), ("clients", "seq")))

        lm_tp = TransformerLM(vocab_size=vocab, width=width, depth=1,
                              num_heads=heads, max_len=S)
        tp_fn, shard_params = make_tp_federated_round(
            lm_tp, "nwp", cfg, Mesh(devs.reshape(4, 2), ("clients", "tp")))

        variables = lm_tp.init(jax.random.key(1), jnp.asarray(x[0, :1]),
                               train=False)
        return seq_fn, tp_fn, shard_params, variables, args

    def test_seq_steady_state_does_not_recompile(self):
        seq_fn, _, _, variables, args = self._build()
        v, _ = seq_fn(variables, *args)      # signature 1: uncommitted
        v, _ = seq_fn(v, *args)              # signature 2: committed
        jax.block_until_ready(v)
        warmed = seq_fn._cache_size()
        for _ in range(3):                   # steady state: zero new compiles
            v, _ = seq_fn(v, *args)
        jax.block_until_ready(v)
        assert seq_fn._cache_size() == warmed, (
            "seq round recompiled after both warmup signatures — a compile "
            "is back inside what bench_parallel_axes times")

    def test_seq_vs_tp_ratio_at_cpu_shapes(self):
        seq_fn, tp_fn, shard_params, variables, args = self._build()

        def tokens_per_sec(fn, v, steps=3):
            v, _ = fn(v, *args)              # warm signature 2 (seq); tp hit
            jax.block_until_ready(v)
            t0 = time.perf_counter()
            for _ in range(steps):
                v, _ = fn(v, *args)
            jax.block_until_ready(v)
            # 4 clients * n_pad 2 * S 64 tokens per round
            return steps * 4 * 2 * 64 / (time.perf_counter() - t0)

        v0, _ = seq_fn(variables, *args)     # signature 1 outside timing
        jax.block_until_ready(v0)
        seq_tps = tokens_per_sec(seq_fn, v0)
        tp_tps = tokens_per_sec(tp_fn, shard_params(variables))
        # the r5 pathology was ~4000x; 50x absorbs 1-core CI noise while
        # still catching any compile landing back inside the timed region
        assert seq_tps > tp_tps / 50, (
            f"seq round {seq_tps:.1f} tok/s vs tp {tp_tps:.1f} tok/s — "
            "ratio beyond the regression band (compile inside the timed "
            "region?)")
