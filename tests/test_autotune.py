"""ops.autotune: shape-aware attention selection + persistent decisions.

The contracts pinned here are the round-6 acceptance criteria: the winner
is measured per shape (deterministic under an injected timer), the
decision survives a process boundary (a FRESH cache instance reloads it
from disk and never re-times), and when tuning is unavailable the XLA
reference — the implementation that never silently loses — is dispatched.
All timing here is faked; no test waits on real kernels beyond one tiny
interpret-mode dispatch check.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import autotune as at
from fedml_tpu.parallel.sequence import reference_attention

GRID = ((16, 16), (32, 16))


def _qkv(b=1, s=64, h=2, d=8, dtype=jnp.float32):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), dtype)  # noqa: E731
    return mk(), mk(), mk()


def _fake_timer(table):
    """measure(label, attn_fn) from a {label: seconds} table, recording
    every call — tests assert on BOTH the winner and the call log."""
    calls = []

    def measure(label, attn_fn):
        calls.append(label)
        return table[label]
    return measure, calls


class TestCandidates:
    def test_filters_indivisible_blocks(self):
        assert at.block_candidates(64, GRID) == GRID
        # 48 % 32 != 0: only the 16s survive
        assert at.block_candidates(48, GRID) == ((16, 16),)

    def test_clamps_oversized_blocks_then_dedupes(self):
        # s=8 < every block: all entries clamp to (8, 8), one candidate
        assert at.block_candidates(8, GRID) == ((8, 8),)

    def test_empty_when_nothing_divides(self):
        assert at.block_candidates(50, GRID) == ()


class TestKey:
    def test_key_separates_every_field(self):
        keys = {
            at.attention_key(2048, 64, 4, jnp.float32, True),
            at.attention_key(1024, 64, 4, jnp.float32, True),
            at.attention_key(2048, 32, 4, jnp.float32, True),
            at.attention_key(2048, 64, 8, jnp.float32, True),
            at.attention_key(2048, 64, 4, jnp.bfloat16, True),
            at.attention_key(2048, 64, 4, jnp.float32, False),
            # batch is part of the dispatched shape: a winner tuned at
            # batch=4 must not be silently served at batch=32
            at.attention_key(2048, 64, 4, jnp.float32, True, batch=32),
        }
        assert len(keys) == 7


class TestDeterministicWinner:
    def test_fastest_pallas_candidate_wins(self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        measure, calls = _fake_timer(
            {"xla": 2.0, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        dec = at.autotune_attention(64, 8, num_heads=2, cache=cache,
                                    grid=GRID, measure=measure)
        assert (dec.impl, dec.block_q, dec.block_k) == ("pallas", 32, 16)
        assert dec.source == "tuned"
        # every candidate AND the reference raced exactly once
        assert sorted(calls) == ["pallas_16x16", "pallas_32x16", "xla"]
        assert dec.timings["pallas_32x16"] == 1.0

    def test_xla_wins_when_reference_is_fastest(self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        measure, _ = _fake_timer(
            {"xla": 0.5, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        dec = at.autotune_attention(64, 8, num_heads=2, cache=cache,
                                    grid=GRID, measure=measure)
        assert dec.impl == "xla"
        assert dec.block_q is None


class TestCacheRoundTrip:
    def test_fresh_state_reloads_without_retiming(self, tmp_path):
        """The second-process contract: tune once, then a FRESH cache
        instance (new process simulation) must serve the decision from
        disk — the timer is a tripwire that fails on any re-timing."""
        measure, calls = _fake_timer(
            {"xla": 2.0, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        at.autotune_attention(64, 8, num_heads=2,
                              cache=at.AutotuneCache(str(tmp_path)),
                              grid=GRID, measure=measure)
        assert calls  # first process really timed

        def tripwire(label, attn_fn):
            raise AssertionError("second process re-timed the shape")

        dec = at.autotune_attention(64, 8, num_heads=2,
                                    cache=at.AutotuneCache(str(tmp_path)),
                                    grid=GRID, measure=tripwire)
        assert (dec.impl, dec.block_q, dec.block_k) == ("pallas", 32, 16)
        assert dec.source == "cache"

    def test_cache_file_is_strict_json_keyed_by_device_and_shape(
            self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        measure, _ = _fake_timer(
            {"xla": 1.0, "pallas_16x16": 2.0, "pallas_32x16": 3.0})
        at.autotune_attention(64, 8, num_heads=2, causal=True, cache=cache,
                              grid=GRID, measure=measure)
        with open(cache.path) as f:
            entries = json.load(f)
        key, = entries
        assert key == ("cpu/"
                       + at.attention_key(64, 8, 2, jnp.float32, True))
        assert entries[key]["impl"] == "xla"

    def test_refresh_retimes_over_a_cache_hit(self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        m1, _ = _fake_timer(
            {"xla": 0.5, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        at.autotune_attention(64, 8, num_heads=2, cache=cache, grid=GRID,
                              measure=m1)
        # the bench's mode: refresh re-races and the decision can flip
        m2, calls2 = _fake_timer(
            {"xla": 2.0, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        dec = at.autotune_attention(64, 8, num_heads=2, cache=cache,
                                    grid=GRID, measure=m2, refresh=True)
        assert calls2 and dec.impl == "pallas"

    def test_concurrent_writers_merge_per_key(self, tmp_path):
        """put() must merge with the on-disk file, not overwrite it from
        a stale memo: two cache instances (concurrent launchers) that both
        loaded the empty file write different keys — BOTH must survive."""
        c1 = at.AutotuneCache(str(tmp_path))
        c2 = at.AutotuneCache(str(tmp_path))
        c1._load(), c2._load()  # both memoize the (missing) file
        c1.put("cpu/shape_a", at.AttentionDecision(impl="xla"))
        c2.put("cpu/shape_b", at.AttentionDecision(
            impl="pallas", block_q=16, block_k=16))
        with open(c1.path) as f:
            entries = json.load(f)
        assert set(entries) == {"cpu/shape_a", "cpu/shape_b"}

    def test_corrupt_cache_file_is_ignored(self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        import os
        os.makedirs(cache.cache_dir, exist_ok=True)
        with open(cache.path, "w") as f:
            f.write("{not json")
        assert cache.get("cpu/whatever") is None


class TestFallbackSelection:
    def test_cpu_without_timer_defaults_to_xla_unpersisted(self, tmp_path):
        """No measure, CPU backend: the XLA reference is selected without
        timing, and the default is NOT persisted (a later chip process
        must still get to tune the shape)."""
        cache = at.AutotuneCache(str(tmp_path))
        dec = at.autotune_attention(64, 8, num_heads=2, cache=cache,
                                    grid=GRID)
        assert (dec.impl, dec.source) == ("xla", "default")
        assert cache.get("cpu/" + at.attention_key(
            64, 8, 2, jnp.float32, True)) is None

    def test_default_cache_reverts_when_env_unset(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv(at.CACHE_DIR_ENV, str(tmp_path))
        assert at.default_cache().cache_dir == str(tmp_path)
        monkeypatch.delenv(at.CACHE_DIR_ENV)
        assert at.default_cache().cache_dir != str(tmp_path)

    def test_autotune_env_zero_disables_timing(self, tmp_path, monkeypatch):
        monkeypatch.setenv(at.AUTOTUNE_ENV, "0")
        dec = at.autotune_attention(64, 8, num_heads=2,
                                    cache=at.AutotuneCache(str(tmp_path)),
                                    grid=GRID)
        assert (dec.impl, dec.source) == ("xla", "default")

    def test_env_zero_beats_injected_measure_and_refresh(self, tmp_path,
                                                         monkeypatch):
        """The documented kill-switch contract: FEDML_TPU_AUTOTUNE=0 means
        NEVER time candidates — even the bench's injected timer with
        refresh=True must not race the grid, and a prior cached decision
        is served instead of the XLA default."""
        cache = at.AutotuneCache(str(tmp_path))
        measure, _ = _fake_timer(
            {"xla": 2.0, "pallas_16x16": 3.0, "pallas_32x16": 1.0})
        at.autotune_attention(64, 8, num_heads=2, cache=cache, grid=GRID,
                              measure=measure)  # tuned: pallas_32x16

        def tripwire(label, attn_fn):
            raise AssertionError("timed a candidate under AUTOTUNE=0")

        monkeypatch.setenv(at.AUTOTUNE_ENV, "0")
        dec = at.autotune_attention(64, 8, num_heads=2, cache=cache,
                                    grid=GRID, measure=tripwire,
                                    refresh=True)
        assert (dec.impl, dec.block_q, dec.source) == ("pallas", 32,
                                                       "cache")
        # unseen shape under the switch: XLA default, still no timing
        dec2 = at.autotune_attention(128, 8, num_heads=2, cache=cache,
                                     grid=GRID, measure=tripwire,
                                     refresh=True)
        assert (dec2.impl, dec2.source) == ("xla", "default")

    def test_attn_fn_dispatches_reference_on_fallback(self, tmp_path,
                                                      monkeypatch):
        """The never-silently-slower guarantee: with an XLA decision the
        Pallas kernel is not even imported into the dispatch."""
        import importlib
        # the package __init__ re-exports the function under the same
        # name, so plain attribute-style import resolves to the function
        fa = importlib.import_module("fedml_tpu.ops.flash_attention")

        def boom(*a, **kw):
            raise AssertionError("pallas dispatched under an xla decision")
        monkeypatch.setattr(fa, "flash_attention", boom)
        attn = at.make_autotuned_attention(
            cache=at.AutotuneCache(str(tmp_path)), grid=GRID)
        q, k, v = _qkv()
        out = attn(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=1e-6, atol=1e-6)


class TestAutotunedAttnFn:
    def test_pallas_decision_dispatches_kernel_and_matches_oracle(
            self, tmp_path):
        cache = at.AutotuneCache(str(tmp_path))
        measure, _ = _fake_timer(
            {"xla": 2.0, "pallas_16x16": 1.0, "pallas_32x16": 3.0})
        at.autotune_attention(64, 8, num_heads=2, cache=cache, grid=GRID,
                              measure=measure)
        attn = at.make_autotuned_attention(cache=cache, grid=GRID,
                                           interpret=True)
        q, k, v = _qkv()
        out = attn(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_resolves_under_jit_and_memoizes(self, tmp_path):
        """Safe at trace time: only static metadata is read from the
        tracers, the decision resolves once per shape, and retraces hit
        the in-process memo (the tuner runs zero extra times)."""
        cache = at.AutotuneCache(str(tmp_path))
        measure, calls = _fake_timer(
            {"xla": 1.0, "pallas_16x16": 2.0, "pallas_32x16": 3.0})
        attn = at.make_autotuned_attention(cache=cache, grid=GRID,
                                           measure=measure)
        q, k, v = _qkv()
        fn = jax.jit(lambda q, k, v: attn(q, k, v, causal=True))
        out = fn(q, k, v)
        n_calls = len(calls)
        assert n_calls == 3  # xla + two candidates, once
        fn(q * 2, k, v)  # same shape: memo hit, no new timing
        assert len(calls) == n_calls
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=1e-6, atol=1e-6)

    def test_transformer_lm_accepts_auto_attn(self, tmp_path, monkeypatch):
        """attn_fn="auto" end-to-end through TransformerLM on CPU: falls
        back to the XLA reference (no cache entry, no timer) and matches
        the default-attention model exactly."""
        monkeypatch.setenv(at.CACHE_DIR_ENV, str(tmp_path))
        from fedml_tpu.models.transformer import TransformerLM

        x = jnp.asarray(np.random.RandomState(0).randint(
            0, 32, (2, 16)).astype(np.int32))
        lm_auto = TransformerLM(vocab_size=32, width=16, depth=1,
                                num_heads=2, max_len=16, attn_fn="auto")
        lm_ref = TransformerLM(vocab_size=32, width=16, depth=1,
                               num_heads=2, max_len=16)
        variables = lm_ref.init(jax.random.key(0), x, train=False)
        got = lm_auto.apply(variables, x, train=False)
        want = lm_ref.apply(variables, x, train=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


class TestMakeFlashAttentionAuto:
    def test_auto_returns_autotuned_selection(self, tmp_path, monkeypatch):
        monkeypatch.setenv(at.CACHE_DIR_ENV, str(tmp_path))
        from fedml_tpu.ops.flash_attention import make_flash_attention

        attn = make_flash_attention(block_q="auto")
        q, k, v = _qkv()
        out = attn(q, k, v, causal=True)  # cpu fallback: xla reference
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=1e-6, atol=1e-6)

    def test_fixed_blocks_unchanged(self):
        from fedml_tpu.ops.flash_attention import make_flash_attention

        attn = make_flash_attention(block_q=16, block_k=16, interpret=True)
        q, k, v = _qkv()
        np.testing.assert_allclose(
            np.asarray(attn(q, k, v, causal=True)),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5)


class TestSequenceParallelWiring:
    def test_size_one_seq_axis_short_circuits_to_local_attn(self):
        """On a degenerate (size-1) seq axis the ring machinery is pure
        overhead — the wrapper must dispatch the local attention (the
        single-chip bench case) and still match the oracle."""
        from jax.sharding import Mesh
        from fedml_tpu.parallel.sequence import (
            make_sequence_parallel_attention)

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("seq",))
        seen = []

        def spy_attn(q, k, v, causal=False):
            seen.append(q.shape)
            return reference_attention(q, k, v, causal=causal)

        fn = make_sequence_parallel_attention(mesh, scheme="ring",
                                              causal=True,
                                              local_attn=spy_attn)
        q, k, v = _qkv(s=32)
        out = fn(q, k, v)
        assert seen  # the local attention actually ran
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=1e-5, atol=1e-5)

    def test_ulysses_local_attn_injection_matches_oracle(self):
        from jax.sharding import Mesh
        from fedml_tpu.parallel.sequence import (
            make_sequence_parallel_attention)

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("seq",))
        fn = make_sequence_parallel_attention(
            mesh, scheme="ulysses", causal=True,
            local_attn=reference_attention)
        q, k, v = _qkv(s=32)
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v)),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=1e-5, atol=1e-5)
