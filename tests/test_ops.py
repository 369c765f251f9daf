"""Pallas kernel tests (interpret mode on the CPU mesh) vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.models import create_model
from fedml_tpu.ops import (dequantize_int8, dequantize_tree,
                           mean_kernel_params, quantize_int8, quantize_tree,
                           tree_weighted_mean_pallas,
                           weighted_mean_flat_reference)
from fedml_tpu.ops.aggregate import stacked_mean_leaf


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestWeightedMean:
    def test_matches_reference_flat(self):
        # a [C, D] leaf: a vector a client, the side that is left to XLA
        rng = np.random.RandomState(0)
        x = rng.randn(7, 5000).astype(np.float32)
        w = rng.uniform(1, 100, size=7).astype(np.float32)
        got = tree_weighted_mean_pallas({"w": jnp.asarray(x)},
                                        jnp.asarray(w), interpret=True)["w"]
        want = weighted_mean_flat_reference(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_unpadded_tile_boundary(self):
        # the same stack as whole (8, 128) tiles: the kernel's side
        rng = np.random.RandomState(1)
        x = rng.randn(3, 4096).astype(np.float32)
        w = np.array([1.0, 2.0, 3.0], np.float32)
        leaf = jnp.asarray(x).reshape(3, 32, 128)
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda a: tree_weighted_mean_pallas(a, w, interpret=True))(leaf))
        got = tree_weighted_mean_pallas(leaf, jnp.asarray(w), interpret=True)
        want = weighted_mean_flat_reference(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got).ravel(), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_tree_frontend_matches_pytree_rule(self):
        rng = np.random.RandomState(2)
        tree = {
            "dense": {"kernel": jnp.asarray(rng.randn(4, 17, 33), jnp.float32),
                      "bias": jnp.asarray(rng.randn(4, 33), jnp.float32)},
            "scalar": jnp.asarray(rng.randn(4), jnp.float32),
        }
        w = jnp.asarray([10.0, 20.0, 30.0, 40.0])
        got = tree_weighted_mean_pallas(tree, w, interpret=True)
        want = tree_weighted_mean(tree, w)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            got, want)

    # (leaf shape behind the client axis, dtype, does the kernel take it)
    LEAVES = {
        "conv3x3": ((3, 3, 16, 128), jnp.float32, True),
        "conv1x1": ((1, 1, 8, 256), jnp.float32, True),
        # 200 rows: no block of 128, 72 or 32 rows divides them
        "ragged_rows": ((200, 128), jnp.float32, True),
        "wide": ((8, 2304), jnp.float32, True),
        "narrow64": ((3, 3, 8, 64), jnp.float32, False),
        "head": ((128, 62), jnp.float32, False),
        "odd_rows": ((3, 3, 4, 128), jnp.float32, False),
        "vector": ((128,), jnp.float32, False),
        "scalar": ((), jnp.float32, False),
        "bf16": ((16, 128), jnp.bfloat16, False),
    }

    @pytest.mark.parametrize("clients", [8, 104, 256])
    @pytest.mark.parametrize("leaf", sorted(LEAVES))
    def test_leaf_mean_matches_the_float64_mean(self, leaf, clients):
        shape, dtype, kernel = self.LEAVES[leaf]
        rng = np.random.RandomState(len(leaf) + clients)
        x = jnp.asarray(rng.randn(clients, *shape), dtype)
        w = rng.randint(17, 341, size=clients).astype(np.float32)
        share = jnp.asarray(w / w.sum())

        def mean(a):
            return stacked_mean_leaf(a, share, interpret=True)

        assert ("pallas_call" in str(jax.make_jaxpr(mean)(x))) == kernel
        got = jax.jit(mean)(x)
        assert got.dtype == dtype and got.shape == shape
        with jax.enable_x64(True):
            want = tree_weighted_mean(np.asarray(x, np.float64),
                                      np.asarray(w, np.float64))
        # float32 sums of `clients` terms; bf16 rounds the result once
        tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -8
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   np.asarray(want), rtol=tol, atol=tol)

    def test_a_cohort_too_wide_for_vmem_goes_to_xla(self):
        x = jax.ShapeDtypeStruct((2048, 16, 128), jnp.float32)
        share = jax.ShapeDtypeStruct((2048,), jnp.float32)
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda a, s: stacked_mean_leaf(a, s, interpret=True))(x, share))

    @pytest.mark.parametrize("model, args, row, clients, kernel, total", [
        ("resnet18_gn", dict(output_dim=100, small_images=False),
         (24, 24, 3), 104, 11_010_048, 11_227_812),
        ("cnn", dict(output_dim=62), (28, 28, 1), 256, 1_179_648,
         1_206_590)])
    def test_no_stack_of_the_cohort_is_built(self, model, args, row, clients,
                                             kernel, total):
        module = create_model(model, **args)
        variables = jax.eval_shape(lambda: module.init(
            jax.random.key(0), jnp.zeros((1,) + row), train=False))
        assert mean_kernel_params(variables, clients) == (kernel,
                                                          total - kernel)
        stacked = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((clients,) + a.shape, a.dtype),
            variables)
        closed = jax.make_jaxpr(lambda s, w: tree_weighted_mean_pallas(
            s, w, interpret=True))(
                stacked, jax.ShapeDtypeStruct((clients,), jnp.float32))
        names = [eqn.primitive.name for eqn in _eqns(closed.jaxpr)]
        assert not {"concatenate", "pad", "dynamic_update_slice",
                    "gather"} & set(names)
        # one call a leaf the kernel takes, whatever their shapes
        calls = sum(
            mean_kernel_params([a], clients)[0] > 0
            for a in jax.tree.leaves(variables))
        assert names.count("pallas_call") == calls > 0
        # nothing larger than the largest stacked leaf is ever formed
        largest = max(a.size for a in jax.tree.leaves(stacked))
        assert largest < clients * total
        for eqn in closed.jaxpr.eqns:
            assert all(v.aval.size <= largest for v in eqn.outvars), eqn


class TestQuantize:
    def test_round_trip_error_bound(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(10_000).astype(np.float32))
        vals, scales = quantize_int8(x, jax.random.key(0), interpret=True)
        assert vals.dtype == jnp.int8
        back = dequantize_int8(vals, scales, x.size, interpret=True)
        # per-block error bounded by one quantization step = absmax/127
        err = np.abs(np.asarray(back) - np.asarray(x)).max()
        step = np.abs(np.asarray(x)).max() / 127.0
        assert err <= step + 1e-6

    def test_stochastic_rounding_unbiased(self):
        # constant vector between two int levels: mean of dequantized values
        # must approach the true value, not the nearest level
        x = jnp.full((4096,), 0.6 * (1.27 / 127.0) * 100, jnp.float32)
        # place absmax so scale is known: append the max
        x = x.at[0].set(1.27)
        means = []
        for s in range(5):
            vals, scales = quantize_int8(x, jax.random.key(s), interpret=True)
            back = dequantize_int8(vals, scales, x.size, interpret=True)
            means.append(float(jnp.mean(back[1:])))
        assert abs(np.mean(means) - float(x[1])) < 2e-4

    def test_zero_vector(self):
        x = jnp.zeros((700,), jnp.float32)
        vals, scales = quantize_int8(x, jax.random.key(0), interpret=True)
        back = dequantize_int8(vals, scales, 700, interpret=True)
        assert float(jnp.abs(back).max()) == 0.0

    def test_tree_round_trip(self):
        rng = np.random.RandomState(4)
        tree = {"w": jnp.asarray(rng.randn(37, 11), jnp.float32),
                "b": jnp.asarray(rng.randn(11), jnp.float32)}
        vals, scales, spec = quantize_tree(tree, jax.random.key(1),
                                           interpret=True)
        back = dequantize_tree(vals, scales, spec, interpret=True)
        assert jax.tree.structure(back) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
            assert a.shape == b.shape and a.dtype == b.dtype
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(back), jax.tree.leaves(tree)))
        # global blocks: bound by the largest block absmax step
        gmax = max(float(jnp.abs(l).max()) for l in jax.tree.leaves(tree))
        assert err <= gmax / 127.0 + 1e-6


@pytest.mark.parametrize("d", [100, 512, 513, 16384])
def test_quantize_sizes(d):
    rng = np.random.RandomState(d)
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    vals, scales = quantize_int8(x, jax.random.key(0), interpret=True)
    back = dequantize_int8(vals, scales, d, interpret=True)
    assert back.shape == (d,)
    assert float(jnp.abs(back - x).max()) <= float(jnp.abs(x).max()) / 127 + 1e-6
