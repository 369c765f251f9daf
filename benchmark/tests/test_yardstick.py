"""The yardstick's arithmetic: FLOP counts, peaks, kernel costs, the
federation generator, the model factory, the cohort sampler."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness import flops, peaks, spec

ROOT = spec.ROOT
federation = spec.load_module(os.path.join(
    ROOT, "benchmark", "generators", "class_images.py"))


def config(name):
    return spec.load_json(os.path.join(ROOT, "benchmark", "configs",
                                       name + ".json"))


def test_flops_multiply_scan_lengths_and_count_dots_exactly():
    def body(carry, x):
        return carry @ x, ()

    def fn(a, xs):
        return jax.lax.scan(body, a, xs)[0]

    a, xs = jnp.ones((8, 16)), jnp.ones((5, 16, 16))
    assert flops.count(fn, a, xs) == 5 * 2 * 8 * 16 * 16
    conv = lambda x, k: jax.lax.conv_general_dilated(  # noqa: E731
        x, k, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x, k = jnp.ones((2, 10, 10, 3)), jnp.ones((3, 3, 3, 7))
    assert flops.count(conv, x, k) == 2 * (2 * 8 * 8 * 7) * 3 * 9


def test_flops_refuse_a_loop_without_a_trip_count():
    with pytest.raises(ValueError, match="trip count"):
        flops.count(lambda x: jax.lax.while_loop(
            lambda v: v[0, 0] < 3, lambda v: v @ v, x), jnp.ones((2, 2)))


@pytest.mark.parametrize("name, kwargs, want", [
    ("femnist_cnn", None, 75.3e6),
    ("fedcifar100_resnet18gn", {"small_images": True}, 2.085e9),
    ("fedcifar100_resnet18gn", None, 182.1e6)])
def test_flops_per_row_reproduce_the_traced_figures(name, kwargs, want):
    """Within 1 % of the figures ISSUE 22 traced from the program's round
    (75.3 MFLOP a packed row for the CNN, 2.085 GFLOP for ResNet-18-GN with
    the repo's 3x3 stem), and of the published 7x7 stem's 182.1 MFLOP - the
    configuration as it is run."""
    cfg = config(name)
    data = cfg["data"]
    image = (1, data["image_hw"], data["image_hw"], data["channels"])
    published = kwargs is None
    if not published:
        cfg["model"]["kwargs"] = kwargs
    module = cell_mod.make_model(cfg)
    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros(image), train=False))
    variables = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), variables)
    reference = spec.load_module(spec.find_file(
        ["benchmark"], "references", cfg["reference"], ".py"))
    got = reference.flops_per_row(module, cfg["model"]["task"], cfg["train"],
                                  variables, np.zeros(image), flops.count)
    assert got == pytest.approx(want, rel=0.01)
    if published:
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(variables)
                   ) == cfg["model"]["parameters"]


def test_the_model_is_the_one_the_source_publishes():
    """fed-CIFAR100's ResNet-18 keeps the 7x7 stride-2 stem and the max-pool
    of FedML's ``resnet_gn.py``: 11,227,812 parameters at 100 classes, not
    the 11,220,132 of the repo's 3x3 stem; the model's arguments come from
    the configuration file."""
    cfg = config("fedcifar100_resnet18gn")
    module = cell_mod.make_model(cfg)
    assert module.small_images is False and module.num_classes == 100
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 24, 24, 3)), train=False))
    stem = shapes["params"]["Conv_0"]["kernel"].shape
    assert stem == (7, 7, 3, 64)
    assert cfg["model"]["parameters"] == 11227812
    assert cfg["reduced"] == []


def test_peaks_know_the_v5e_and_refuse_what_they_do_not_know():
    path = os.path.join(ROOT, "benchmark", "peaks.json")
    v5e = peaks.lookup(path, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v5"):
        peaks.lookup(path, "TPU v5")  # no substring matching
    with pytest.raises(KeyError):
        peaks.lookup(path, "cpu")


def test_wmean_kernel_cost():
    wmean = spec.load_module(os.path.join(ROOT, "benchmark", "kernels",
                                          "wmean.py"))
    ops, nbytes = wmean.cost(80, 11220132)
    assert ops == 2.0 * 80 * 11220132
    assert nbytes == 4.0 * (80 * 11220132 + 11220132 + 80)


def test_federation_is_a_function_of_the_seed():
    data = spec.load_json(os.path.join(
        ROOT, "benchmark", "tests", "fixture", "configs", "tiny_cnn.json")
    )["data"]
    a, na = federation.build(data, 12, 7)
    b, nb = federation.build(data, 12, 7)
    c, nc = federation.build(data, 12, 8)
    assert np.array_equal(na, nb)
    assert np.array_equal(a.train_data_global[0], b.train_data_global[0])
    assert np.array_equal(a.test_data_global[1], b.test_data_global[1])
    # another seed: other content, other owners of the sizes, but the same
    # multiset of sizes, so the same totals and the same compiled shapes
    assert not np.array_equal(a.train_data_global[0], c.train_data_global[0])
    assert sorted(na) == sorted(nc)
    assert a.train_data_num == c.train_data_num == int(na.sum())
    assert a.test_data_num == c.test_data_num
    # every client's shard is a view of the union, in client order
    x5, y5 = a.train_data_local_dict[5]
    lo = int(na[:5].sum())
    assert np.shares_memory(x5, a.train_data_global[0])
    assert np.array_equal(y5, a.train_data_global[1][lo:lo + na[5]])
    assert x5.dtype == np.float32 and 0.0 <= x5.min() and x5.max() <= 1.0


def test_reference_scale_sizes_keep_the_published_shape():
    fem = federation.client_sizes(config("femnist_cnn")["data"]["sizes"],
                                  3400, np.random.default_rng(0))
    assert fem.min() >= 20 and fem.max() == 400
    assert 140 <= np.median(fem) <= 170  # LEAF-like: median about 150
    cif = federation.client_sizes(
        config("fedcifar100_resnet18gn")["data"]["sizes"], 500,
        np.random.default_rng(0))
    assert set(cif) == {100}


def test_cohorts_are_the_reference_sampling_contract():
    from fedml_tpu.core.sampling import sample_clients

    for r in (0, 1, 17):
        assert np.array_equal(cell_mod.sample_cohort(r, 500, 80),
                              sample_clients(r, 500, 80))
    assert np.array_equal(cell_mod.sample_cohort(3, 16, 16), np.arange(16))
    # a smaller draw of the same round is a prefix of the larger
    assert np.array_equal(cell_mod.sample_cohort(0, 500, 8),
                          cell_mod.sample_cohort(0, 500, 80)[:8])
