"""The stacked mean's Pallas kernel compiled for a described TPU v5e, at the
widths and cohorts the benchmark's cells run: what Mosaic refuses (a block
off the tiling, too much VMEM) the interpreter accepts, and this costs no
chip time. And the sim round of ResNet-18-GN, for the passes over dead
convolution taps it must not hold. Nothing runs: no result and no time
comes from here.

The topology is described inside a fixture, never at import: only the
worker that is given this file may load the TPU's library."""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fedml_tpu.ops.aggregate import tree_weighted_mean_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        for name, value in (("TPU_LOG_DIR", "disabled"),
                            ("TPU_SKIP_MDS_QUERY", "1"),
                            ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                            ("TPU_WORKER_HOSTNAMES", "localhost")):
            env.setenv(name, value)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


# ResNet-18-GN's kernel-side leaves at dense's cohort, the CNN's at the CNN
# cells', and a cohort whose block is the smallest the rule allows
@pytest.mark.parametrize("clients, shape", [
    (104, (3, 3, 512, 512)), (104, (3, 3, 256, 512)), (104, (3, 3, 64, 128)),
    (104, (1, 1, 256, 512)), (104, (1, 1, 64, 128)), (256, (9216, 128)),
    (256, (3, 3, 512, 512)), (1024, (520, 384))])
def test_the_mean_kernel_compiles_for_a_v5e(one_chip, clients, shape):
    leaf = jax.ShapeDtypeStruct((clients,) + shape, jnp.float32,
                                sharding=one_chip)
    weights = jax.ShapeDtypeStruct((clients,), jnp.float32,
                                   sharding=one_chip)
    text = jax.jit(tree_weighted_mean_pallas).lower(
        leaf, weights).compile().as_text()
    assert "tpu_custom_call" in text


def test_the_resnet_round_makes_no_pass_over_dead_taps(one_chip):
    """The published ResNet-18-GN's sim round at 24x24 crops and a cohort of
    8, as the driver assembles it on a TPU: the last stage runs on a 1x1
    map, so its 3x3 kernels are read through their live window
    (``models/common.py::LiveTapConv``) and the program holds no bf16 copy
    and no ``reverse`` of a whole ``[3, 3, 512, 512]`` kernel (the parent's
    held nine such passes, 490 MB each at the cell's cohort of 104). The
    local loop carries the windows alone (``make_local_train``): the SGD
    update runs at ``[1, 1, 512, 512]`` a client, nothing adds to, selects
    or copies the cohort's whole kernels, and each is written once, the
    trained window into the broadcast global kernel, on its way to the
    mean. Stage 3's ``[3, 3, 256, 256]`` kernels run on a 2x2 map, every
    tap live: their re-layouts rightly stay."""
    from fedml_tpu.algorithms.fedavg import make_vmapped_body
    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.functional import TrainConfig, make_local_train

    cohort, rows = 8, 40
    module = create_model("resnet18_gn", output_dim=100, small_images=False)
    body = make_vmapped_body(make_local_train(
        module, "classification", TrainConfig(epochs=1, batch_size=20,
                                              lr=0.1)))

    def round_fn(variables, x, y, mask, keys, weights):
        stacked, totals = body(variables, x, y, mask, keys, None)
        return tree_weighted_mean_pallas(stacked, weights), totals

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 24, 24, 3)), train=False))
    key = jax.eval_shape(lambda: jax.random.key(0))
    text = jax.jit(round_fn, donate_argnums=(0,)).lower(
        jax.tree.map(lambda a: arg(a.shape, a.dtype), variables),
        arg((cohort, rows, 24, 24, 3), jnp.float32),
        arg((cohort, rows), jnp.int32), arg((cohort, rows), jnp.float32),
        arg((cohort,), key.dtype), arg((cohort,), jnp.float32)
    ).compile().as_text()

    def kernels(pattern, width, taps=3):
        """Result shapes matching ``pattern`` that hold the cohort's
        ``taps`` x ``taps`` kernels of ``width`` x ``width``, in any order
        of dimensions."""
        return [m for m in re.findall(pattern, text)
                if sorted(int(d) for d in m.split(","))
                == sorted([taps, taps, cohort, width, width])]

    def op(name):
        return r"= f32\[([0-9,]+)\]\S* %s\(" % name

    assert "tpu_custom_call" in text
    reverse = r"= \w+\[([0-9,]+)\]\S* reverse\("
    for passing in ("add", "select", "copy", "multiply", "pad"):
        assert not kernels(op(passing), 512), passing
    assert len(kernels(op("add"), 512, taps=1)) == 3  # the update's
    # the one write of each whole kernel: the vmapped dynamic_update_slice
    # (a scatter in the jaxpr) over the broadcast global kernel
    assert len(kernels(op("dynamic-update-slice"), 512)) == 3
    assert len(kernels(op("broadcast"), 512)) == 3
    assert not kernels(op("scatter"), 512)
    assert not kernels(reverse, 512)
    assert not kernels(r"bf16\[([0-9,]+)\]", 512)
    assert kernels(reverse, 256)


def test_the_routed_experts_are_xla_and_ragged_dot_would_not_be(one_chip):
    """``ops/moe.py`` at the widths of ``lfm2_8b_a1b_ep4.silo4`` (4,096
    tokens, top-4 of 32, 8 experts of 2048 x 1792 held), forward and
    backward: the TPU compiler takes the loops and makes no custom call of
    them - the benchmark books every ``tpu_custom_call`` of the round as
    aggregation - where it lowers ``jax.lax.ragged_dot`` to one."""
    from fedml_tpu.ops.moe import routed_experts

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(s, router, bias, w1, w3, w2):
        y, load = routed_experts(s, router, bias, w1, w3, w2, top_k=4,
                                 experts_held=(0, 8))
        return jnp.sum(y * y), load

    text = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 3, 4, 5), has_aux=True)).lower(
            arg(4096, 2048), arg(2048, 32), arg(32), arg(8, 2048, 1792),
            arg(8, 2048, 1792), arg(8, 1792, 2048)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "while" in text and re.search(r"f32\[8,2048,1792\]", text)
    ragged = jax.jit(jax.lax.ragged_dot).lower(
        arg(16384, 2048), arg(8, 2048, 1792),
        arg(8, dtype=jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in ragged


def test_the_chunked_ssd_scan_is_xla_on_a_v5e(one_chip):
    """``ops/ssd.py`` at the widths of ``granite_4_0_h_micro_10l.silo4`` (a
    row of 2,048 tokens, 64 heads of 64, a state of 128, one group, chunks
    of 256), forward and backward: the TPU compiler takes the scan over the
    chunks and its products and makes no custom call of them - the benchmark
    books every ``tpu_custom_call`` of the round as aggregation - and the
    program holds one chunk's ``[64, 256, 256]`` decay matrices, never the
    row's eight."""
    from fedml_tpu.ops.ssd import ssd_scan

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(xs, dt, a, b, c):
        y = ssd_scan(xs, dt, a, b, c, chunk=256)
        return jnp.sum(y * y)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg(2048, 64, 64), arg(2048, 64), arg(64), arg(2048, 1, 128),
        arg(2048, 1, 128)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert re.search(r"f32\[64,256,256\]", text)
    assert re.search(r"f32\[(1,)?64,64,128\]", text)
    assert not re.search(r"\[8,64,256,256\]", text)
    # the patterns of benchmark/metrics/ssd_ms.json name these tensors
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "metrics",
            "ssd_ms.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    named = [line for line in text.splitlines()
             if " = " in line and pattern.search(line)]
    assert any(" convolution(" in line or " fusion(" in line
               for line in named)


def test_the_latent_attention_and_the_sparse_layer_are_xla_on_a_v5e(one_chip):
    """One sparse layer of ``models/deepseek_v3.py`` at the widths of
    ``kanana_2_30b_a3b_ep8.silo4`` (a row of 2,048 tokens; 32 heads of 128 +
    64 | 128 over a latent of 512; top-6 of 128, 16 experts of 2048 x 768
    held in blocks of 512 rows, the shared SwiGLU of 1536), forward and
    backward: the TPU compiler makes no custom call of the latent-attention
    block, the routed or the shared experts - the benchmark books every
    ``tpu_custom_call`` of the round as aggregation. The experts' width
    names their tensors for ``benchmark/metrics/small_expert_ms.json``."""
    from fedml_tpu.models import create_model, deepseek_v3

    module = create_model("deepseek_v3", output_dim=16032,
                          experts_held=(0, 16), layer_ids=(1,))
    cfg = module.cfg()
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes["params"]["layer_01"])

    def loss(p, x):
        y, load = deepseek_v3._layer(p, x, dense=False, cfg=cfg)
        return jnp.sum(y * y), load

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                   ).lower(leaves, jax.ShapeDtypeStruct(
                       (1, 2048, 2048), jnp.float32, sharding=one_chip)
                   ).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "while" in text and re.search(r"f32\[16,2048,768\]", text)
    # a block of queries against the keys it may see, every head
    assert re.search(r"\[32,(1,)?512,(512|1024|1536|2048)\]", text)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "metrics",
            "small_expert_ms.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    named = [line for line in text.splitlines()
             if " = " in line and pattern.search(line)]
    assert any(" convolution(" in line or " fusion(" in line
               for line in named)
    # the shared experts and the attention's widths are not the pattern's
    for shape in ("f32[2048,1536]", "f32[2048,576]", "f32[32,2048,192]",
                  "f32[2048,6144]", "f32[512,8192]", "f32[2048,128]"):
        assert not pattern.search(shape), shape
