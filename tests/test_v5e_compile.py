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
        y, load, _ = routed_experts(s, router, bias, w1, w3, w2, top_k=4,
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


def test_the_latent_attention_core_is_the_kernel_and_the_experts_xla(
        one_chip, monkeypatch):
    """One sparse layer of ``models/deepseek_v3.py`` at the widths of
    ``kanana_2_30b_a3b_ep8.silo4`` (a row of 2,048 tokens; 32 heads of 128 +
    64 | 128 over a latent of 512; top-6 of 128, 16 experts of 2048 x 768
    held in blocks of 512 rows, the shared SwiGLU of 1536), forward and
    backward, as the chip runs it: the layer's Pallas calls are exactly the
    core's forward, dK/dV and dQ kernels, each under ``fedml.mla_core`` in
    its ``op_name`` and in the scope map's chain - with the XLA reduction
    beside them - so ``mla_core_roofline`` reads the whole core; no score
    block is left in HBM; the routed and the shared experts stay XLA, and
    the experts' width names their tensors alone for
    ``benchmark/metrics/small_expert_ms.json``."""
    from fedml_tpu.models import create_model, deepseek_v3
    from fedml_tpu.utils.tracing import parse_hlo_scopes

    # the core's backend rule answers for the described chip (a CPU here)
    monkeypatch.setattr(deepseek_v3, "on_tpu", lambda: True)
    module = create_model("deepseek_v3", output_dim=16032,
                          experts_held=(0, 16), layer_ids=(1,))
    cfg = module.cfg()
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes["params"]["layer_01"])

    def loss(p, x):
        y, load, _ = deepseek_v3._layer(p, x, dense=False, cfg=cfg)
        return jnp.sum(y * y), load

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                   ).lower(leaves, jax.ShapeDtypeStruct(
                       (1, 2048, 2048), jnp.float32, sharding=one_chip)
                   ).compile().as_text()
    _, scopes = parse_hlo_scopes(text)
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and " = " in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%")
            calls[name] = line
    # forward (out, lse), dK/dV, dQ: 32 heads of 192 | 128
    assert sorted(scopes.kinds[n] for n in calls) == sorted([
        "(f32[32,2048,128], f32[32,2048,1]) custom-call",
        "(f32[32,2048,192], f32[32,2048,128]) custom-call",
        "f32[32,2048,192] custom-call"])
    for name, line in calls.items():
        assert "/fedml.mla_core/" in re.search(
            r'op_name="([^"]*)"', line).group(1), name
        assert scopes.chains[name] == ("fedml.mla", "fedml.mla_core"), name
    # rowsum(dO * O), read by both backward kernels
    assert any(scopes.chains[n] == ("fedml.mla", "fedml.mla_core")
               and scopes.kinds[n] == "f32[32,2048] fusion"
               for n in scopes.chains)
    assert not re.search(r"\[32,(1,)?512,(512|1024|1536|2048)\]", text)
    assert "while" in text and re.search(r"f32\[16,2048,768\]", text)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "metrics",
            "small_expert_ms.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    named = [line for line in text.splitlines()
             if " = " in line and pattern.search(line)]
    assert any(" convolution(" in line or " fusion(" in line
               for line in named)
    assert not any(name in calls for name in (
        line.split(" = ")[0].split()[-1].lstrip("%") for line in named))
    # the shared experts and the attention's widths are not the pattern's
    for shape in ("f32[2048,1536]", "f32[2048,576]", "f32[32,2048,192]",
                  "f32[2048,6144]", "f32[512,8192]", "f32[2048,128]"):
        assert not pattern.search(shape), shape


def test_the_qwen3_next_layers_are_xla_and_name_their_scopes(one_chip):
    """A Gated DeltaNet layer and the gated full-attention layer of
    ``models/qwen3_next.py`` at the widths of
    ``qwen3_next_80b_a3b_ep16.silo4`` (a row of 512 tokens; 16 key heads
    and 32 value heads of 128, chunks of 64; 16 query heads and 2 KV heads
    of 256; top-10 of 512 by softmax, 32 experts of 2048 x 512 held, the
    gated shared expert), forward and backward: the TPU compiler takes the
    chunked delta rule - its unrolled scan, the triangular inverse's
    products - and makes no custom call of it; the recurrence's call, its
    block and the full-attention block are named in the instructions'
    ``op_name`` chains, which ``gated_delta_core_ms``, ``gated_delta_ms``
    and ``gated_attention_ms`` read through the scope map; no chunk's
    output is written into a stack of the row's chunks (``ops/ssd.py``'s
    lesson: unrolled, the scan concatenates)."""
    from fedml_tpu.models import create_model, qwen3_next
    from fedml_tpu.utils.tracing import parse_hlo_scopes

    module = create_model("qwen3_next", output_dim=18992,
                          experts_held=(0, 32), layer_ids=(2, 3))
    cfg = module.cfg()
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        {name: shapes["params"][name] for name in ("layer_02", "layer_03")})

    def loss(p, x):
        x, _, _ = qwen3_next._layer(p["layer_02"], x, full=False, cfg=cfg)
        x, _, _ = qwen3_next._layer(p["layer_03"], x, full=True, cfg=cfg)
        return jnp.sum(x * x)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        leaves, jax.ShapeDtypeStruct((1, 512, 2048), jnp.float32,
                                     sharding=one_chip)).compile().as_text()
    assert "tpu_custom_call" not in text
    _, scopes = parse_hlo_scopes(text)
    chains = set(scopes.chains.values())
    assert ("fedml.gated_delta", "fedml.gated_delta_core") in chains
    for scope in ("fedml.gated_attention", "fedml.moe",
                  "fedml.shared_experts"):
        assert any(scope in chain for chain in chains), scope
    # a chunk's decay matrices and inverse, 16 key heads x 2 value heads
    assert re.search(r"f32\[16,2,64,64\]", text)
    assert not re.search(r"\[8,64,4096\][^ ]* dynamic-update-slice", text)


@pytest.mark.slow
def test_the_qwen3_next_round_fits_and_names_its_scopes(one_chip):
    """The round of ``qwen3_next_80b_a3b_ep16.silo4`` as ``FedAvgAPI``
    folds it on a TPU (``benchmark/tools/compile_fold_v5e.py::lower_round``:
    4 silos, 2 steps of a 2,048-token row, the Pallas fold; published
    widths, layers 0-3, experts 0-31 of 512, 18,992 rows): it fits beside
    the two copies of the model the process also holds (the global model
    and the harness's initial one: 16.9 GB less 2 x 2.50), the delta rule is
    XLA - the only custom calls are the fold's - and the chunked recurrence
    and the full-attention block are named in the instructions' ``op_name``
    chains, which ``gated_delta_core_ms`` and ``gated_attention_ms`` read
    through the scope map. Seven minutes of compilation on the CPU: not
    tier-1."""
    import types

    from benchmark.harness import spec
    from fedml_tpu.utils.tracing import parse_hlo_scopes

    compile_fold_v5e = spec.load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "tools", "compile_fold_v5e.py"))
    cell = spec.load_cell("qwen3_next_80b_a3b_ep16.silo4")
    lowered, params = compile_fold_v5e.lower_round(
        cell, types.SimpleNamespace(devices=list(one_chip.device_set)))
    compiled = lowered.compile()
    assert params == 625_667_136
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak < 16.9e9 - 2 * 4 * params
    text = compiled.as_text()
    _, scopes = parse_hlo_scopes(text)
    chains = set(scopes.chains.values())
    for scope in ("fedml.gated_delta_core", "fedml.gated_attention",
                  "fedml.gated_delta", "fedml.shared_experts", "fedml.moe"):
        assert any(scope in chain for chain in chains), scope
    assert any(chain[-2:] == ("fedml.gated_delta", "fedml.gated_delta_core")
               for chain in chains)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and " = " in line]
    assert calls and all("fedml.fold" in line for line in calls)
