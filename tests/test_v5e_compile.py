"""The stacked mean's Pallas kernel compiled for a described TPU v5e, at the
widths and cohorts the benchmark's cells run: what Mosaic refuses (a block
off the tiling, too much VMEM) the interpreter accepts, and this costs no
chip time. Nothing runs: no result and no time comes from here.

The topology is described inside a fixture, never at import: only the
worker that is given this file may load the TPU's library."""

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
