"""Pallas TPU kernels (and two XLA operators) for the framework's hot ops.

The reference's server hot path is a host-side Python loop over ``state_dict``
keys (reference: fedml_api/distributed/fedavg/FedAVGAggregator.py:58-87) and
its comm payloads are full-precision pickled tensors (reference:
fedml_core/distributed/communication/mpi/mpi_send_thread.py:27). Here the two
corresponding device-side primitives are hand-tiled Pallas kernels:

- :mod:`fedml_tpu.ops.aggregate` — sample-weighted client aggregation (the
  FedAvg server rule) leaf by leaf in the leaf's own shape: the mean of a
  stacked cohort, or a client folded in place into a running sum.
- :mod:`fedml_tpu.ops.quantize` — int8 block-scaled quantization with
  stochastic rounding for cross-silo model-delta compression.
- :mod:`fedml_tpu.ops.flash_attention` — streaming-softmax attention for
  the transformer path (VMEM-blocked K/V, causal block skipping), with a
  blockwise custom VJP.
- :mod:`fedml_tpu.ops.autotune` — shape-aware selection between the
  Pallas kernel's (block_q, block_k) grid and the XLA reference
  attention, memoized in an on-disk per-device-kind cache so neither
  tuning nor a losing kernel is ever paid twice.

- :mod:`fedml_tpu.ops.moe` — routed gated experts without drops for the
  experts one chip holds (XLA: blocks of sorted rows through ``dot_general``,
  the backward pass written out); :mod:`fedml_tpu.ops.selective_scan`
  (Mamba-1, elementwise), :mod:`fedml_tpu.ops.ssd` (Mamba-2 in its chunked
  dual form: matrix products inside chunks, a scan over chunk states) and
  :mod:`fedml_tpu.ops.block_attention` are XLA too.

Every kernel has an ``interpret=True`` path so the math is testable on the
CPU mesh, and a pure-jnp reference used both as the CPU fallback and as the
test oracle.
"""

from fedml_tpu.ops.aggregate import (fold_weighted, mean_kernel_params,
                                     tree_fold_pallas,
                                     tree_weighted_mean_pallas,
                                     weighted_mean_flat_reference)
from fedml_tpu.ops.autotune import (AttentionDecision, AutotuneCache,
                                    autotune_attention,
                                    make_autotuned_attention)
from fedml_tpu.ops.flash_attention import (flash_attention,
                                           make_flash_attention)
from fedml_tpu.ops.moe import routed_experts
from fedml_tpu.ops.quantize import (dequantize_int8, dequantize_tree,
                                    quantize_int8, quantize_tree)

__all__ = [
    "weighted_mean_flat_reference",
    "tree_weighted_mean_pallas",
    "mean_kernel_params",
    "fold_weighted",
    "tree_fold_pallas",
    "quantize_int8",
    "dequantize_int8",
    "quantize_tree",
    "dequantize_tree",
    "flash_attention",
    "make_flash_attention",
    "routed_experts",
    "AttentionDecision",
    "AutotuneCache",
    "autotune_attention",
    "make_autotuned_attention",
]
