"""Delta compression for the cross-silo wire: int8 and top-k + EF payloads.

The reference ships every model update at full precision (pickled tensors
over MPI, mpi_send_thread.py:27; JSON float lists over MQTT,
fedavg/utils.py:12). Here two payload families compress the DELTA against a
base model both ends hold:

- ``delta_int8`` — int8 block-scaled quantization of the full delta (4x)
  using the Pallas kernels (fedml_tpu/ops/quantize.py). Stochastic rounding
  keeps the quantizer unbiased, so the server's weighted mean of dequantized
  deltas is an unbiased estimate of the uncompressed aggregate.
- ``topk_ef`` / ``topk_ef_int8`` — magnitude top-k sparsification of the
  delta (ops/sparsify.py), optionally int8-quantizing the survivors
  (~10-50x smaller at 1-5% keep fractions). Top-k is biased: callers MUST
  run the error-feedback loop — :func:`compress_topk` returns the un-sent
  residual, and the caller adds it to the next round's delta.

Wire format: a plain dict of arrays/ints (codec-friendly — no treedefs on
the wire). Both ends hold the same model structure: the sender compresses
against a base the receiver also holds (the round's broadcast for uplink,
the silo mirror for downlink), and the receiver rebuilds against its copy.
This only holds for ROUND-based servers (plain + quorum, where stale
replies are dropped); the FedAsync server moves the global model every
update, so its base would drift — the async server enforces full precision
with a loud guard (algorithms/fedavg_async.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np

from fedml_tpu.core import pytree as pt
from fedml_tpu.ops.quantize import dequantize_tree, quantize_tree
from fedml_tpu.ops.sparsify import (k_for, topk_densify, topk_dequantize,
                                    topk_quantize, topk_quantize_donated,
                                    topk_sparsify, topk_sparsify_donated)
from fedml_tpu.utils import on_tpu

COMPRESSED_FLAG = "__delta_int8__"
TOPK_FLAG = "__topk_ef__"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    # compiled kernels on tpu, the Pallas interpreter on cpu (utils.on_tpu)
    if interpret is None:
        return not on_tpu()
    return interpret


def _tree_size(tree) -> int:
    return sum(int(np.prod(l.shape)) if l.shape else 1
               for l in jax.tree.leaves(tree))


def _tree_fingerprint(tree) -> str:
    """Cheap structure hash over the leaf (path, shape, dtype) list.

    A total-parameter-count check alone admits any version skew that
    preserves the count (transposed layer, swapped widths) and silently
    corrupts the rebuilt aggregate; the fingerprint rejects it."""
    import hashlib

    parts = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        # metadata only — leaf.dtype avoids a device->host copy of the tree
        dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        parts.append(f"{jax.tree_util.keystr(path)}:"
                     f"{tuple(leaf.shape)}:{np.dtype(dtype).name}")
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


#: public name — the cross-silo managers exchange base fingerprints on the
#: wire (silo replies report what they hold; the server's downlink falls
#: back to full precision on mismatch)
tree_fingerprint = _tree_fingerprint


def compress_delta(new_tree, base_tree, key,
                   interpret: Optional[bool] = None) -> Dict[str, Any]:
    """int8-quantize (new - base); returns a codec-friendly payload dict
    (no treedef on the wire — the receiver rebuilds against its own base)."""
    delta = pt.tree_sub(new_tree, base_tree)
    vals, scales, _spec = quantize_tree(delta,
                                        key,
                                        interpret=_resolve_interpret(
                                            interpret))
    return {COMPRESSED_FLAG: True, "q": np.asarray(vals),
            "s": np.asarray(scales), "d": _tree_size(delta),
            "fp": _tree_fingerprint(base_tree)}


def decompress_delta(payload: Dict[str, Any], base_tree,
                     interpret: Optional[bool] = None):
    """Rebuild the full model: base + dequantized delta (leaf order/shapes
    from the receiver's own base_tree)."""
    import jax.numpy as jnp
    # count can survive version skew (transposed layer, swapped widths);
    # the structure fingerprint cannot — _check_base guards both
    expected = _check_base(payload, base_tree)
    leaves, treedef = jax.tree.flatten(base_tree)
    spec = (treedef, [(l.shape, np.asarray(l).dtype.name) for l in leaves],
            expected)
    delta = dequantize_tree(jnp.asarray(payload["q"]),
                            jnp.asarray(payload["s"]), spec,
                            interpret=_resolve_interpret(interpret))
    return pt.tree_add(base_tree, delta)


def _flatten_tree(tree):
    """Concatenate leaves to the flat f32 layout ``quantize_tree`` uses."""
    import jax.numpy as jnp
    return jnp.concatenate([jnp.asarray(l).reshape(-1).astype(jnp.float32)
                            for l in jax.tree.leaves(tree)])


def _unflatten_like(flat, base_tree):
    """Inverse of :func:`_flatten_tree` against ``base_tree``'s structure
    (leaf order/shapes/dtypes from the receiver's own copy)."""
    import jax.numpy as jnp
    leaves, treedef = jax.tree.flatten(base_tree)
    out, off = [], 0
    for l in leaves:
        size = int(np.prod(l.shape)) if np.shape(l) else 1
        out.append(jnp.reshape(flat[off:off + size], np.shape(l)).astype(
            np.asarray(l).dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def _check_base(payload: Dict[str, Any], base_tree) -> int:
    """Shared skew guards: parameter count + structure fingerprint."""
    expected = _tree_size(base_tree)
    if int(payload["d"]) != expected:
        raise ValueError(
            f"compressed delta carries {payload['d']} parameters but the "
            f"receiver's model has {expected} — model-version skew or a "
            "malformed payload; refusing to rebuild")
    if "fp" in payload:
        fp = _tree_fingerprint(base_tree)
        if payload["fp"] != fp:
            raise ValueError(
                f"compressed delta structure fingerprint {payload['fp']} "
                f"does not match the receiver's model ({fp}) — the sender "
                "trained a differently-shaped tree; refusing to rebuild")
    return expected


def compress_topk(new_tree, base_tree, residual, key, *,
                  frac: float = 0.01, quantize: bool = True,
                  interpret: Optional[bool] = None):
    """Top-k (+ optional int8) compress ``(new - base) + residual``.

    Returns ``(payload, new_residual)``: the codec-friendly payload dict
    and the flat f32 error-feedback residual the caller must carry into
    the NEXT call (pass ``None`` the first round). Dropping the residual
    turns the biased top-k into plain (non-converging) truncation.
    """
    import jax.numpy as jnp
    interpret = _resolve_interpret(interpret)
    flat = _flatten_tree(pt.tree_sub(new_tree, base_tree))
    d = int(flat.size)
    if residual is not None:
        flat = flat + jnp.asarray(residual, jnp.float32)
    k = k_for(d, frac)
    payload: Dict[str, Any] = {TOPK_FLAG: True, "d": d,
                               "fp": _tree_fingerprint(base_tree)}
    # `flat` is a freshly built temporary at this point (concat of leaf
    # casts, plus the EF add) — donate it so the residual output aliases
    # its memory on tpu/gpu. Bit-exact with the undonated kernels and the
    # numpy oracle (topk_sparsify_reference); the parity tests pin that.
    if quantize:
        idx, q, scales, res = topk_quantize_donated(flat, key, k,
                                                    interpret=interpret)
        payload.update(i=np.asarray(idx), q=np.asarray(q),
                       s=np.asarray(scales))
    else:
        idx, vals, res = topk_sparsify_donated(flat, k)
        payload.update(i=np.asarray(idx), v=np.asarray(vals))
    return payload, np.asarray(res)


def decompress_topk(payload: Dict[str, Any], base_tree,
                    interpret: Optional[bool] = None):
    """Rebuild the full model from a :func:`compress_topk` payload:
    base + densified sparse delta."""
    import jax.numpy as jnp
    d = _check_base(payload, base_tree)
    idx = np.asarray(payload["i"])
    if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= d):
        # the jnp scatter would silently drop/clamp out-of-bounds
        # indices — a corrupted frame must refuse loudly like every
        # other malformed-payload path in this module
        raise ValueError(
            f"top-k payload carries indices outside [0, {d}) — corrupted "
            "or malformed frame; refusing to rebuild")
    if "q" in payload:
        dense = topk_dequantize(jnp.asarray(payload["i"]),
                                jnp.asarray(payload["q"]),
                                jnp.asarray(payload["s"]), d,
                                interpret=_resolve_interpret(interpret))
    else:
        dense = topk_densify(jnp.asarray(payload["i"]),
                             jnp.asarray(payload["v"]), d)
    return pt.tree_add(base_tree, _unflatten_like(dense, base_tree))


def decompress(payload: Dict[str, Any], base_tree,
               interpret: Optional[bool] = None):
    """Rebuild any compressed payload family against ``base_tree``."""
    if payload.get(TOPK_FLAG):
        return decompress_topk(payload, base_tree, interpret=interpret)
    return decompress_delta(payload, base_tree, interpret=interpret)


def compress_for_policy(new_tree, base_tree, residual, key, policy,
                        interpret: Optional[bool] = None):
    """Encode ``new_tree`` against ``base_tree`` per a CompressionPolicy
    (comm/policy.py). Returns ``(payload, new_residual)`` — residual is
    ``None`` for the non-top-k policies (no error feedback needed: int8
    stochastic rounding is unbiased)."""
    if policy.uplink_topk:
        return compress_topk(new_tree, base_tree, residual, key,
                             frac=policy.topk_frac,
                             quantize=policy.uplink_int8,
                             interpret=interpret)
    if policy.name == "delta_int8":
        return compress_delta(new_tree, base_tree, key,
                              interpret=interpret), None
    return jax.tree.map(np.asarray, new_tree), None


def is_compressed(payload) -> bool:
    return isinstance(payload, dict) and bool(
        payload.get(COMPRESSED_FLAG) or payload.get(TOPK_FLAG))


def wire_bytes(payload) -> int:
    """TRUE payload size on the wire: the encoded frame length, exactly
    what the transport ships (header, scalars, and framing included —
    summing only ndarray values under-reported every ratio)."""
    from fedml_tpu.comm import serialization
    return sum(len(p) for p in serialization.dumps_parts(payload))
