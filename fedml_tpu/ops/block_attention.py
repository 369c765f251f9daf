"""Causal softmax attention in blocks of queries, in XLA, for training.

For a block of queries only the keys it may see are read: everything up to
the block's last position, or with a sliding ``window`` only the keys at
most ``window - 1`` positions behind the block's first query. The slices
are static, so a windowed layer never touches the key blocks its window
excludes, and the scores of one block ``[heads, block, keys]`` are the
largest tensor there is; each block is rematerialised, so the backward
pass recomputes them block by block.

Grouped queries: ``q`` has ``G`` times the heads of ``k`` and ``v``, and
query head ``j`` reads key/value head ``j // G``. The softmax runs in
float32; the two matrix products run at the backend's default precision.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _block(q, k, v, q0: int, k0: int, window: Optional[int], scale: float):
    """Queries ``q [Hk, G, tq, D]`` at positions ``q0..`` against keys
    ``k [Hk, tk, D]`` at positions ``k0..``."""
    scores = jnp.einsum("hgqd,hkd->hgqk", q, k) * scale
    q_pos = q0 + jnp.arange(q.shape[2])[:, None]
    k_pos = k0 + jnp.arange(k.shape[1])[None, :]
    seen = k_pos <= q_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    scores = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hgqk,hkd->hgqd", probs, v)


def causal_attention(q, k, v, *, scale: float, window: Optional[int] = None,
                     block: int = 512):
    """``q [Hq, T, D]``, ``k [Hk, T, D]``, ``v [Hk, T, Dv]`` ->
    ``[Hq, T, Dv]``. A query at ``t`` sees the keys ``j <= t``, and with
    ``window`` only those with ``t - j < window`` (``window`` positions,
    its own among them)."""
    heads, length, _ = q.shape
    kv_heads = k.shape[0]
    q = q.reshape((kv_heads, heads // kv_heads) + q.shape[1:])
    out = []
    for q0 in range(0, length, block):
        q1 = min(q0 + block, length)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        fn = jax.checkpoint(_block, static_argnums=(3, 4, 5, 6))
        out.append(fn(q[:, :, q0:q1], k[:, k0:q1], v[:, k0:q1], q0, k0,
                      window, scale))
    out = jnp.concatenate(out, axis=2)
    return out.reshape((heads,) + out.shape[2:])
