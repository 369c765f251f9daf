"""What the attention cores of a round have to do where the heads' keys and
values differ in size (latent attention in its decompressed, training form:
``softmax(q k' * scale + causal mask) v`` a head, queries and keys of
``qk_dim``, values of ``v_dim``), whatever implements them.

Operations, the **causal half** only: a row of ``T`` positions has ``T (T +
1) / 2`` (query, key) pairs a query may see, each ``2 x qk_dim`` for its
score and ``2 x v_dim`` for its part of the output, every head; the backward
pass has two products for each: three times that. The masked half, which a
blockwise implementation computes on its diagonal blocks, is not billed.

Bytes, for an ideal kernel that never writes the scores, float32: forward it
reads ``q``, ``k`` (``qk_dim`` floats a token and head each) and ``v``
(``v_dim``) and writes ``o`` (``v_dim``); backward it reads those four and
``do`` and writes the three gradients. The projections, the rope, the
latent's norm and the up-projection are left out on both sides of the share:
the operations the metric times are the core's. Rematerialisation is what an
implementation adds.
"""


def cost(tokens: float, row_length: int, heads: int, qk_dim: int, v_dim: int,
         layers: int):
    """(floating-point operations, bytes to and from HBM) of the attention
    cores of ``tokens`` positions in rows of ``row_length`` through
    ``layers`` layers, forward and backward."""
    rows = tokens / row_length
    seen = row_length * (row_length + 1) / 2.0
    forward = seen * 2.0 * (qk_dim + v_dim) * heads
    floats = ((2 * qk_dim + 2 * v_dim)      # forward: q, k, v in, o out
              + (2 * qk_dim + 3 * v_dim)    # backward: those and do in
              + (2 * qk_dim + v_dim))       # the three gradients out
    return (3.0 * forward * rows * layers,
            4.0 * floats * heads * tokens * layers)
