"""What the gated delta rules of a round have to do (``ops/gated_delta.py``;
Gated DeltaNet's ``S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - S' k_t))'``,
``o_t = S_t' q_t``, one scalar decay and write strength a value head and
step, ``value_heads / key_heads`` value heads reading each key head),
whatever implements them.

Operations, of the chunked WY form at a fixed chunk of ``CHUNK`` positions
(whatever chunk the program runs, so that the share reads the same work), a
chunk and a layer forward:

* once a key head: ``K K'`` and ``Q K'``, ``2 C C dk`` each;
* once a value head: the inverse of the unit lower-triangular ``I + L``
  (``C^3 / 3``, the count of a triangular inversion); ``T V`` and ``T K``
  (``2 C C dv``, ``2 C C dk``); ``W S``, ``Q S`` and ``K' V'`` (``2 C dk dv``
  each); the masked scores applied to ``V'`` (``2 C C dv``).

The whole ``C x C`` blocks are billed, not their triangles - as the matrix
unit computes them. The backward pass has two products for each: three times
that.

Bytes, for an ideal kernel that keeps the state and a chunk's blocks on the
chip, float32: forward it reads ``q`` and ``k`` (``dk`` floats a token and
*key* head each), ``v`` (``dv`` a value head), ``g`` and ``beta`` (one each a
value head) and writes ``o`` (``dv`` a value head); backward it reads those
and ``do`` and writes the five gradients. The convolution, the norms, the
gates and the projections are left out on both sides of the share: the
operations the metric times are the recurrence's. Rematerialisation is what
an implementation adds.
"""

#: the chunk the operations are billed at: the published kernels'
CHUNK = 64


def cost(tokens: float, key_heads: int, value_heads: int, dk: int, dv: int,
         layers: int):
    """(floating-point operations, bytes to and from HBM) of the gated
    delta rules of ``tokens`` positions in ``layers`` layers, forward and
    backward."""
    c = CHUNK
    per_key_head = 4.0 * c * dk                  # K K', Q K' a token
    per_value_head = (c * c / 3.0                # the triangular inverse
                      + 2.0 * c * dv + 2.0 * c * dk   # T V, T K
                      + 6.0 * dk * dv            # W S, Q S, K' V'
                      + 2.0 * c * dv)            # the scores applied to V'
    forward = key_heads * per_key_head + value_heads * per_value_head
    read = 2 * key_heads * dk + value_heads * (dv + 2)  # q, k, v, g, beta
    floats = ((read + value_heads * dv)          # forward: those in, o out
              + (read + value_heads * dv)        # backward: those and do in
              + read)                            # the five gradients out
    return 3.0 * forward * tokens * layers, 4.0 * floats * tokens * layers
