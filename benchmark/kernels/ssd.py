"""What the state-space recurrences of a round have to do (``ops/ssd.py``;
Mamba-2's ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t outer B_t``, ``y_t = S_t
C_t``, one scalar decay a head and step), whatever implements them.

Operations, of the chunked dual form (arXiv:2405.21060), a token and a layer
forward: ``2 Q N G`` (``C B'`` against the ``Q`` positions of the token's
chunk, once a group) ``+ 2 Q P H`` (the masked, decay-weighted scores applied
to the inputs, every head) ``+ 4 N P H`` (the chunk's state built from the
token, and the incoming state read for it); the backward pass has two
products for each: three times that. The whole ``Q x Q`` block is billed, not
its causal half - as the matrix unit computes it.

Bytes, for an ideal kernel that keeps the state ``[H, P, N]`` and a chunk's
decay matrices on the chip, float32: forward it reads ``xs`` (``H P`` floats
a token), ``dt`` (``H``), ``B`` and ``C`` (``G N`` each) and writes ``y`` (``H
P``); backward it reads those and ``dy`` and writes the four gradients. The
skip ``D xs``, the gate, the convolution and the projections are left out on
both sides of the share: the operations the metric times are the
recurrence's. Rematerialisation is what an implementation adds.
"""


def cost(tokens: float, heads: int, head_dim: int, d_state: int, groups: int,
         chunk: int, layers: int):
    """(floating-point operations, bytes to and from HBM) of the recurrences
    of ``tokens`` positions in ``layers`` layers, forward and backward."""
    inner, shared = heads * head_dim, groups * d_state
    forward = (2.0 * chunk * shared + 2.0 * chunk * inner
               + 4.0 * d_state * inner)
    read_forward = inner + heads + 2 * shared  # xs, dt, B, C
    floats = ((read_forward + inner)           # forward: those in, y out
              + (read_forward + inner)         # backward: those and dy in
              + read_forward)                  # the four gradients out
    return 3.0 * forward * tokens * layers, 4.0 * floats * tokens * layers
