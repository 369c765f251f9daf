"""What an ideal selective scan has to move (``ops/selective_scan.py``; the
recurrence ``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) outer B_t``, ``y_t
= h_t C_t``): a kernel that keeps the state ``[d_inner, d_state]`` on the
chip reads and writes only the per-token tensors.

Forward, a token and a layer: read ``delta`` and ``u`` (``d_inner`` floats
each) and ``B``, ``C`` (``d_state`` each), write ``y`` (``d_inner``).
Backward: read those and ``dy``, write ``d delta``, ``d u``, ``dB``, ``dC``.
The gate ``y * silu(z)`` and the skip ``D u`` are left out on both sides of
the share: the operations the metric times are the recurrence's (those that
touch a ``[.., d_state, d_inner]`` tensor), and the gate is not among them.
"""


def cost(tokens: int, d_inner: int, d_state: int, layers: int):
    """(floating-point operations, bytes to and from HBM) of the scans of
    ``tokens`` positions in ``layers`` layers, forward and backward, in
    float32."""
    forward = 3 * d_inner + 2 * d_state
    backward = 5 * d_inner + 4 * d_state
    # a state element a token: exp, two multiplies and an add to advance
    # it, a multiply and an add into y; the backward pass about twice that
    flops = 3 * 6.0 * d_inner * d_state
    return (flops * tokens * layers,
            4.0 * (forward + backward) * tokens * layers)
