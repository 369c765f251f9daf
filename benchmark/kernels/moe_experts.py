"""What the routed experts of a round have to do (``ops/moe.py``; a gated
expert is ``W2(silu(W1 x) * W3 x)``): the three products of every (token,
choice) pair that landed on a held expert, forward and backward, whatever
implements them.

Operations: a pair takes three ``hidden x width`` products forward and each
has two backward (the data gradient and the weight gradient): ``3 x 3 x 2 x
hidden x width`` a pair. Bytes, for an ideal grouped kernel that keeps a
block's hidden activations on the chip: a local step reads each held
expert's three matrices once forward and once backward and writes their
gradient once; a pair's input row is read forward and backward, its output
row written forward, its output's gradient read and its input's gradient
written backward. Float32 throughout. Rematerialisation, the gathers and the
SGD update are left out: they are what an implementation adds.
"""


def cost(pairs: float, held: int, hidden: int, width: int, steps: int):
    """(floating-point operations, bytes to and from HBM) of the expert
    products of one round: ``pairs`` (token, choice) pairs on the ``held``
    experts of a chip over all sparse layers and local steps, ``steps`` the
    (sparse layer, local step) passes over the experts' weights."""
    flops = 18.0 * pairs * hidden * width
    weights = 3.0 * held * 3 * hidden * width * steps
    rows = 5.0 * pairs * hidden
    return flops, 4.0 * (weights + rows)
