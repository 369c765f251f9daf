"""What the in-place fold of a round has to do (``ops/aggregate.py``
``_fold_kernel``, one call a leaf and a client: ``acc += w * x`` in
float32): for each of the round's clients read the running sum and the
client's model once and write the sum once."""


def cost(clients: int, params: int):
    """(floating-point operations, bytes to and from HBM) of the folds of
    one round: ``clients`` passes over ``params`` parameters."""
    return 2.0 * clients * params, 12.0 * clients * params
