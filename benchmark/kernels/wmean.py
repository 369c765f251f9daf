"""What the weighted-mean aggregation kernel has to do for one call
(``ops/aggregate.py`` ``_wmean_kernel``: ``[1, C] @ [C, D]`` in float32):
read the stacked client models and the weights once, write the mean once."""


def cost(clients: int, params: int):
    """(floating-point operations, bytes to and from HBM) of one call."""
    return (2.0 * clients * params,
            4.0 * (clients * params + params + clients))
