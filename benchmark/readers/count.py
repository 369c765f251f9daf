"""An exact count the harness took over the window (``ctx.counts``)."""


def read(ctx, name):
    value = ctx.counts.get(name)
    return None if value is None else float(value)
