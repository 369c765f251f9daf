"""A ``RoundTimer`` event counter's increase over the window."""


def read(ctx, counter):
    return float(ctx.window.counters.get(counter, 0))
