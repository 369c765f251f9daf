"""A ``RoundTimer`` event counter's increase over the window, per round of
the window. Nothing where the program keeps no such counter."""


def read(ctx, counter):
    total = ctx.window.counters.get(counter)
    if not total or not ctx.window.rounds:
        return None
    return float(total) / ctx.window.rounds
