"""Host milliseconds per round in the named ``RoundTimer`` phases, over the
whole window (phases on the prefetch thread included: time spent, not time
the round waited)."""


def read(ctx, phases):
    return 1e3 * sum(ctx.window.phases.get(p, 0.0) for p in phases) \
        / ctx.window.rounds
