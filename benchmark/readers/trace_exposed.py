"""Of the time the matching collectives are in flight in the traced slice,
the share in per cent during which no other operation runs on that chip."""

from benchmark.harness import trace as tr


def read(ctx, pattern):
    if ctx.trace is None:
        return None
    share = tr.exposed_share(ctx.trace, ctx.trace_window, pattern)
    return None if share is None else 100.0 * share
