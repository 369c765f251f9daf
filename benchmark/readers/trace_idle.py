"""Share of the traced slice in which no operation ran on the device, in
per cent, averaged over the chips."""

from benchmark.harness import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - tr.busy_seconds(ctx.trace, ctx.trace_window)
                    / (hi - lo))
