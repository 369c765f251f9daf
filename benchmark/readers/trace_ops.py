"""Device milliseconds per round of the operations whose name matches
``pattern`` (and not ``exclude``), optionally only while a program matching
``within_modules`` runs and none of the host spans ``outside_spans`` is
open; from the traced slice, averaged over the chips."""

from benchmark.harness import trace as tr


def read(ctx, pattern=None, exclude=None, within_modules=None,
         outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds:
        return None
    seconds = tr.op_seconds(ctx.trace, ctx.trace_window, pattern, exclude,
                            within_modules, outside_spans)
    return None if seconds is None else 1e3 * seconds / ctx.trace_rounds
