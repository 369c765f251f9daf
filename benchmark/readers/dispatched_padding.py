"""Share of the rows the program dispatched that were padding, in per cent:
1 - real rows / the ``RoundTimer`` counter that adds up, at every dispatch,
the round program's client slots times its padded length (static host-side
shapes, mesh padding included). Real rows are the benchmark's own count from
the federation's sizes. ``padded_rows`` computes the same share from the
packer's policy as the benchmark knows it; this one asks the program.
Nothing where the program keeps no such counter."""


def read(ctx, counter):
    dispatched = ctx.window.counters.get(counter)
    if not dispatched:
        return None
    return 100.0 * (1.0 - ctx.counts["real_rows"] / dispatched)
