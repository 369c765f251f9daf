"""A kernel's share of its roofline in per cent: the least time the chip
could take for one call — the larger of its operations over the bf16 peak
and its bytes over the HBM bandwidth, from ``kernels/<kernel>.py`` — over
the kernel's measured time per call in the traced slice. One call a round."""

from benchmark.harness import trace as tr


def read(ctx, kernel, pattern):
    if ctx.trace is None or not ctx.trace_rounds:
        return None
    seconds = tr.op_seconds(ctx.trace, ctx.trace_window, pattern)
    if not seconds:
        return None
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        ctx.cohort_per_chip, ctx.params)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ctx.trace_rounds)
