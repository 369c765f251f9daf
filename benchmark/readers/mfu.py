"""Model FLOP/s utilization in per cent: the matrix FLOPs that the window's
REAL rows need, forward and backward (the reference's own count per row),
over the training wall, the chips and the chip's bf16 peak. Padding rows,
recomputation and the aggregation earn nothing."""


def read(ctx):
    flops = ctx.flops_per_row * ctx.counts["real_rows"]
    return 100.0 * flops / ctx.window.train_wall_s / (
        ctx.cell.chips * ctx.peak["bf16_flops_per_s"])
