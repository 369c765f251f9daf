"""The selective scan's share of its roofline in per cent: the least time
the chip could take for the scans of one round - what an ideal kernel must
move over the HBM bandwidth, or its operations over the peak, whichever is
larger (``kernels/<kernel>.py``) - over the device time a round of the
operations matching ``pattern`` (as ``trace_ops`` sums them).

The round's work comes from the cell: the window's real rows a round times
the configuration's ``data.sequence_length`` and ``train.epochs`` positions,
the Mamba widths from ``model.kwargs`` (``expand`` x ``hidden_size``,
``d_state``) and as many scanning layers as ``layer_ids`` holds even indices
up to the boundary. Nothing where the trace has no such operation or the
configuration no such keys."""

from benchmark.harness import trace as tr


def read(ctx, kernel, pattern, within_modules=None, outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds or not ctx.window.rounds:
        return None
    model = ctx.cell.config["model"].get("kwargs", {})
    length = ctx.cell.config["data"].get("sequence_length")
    if length is None or "d_state" not in model:
        return None
    seconds = tr.op_seconds(ctx.trace, ctx.trace_window, pattern, None,
                            within_modules, outside_spans)
    if not seconds:
        return None
    boundary = int(model["published_layers"]) // 2
    layers = sum(1 for i in model["layer_ids"]
                 if i % 2 == 0 and i <= boundary)
    tokens = (ctx.counts["real_rows"] / ctx.window.rounds * int(length)
              * int(ctx.cell.config["train"]["epochs"]))
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        tokens, int(model["expand"]) * int(model["hidden_size"]),
        int(model["d_state"]), layers)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ctx.trace_rounds)
