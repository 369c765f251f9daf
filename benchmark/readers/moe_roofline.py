"""The routed experts' share of their roofline in per cent: the least time
the chip could take for the expert products of one round - their operations
over the bf16 peak or what an ideal grouped kernel must move over the HBM
bandwidth, whichever is larger (``kernels/<kernel>.py``) - over the device
time a round of the operations matching ``pattern`` and not ``exclude`` (as
``trace_ops`` sums them).

The round's work comes from the program: ``pairs`` is the window's mean a
round of the stat ``moe_assignments`` (the (token, choice) pairs that landed
on held experts), so the share reads the same work whatever implements it.
The sizes come from the configuration's ``model.kwargs`` (``hidden_size``,
``moe_intermediate_size``, ``experts_held``, the sparse layers among
``layer_ids``), the local steps a round from the window's real rows over the
batch size. Nothing where the trace has no such operation, the rounds carry
no such stat or the configuration no such keys."""

from benchmark.harness import trace as tr


def read(ctx, kernel, pattern, exclude=None, within_modules=None,
         outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds or not ctx.window.rounds:
        return None
    model = ctx.cell.config["model"].get("kwargs", {})
    if "experts_held" not in model:
        return None
    pairs = sum(s.get("moe_assignments", 0.0) for s in ctx.window.stats)
    if not pairs:
        return None
    seconds = tr.op_seconds(ctx.trace, ctx.trace_window, pattern, exclude,
                            within_modules, outside_spans)
    if not seconds:
        return None
    train = ctx.cell.config["train"]
    sparse = sum(1 for i in model["layer_ids"]
                 if i >= int(model["num_dense_layers"]))
    steps = (ctx.counts["real_rows"] / ctx.window.rounds
             * int(train["epochs"]) / int(train["batch_size"]))
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        pairs / ctx.window.rounds, int(model["experts_held"][1]),
        int(model["hidden_size"]), int(model["moe_intermediate_size"]),
        sparse * steps)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ctx.trace_rounds)
