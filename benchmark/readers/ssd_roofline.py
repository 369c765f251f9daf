"""The state-space recurrence's share of its roofline in per cent: the least
time the chip could take for the recurrences of one round - the dual form's
operations over the bf16 peak or what an ideal kernel with the state on the
chip must move over the HBM bandwidth, whichever is larger
(``kernels/<kernel>.py``) - over the device time a round of the operations
matching ``pattern`` and not ``exclude`` (as ``trace_ops`` sums them).

The round's work comes from the program: the counter ``tokens_dispatched``
over the window's rounds (rows x positions at every dispatch) times the
``mamba`` entries of the configuration's ``layer_types`` among
``model.kwargs.layer_ids``, so the share reads the same work whatever
implements the scan. The sizes come from ``model.kwargs`` (``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
``mamba_chunk_size``). Nothing where the trace has no such operation, the
program keeps no such counter or the configuration no such keys."""

from benchmark.harness import trace as tr

SIZES = ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
         "mamba_chunk_size")


def read(ctx, kernel, pattern, exclude=None, within_modules=None,
         outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds or not ctx.window.rounds:
        return None
    model = ctx.cell.config["model"].get("kwargs", {})
    kinds = model.get("layer_types")
    if kinds is None or any(name not in model for name in SIZES):
        return None
    tokens = ctx.window.counters.get("tokens_dispatched")
    layers = sum(1 for i in model["layer_ids"] if kinds[i] == "mamba")
    if not tokens or not layers:
        return None
    seconds = tr.op_seconds(ctx.trace, ctx.trace_window, pattern, exclude,
                            within_modules, outside_spans)
    if not seconds:
        return None
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        float(tokens) / ctx.window.rounds,
        *(int(model[name]) for name in SIZES), layers)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ctx.trace_rounds)
