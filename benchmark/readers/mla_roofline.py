"""The attention core's share of its roofline in per cent, where the model
has latent attention: the least time the chip could take for the cores of one
round - their causal half's operations over the bf16 peak or what an ideal
kernel that never writes the scores must move over the HBM bandwidth,
whichever is larger (``kernels/<kernel>.py``) - over the device time a round
of the operations the program names ``scope`` (as ``scope_ops`` reads them:
``jax.named_scope`` around the core's call alone).

The round's work comes from the program: the counter ``tokens_dispatched``
over the window's rounds (rows x positions at every dispatch), so the share
reads the same work whatever implements the core. The sizes come from the
configuration: ``data.sequence_length``, and of ``model.kwargs``
``num_heads``, ``qk_nope_head_dim`` + ``qk_rope_head_dim``, ``v_head_dim``
and the layers held (``layer_ids``). Nothing where the program names no such
scope or keeps no such counter, or the configuration has no such keys."""

SIZES = ("num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "layer_ids")


def read(ctx, kernel, scope, within_modules=None, outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds or not ctx.window.rounds:
        return None
    model = ctx.cell.config["model"].get("kwargs", {})
    if any(name not in model for name in SIZES):
        return None
    tokens = ctx.window.counters.get("tokens_dispatched")
    if not tokens:
        return None
    core_ms = ctx.cell.module("readers", "scope_ops").read(
        ctx, scope=[scope], within_modules=within_modules,
        outside_spans=outside_spans)
    if not core_ms:
        return None
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        float(tokens) / ctx.window.rounds,
        int(ctx.cell.config["data"]["sequence_length"]),
        int(model["num_heads"]),
        int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"]),
        int(model["v_head_dim"]), len(model["layer_ids"]))
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * core_ms)
