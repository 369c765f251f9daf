"""The gated delta rule's share of its roofline in per cent, where the model
has Gated DeltaNet layers: the least time the chip could take for the
recurrences of one round - the chunked form's operations over the bf16 peak
or what an ideal kernel with the state on the chip must move over the HBM
bandwidth, whichever is larger (``kernels/<kernel>.py``) - over the device
time a round of the operations the program names ``scope`` (as ``scope_ops``
reads them: ``jax.named_scope`` around the recurrence's call alone).

The round's work comes from the program: the counter ``tokens_dispatched``
over the window's rounds (rows x positions at every dispatch), so the share
reads the same work whatever implements the recurrence. The sizes come from
``model.kwargs``: ``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``, and the linear layers
among ``layer_ids`` (published layer ``l`` is full attention where ``(l +
1) % full_attention_interval == 0``, else linear). Nothing where the program
names no such scope or keeps no such counter, or the configuration has no
such keys."""

SIZES = ("linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim")


def read(ctx, kernel, scope, within_modules=None, outside_spans=()):
    if ctx.trace is None or not ctx.trace_rounds or not ctx.window.rounds:
        return None
    model = ctx.cell.config["model"].get("kwargs", {})
    if any(name not in model for name in SIZES + ("layer_ids",
                                                  "full_attention_interval")):
        return None
    interval = int(model["full_attention_interval"])
    layers = sum(1 for i in model["layer_ids"] if (i + 1) % interval)
    tokens = ctx.window.counters.get("tokens_dispatched")
    if not tokens or not layers:
        return None
    core_ms = ctx.cell.module("readers", "scope_ops").read(
        ctx, scope=[scope], within_modules=within_modules,
        outside_spans=outside_spans)
    if not core_ms:
        return None
    flops, nbytes = ctx.cell.module("kernels", kernel).cost(
        float(tokens) / ctx.window.rounds,
        *(int(model[name]) for name in SIZES), layers)
    least = max(flops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * core_ms)
