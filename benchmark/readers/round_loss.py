"""``train_loss_local`` (loss_sum / count of the local steps) of one fixed
round: a value that does not depend on speed and changes only when the
arithmetic does. Nothing if the window did not reach that round."""


def read(ctx, round):
    if round >= len(ctx.window.stats):
        return None
    stats = ctx.window.stats[round]
    return stats["loss_sum"] / max(1.0, stats["count"])
