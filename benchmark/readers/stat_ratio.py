"""The ratio of two of the rounds' stat sums over the window: ``sum of
numerator / sum of denominator``, times the configuration's
``model.kwargs[scale_by]`` where that is named (a number, or for a ``(first,
count)`` pair its count). Nothing where the rounds carry no such stats."""


def read(ctx, numerator, denominator, scale_by=None):
    top = sum(s.get(numerator, 0.0) for s in ctx.window.stats)
    bottom = sum(s.get(denominator, 0.0) for s in ctx.window.stats)
    if not bottom:
        return None
    scale = 1.0
    if scale_by is not None:
        scale = ctx.cell.config["model"].get("kwargs", {}).get(scale_by)
        if scale is None:
            return None
        scale = float(scale[-1] if isinstance(scale, list) else scale)
    return scale * top / bottom
