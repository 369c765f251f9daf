"""Peak memory in GiB: ``device`` is the fullest chip's
``peak_bytes_in_use``, ``host`` the process's peak resident set."""


def read(ctx, which):
    value = {"device": ctx.memory_peak_bytes,
             "host": ctx.host_rss_bytes}[which]
    return None if value is None else value / 2.0 ** 30
