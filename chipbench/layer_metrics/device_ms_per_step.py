"""Milliseconds in which an op ran on device 0 (union of intervals) per
optimizer step, over the traced steps. Source: the device trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1e3 * ctx.trace["busy_s"] / ctx.trace["steps"]
