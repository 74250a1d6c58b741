"""Milliseconds of collective operations (all-gather, all-reduce,
all-to-all, reduce-scatter, collective-permute; synchronous ops and the
start-to-done span of asynchronous pairs, as a union) on device 0 per
optimizer step. Source: the device trace. No number on one chip."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    return 1e3 * ctx.trace["collective_s"] / ctx.trace["steps"]
