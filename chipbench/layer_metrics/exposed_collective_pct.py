"""The part of device 0's collective intervals during which no compute op
ran on it, as a percentage of the step's period. Source: the device trace.
No number on one chip."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    return 100.0 * ctx.trace["exposed_collective_s"] / ctx.trace["window_s"]
