"""Share of the traced steps in which no op ran on device 0: 1 - busy
union / window. (On four chips the worst device's share is printed on an
earlier line.) Source: the device trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
