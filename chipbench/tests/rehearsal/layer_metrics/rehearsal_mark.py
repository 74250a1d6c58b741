"""A per-layer metric added as one file: the mark ``trainstep_marked``
leaves."""


def read(ctx):
    return ctx.counters.get("rehearsal_mark")
