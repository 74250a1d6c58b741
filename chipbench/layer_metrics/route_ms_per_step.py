"""Milliseconds of device 0's ops per optimizer step that route: the scopes
``router`` (scores, top-k, loads, the selection bias), ``dispatch`` (sort by
held expert, gather into the buffer) and ``combine`` (gather back, weigh,
sum) of the expert layer, all phases. Source: the device trace, joined to
the program's compiled text (``scope_trace``)."""

from chipbench import scope_trace


def read(ctx):
    return scope_trace.ms_per_step(
        ctx, lambda a: sum(
            a["scope_s"][s] for s in ("router", "dispatch", "combine")
        ),
    )
