"""Milliseconds per optimizer step in which device 0 ran no compute op while
a collective that ASSEMBLES data was executing or in flight: an all-gather,
or a collective-permute that passes a buffer along unchanged (the ring of a
partitioned matmul). Whose data it is (a parameter's or an activation's) the
name does not say. With ``exposed_reduce_ms_per_step`` and the unknown
remainder (printed by ``program_trace``) it sums to the exposed collective
time of the step. Source: the device trace, joined to the program's compiled
text."""

from chipbench import program_trace


def read(ctx):
    if ctx.chips < 2:
        return None
    return program_trace.device_value(ctx, lambda d: d["exposed"]["assemble"])
