"""Milliseconds per optimizer step in which device 0 ran no compute op while
a collective that SUMS contributions was executing or in flight: a
reduce-scatter, all-reduce or all-to-all, or a collective-permute that
carries partial sums (its data comes from a matmul, an add or a zero
accumulator). Whose sums they are (a gradient's or an activation's) the name
does not say. See ``exposed_assemble_ms_per_step``. Source: the device trace,
joined to the program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    if ctx.chips < 2:
        return None
    return program_trace.device_value(ctx, lambda d: d["exposed"]["reduce"])
