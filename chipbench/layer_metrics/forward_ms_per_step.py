"""Milliseconds of device 0's ops per optimizer step under the forward pass:
``jvp(...)`` or a model scope in the instruction's ``op_name``, and neither
``transpose(`` nor ``rematted_computation`` nor ``optimizer``. Collective ops
of the ops line count in their phase. Source: the device trace, joined to the
program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(ctx, lambda d: d["phase_s"]["forward"])
