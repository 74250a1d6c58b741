"""Milliseconds of device 0's ops per optimizer step that recompute the
forward pass inside the backward one (``rematted_computation`` in the
instruction's ``op_name``): part of ``backward_ms_per_step``. Source: the
device trace, joined to the program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(ctx, lambda d: d["phase_s"]["recompute"])
