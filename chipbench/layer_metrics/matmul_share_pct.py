"""Share of device 0's busy union, over the traced steps, spent in ops that
are a matmul or hold one: the instruction, or an instruction of its fused
computation, is a ``convolution`` or a ``dot`` in the program's compiled
text. Source: the device trace, joined to that text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    found = program_trace.analysis(ctx)
    device = found and found["device"]
    if not device or not device["joined"] or not device["busy_s"] > 0:
        return None
    return 100.0 * device["matmul_s"] / device["busy_s"]
