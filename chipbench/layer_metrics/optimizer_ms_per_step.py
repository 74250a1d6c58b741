"""Milliseconds of device 0's ops per optimizer step under the program's
``optimizer`` scope: everything from the finished gradients to the new state
(unscale, clip, the update, master-weight casts). Source: the device trace,
joined to the program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(ctx, lambda d: d["phase_s"]["optimizer"])
