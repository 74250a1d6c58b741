"""Milliseconds of device 0's ops per optimizer step under SwinIR's
``window_layout`` scope (the cyclic shift, window partition and window
reverse: data movement, no arithmetic), forward and backward together.
Source: the device trace, joined to the program's compiled text
(``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(
        ctx, lambda d: d["component_s"].get("window_layout", 0.0)
    )
