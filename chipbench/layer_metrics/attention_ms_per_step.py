"""Milliseconds of device 0's ops per optimizer step under the models'
``attention`` scope (score, bias, mask, softmax, value product; not the
projections), forward, backward and recomputation together. Source: the
device trace, joined to the program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(
        ctx, lambda d: d["component_s"].get("attention", 0.0)
    )
