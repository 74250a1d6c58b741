"""Milliseconds of device 0's ops per optimizer step under the expert
layer's ``experts`` scope (the grouped matmuls over the held experts' sorted
rows and the gate between them), forward, recomputation and backward
together. Source: the device trace, joined to the program's compiled text
(``scope_trace``)."""

from chipbench import scope_trace


def read(ctx):
    return scope_trace.ms_per_step(ctx, lambda a: a["scope_s"]["experts"])
