"""Milliseconds of device 0's ops per optimizer step under the backward
pass: ``transpose(`` in the instruction's ``op_name`` (autodiff writes it),
the gradient accumulation (``grad_accum``) and the recomputation
(``rematted_computation``) included. Source: the device trace, joined to the
program's compiled text (``program_trace``)."""

from chipbench import program_trace


def read(ctx):
    return program_trace.device_value(
        ctx, lambda d: d["phase_s"]["backward"] + d["phase_s"]["recompute"]
    )
