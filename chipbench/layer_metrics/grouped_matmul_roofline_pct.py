"""Share of its roofline the grouped matmul reaches: the least time the
chip could take for the expert layers' grouped matmuls of one optimizer
step (rows that really arrived, by the step's counter; FLOPs and bytes by
``families/glm4_moe_lite.grouped_matmul_cost``; every forward that really
runs, the rematerialised one too, and the backward) over the time of the
kernels under the ``experts`` scope in the device trace."""

from chipbench import scope_trace


def read(ctx):
    return scope_trace.roofline_pct(ctx, "grouped_matmul", "experts")
