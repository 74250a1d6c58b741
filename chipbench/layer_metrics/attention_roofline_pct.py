"""Share of its roofline the attention kernel reaches: the least time the
chip could take for the causal half of every layer's attention in one
optimizer step (``families/glm4_moe_lite.attention_cost``: two matmuls
forward, four backward, whatever computes them; every forward that really
runs, the rematerialised one too) over the time of the kernels under the
``attention`` scope in the device trace."""

from chipbench import scope_trace


def read(ctx):
    return scope_trace.roofline_pct(ctx, "attention", "attention")
