"""Share of its roofline the banded attention kernel reaches in the window
layers: the least time the chip could take for the BAND of every window
layer's attention in one optimizer step (``families/smallthinker.
attention_cost`` under a window: query t's min(t + 1, window) keys, two
matmuls forward, four backward, whatever computes them; every forward that
really runs, the rematerialised one too) over the time of the kernels under
the ``attention_sliding`` scope in the device trace. A kernel that masks the
band instead of skipping to it reads a fraction of what
``attention_roofline_pct`` reads."""

from chipbench import attention_kinds


def read(ctx):
    return attention_kinds.roofline_pct(ctx, "attention_sliding")
