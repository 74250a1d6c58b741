"""Milliseconds of device 0's ops per optimizer step under the scope
``attention_sliding``: the attention core (score, mask, softmax, value
product; not the projections, not rotary) of the layers that see a causal
window, forward, recomputation and backward together. A third of it (three
window layers a period) against ``global_attention_ms_per_step`` says
whether the band is skipped or only masked. Source: the device trace, joined
to the program's compiled text (``attention_kinds``)."""

from chipbench import attention_kinds


def read(ctx):
    return attention_kinds.ms_per_step(ctx, "attention_sliding")
