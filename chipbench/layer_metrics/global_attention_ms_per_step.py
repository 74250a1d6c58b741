"""Milliseconds of device 0's ops per optimizer step under the scope
``attention_global``: the attention core of the layers that see the whole
causal past (and carry no positions), forward, recomputation and backward
together. Source: the device trace, joined to the program's compiled text
(``attention_kinds``)."""

from chipbench import attention_kinds


def read(ctx):
    return attention_kinds.ms_per_step(ctx, "attention_global")
