"""Milliseconds of device 0's ops per optimizer step under the scopes
``qk_norm`` (the RMSNorm over each head's dimensions of q and of k) and
``attention_gate`` (the gate's projection, its sigmoid and the product with
the core's output) that ``models/afmoe.py`` puts around its attention core:
what a gated, QK-normalised attention costs beside a plain one, forward,
recomputation and backward together. Source: the device trace, joined to
the program's compiled text (``named_scopes``)."""

from chipbench import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, ("qk_norm", "attention_gate"))
