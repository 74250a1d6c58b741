"""Milliseconds of device 0's ops per optimizer step under the scope
``post_norm``: the two RMSNorms a layer of ``models/afmoe.py`` applies AFTER
its branches (attention, MLP or experts) and the residual sums they feed,
forward, recomputation and backward together: what sandwich norms cost
beside pre-norms alone. Source: the device trace, joined to the program's
compiled text (``named_scopes``)."""

from chipbench import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, ("post_norm",))
