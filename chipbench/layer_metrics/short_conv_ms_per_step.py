"""Milliseconds of device 0's ops per optimizer step under the scope
``short_conv`` that ``ops/short_conv.py`` puts around the gated short
convolution of a conv mixer (its kernels and their glue; not the in- and
out-projections around it): forward, recomputation and backward together.
Source: the device trace, joined to the program's compiled text
(``named_scopes``)."""

from chipbench import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, ("short_conv",))
