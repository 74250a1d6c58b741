"""Milliseconds of device 0's ops per optimizer step under the scope
``indexer`` (``models/keye_vl2.py``, ``ops/sparse_attn.py``): the indexer's
projections, key norm and rotary, its selection kernel and the KL's value,
all phases (the KL's gradient into the indexer is made inside the core's
backward kernels and counts under ``sparse_attention``). Source: the device
trace, joined to the program's compiled text (``named_scopes``)."""

from chipbench import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, ("indexer",))
