"""Milliseconds of device 0's ops per optimizer step under the scope
``sparse_attention`` (``ops/sparse_attn.py``, inside ``attention``): the
core over the indexer's selection, forward and backward, and with its
backward the KL's gradient into the indexer. Source: the device trace,
joined to the program's compiled text (``named_scopes``)."""

from chipbench import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, ("sparse_attention",))
