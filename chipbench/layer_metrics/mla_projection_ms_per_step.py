"""Milliseconds of device 0's ops per optimizer step in latent attention's
chain (the low-rank projections, their norms, rotary, the concatenations,
the output projection): scope ``mla`` less the ``attention`` core inside
it, all phases. Source: the device trace, joined to the program's compiled
text (``scope_trace``)."""

from chipbench import scope_trace


def read(ctx):
    return scope_trace.ms_per_step(
        ctx, lambda a: a["scope_s"]["mla"] - a["scope_s"]["attention"]
    )
