"""Calls of the facade's compiled training programs (grad, update, fused
eager window, or the one program behind ``fused_step``) per batch handed to
the facade. Source: the benchmark's call counter around them."""

from chipbench.stoke_common import FACADE_PROGRAMS, FUSED_PROGRAM


def read(ctx):
    if not ctx.window.batches:
        return None
    calls = sum(ctx.calls.get(n, 0) for n in (*FACADE_PROGRAMS, FUSED_PROGRAM))
    return calls / ctx.window.batches
