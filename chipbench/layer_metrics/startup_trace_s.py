"""Seconds of set-up in which jax traced a function of the program or of the
benchmark (``compile.trace`` in the program's start-up ledger), as the UNION
per thread up to the window's opening: a jitted function traced inside a
jitted step counts once. Traces under 5 ms are not kept by the ledger; they
lie inside the longer ones. Source: ``startup_ledger``."""

from chipbench import startup_ledger


def read(ctx):
    return startup_ledger.value(ctx, "trace_s")
