"""Seconds of set-up in which jax lowered a traced program to its MLIR module
(``compile.lower`` in the program's start-up ledger), as the union per thread
up to the window's opening. Source: ``startup_ledger``."""

from chipbench import startup_ledger


def read(ctx):
    return startup_ledger.value(ctx, "lower_s")
