"""Seconds of set-up in which the backend produced an executable: read from the
persistent cache (``compile.cache_read``) or compiled by XLA
(``compile.xla``), as the union per thread up to the window's opening. The
inside-out twin of ``compile_s``, which is the harness's own listener.
Source: the program's start-up ledger (``startup_ledger``)."""

from chipbench import startup_ledger


def read(ctx):
    found = startup_ledger.report(ctx)
    return found and found["cache_read_s"] + found["xla_s"]
