"""Seconds from the loader's pool creation to its workers' first batch
(``loader.start_workers``) and from the device prefetcher's thread start to
its first batch placed (``prefetch.start``); a job with neither has no
number. Source: the program's start-up ledger (``startup_ledger``)."""

from chipbench import startup_ledger


def read(ctx):
    return startup_ledger.input_seconds(ctx)
