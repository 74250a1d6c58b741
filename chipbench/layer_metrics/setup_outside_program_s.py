"""Seconds between the ledger's origin (``runtime.initialize``) and the
window's opening under no top-level span of the program, on any thread: the
harness's backend start, its data, its reference pass, the wait for the
device. The ``{"startup": ...}`` line names each gap by the spans on either
side. Source: the program's start-up ledger (``startup_ledger``)."""

from chipbench import startup_ledger


def read(ctx):
    return startup_ledger.value(ctx, "outside_program_s")
