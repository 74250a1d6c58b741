"""Seconds of set-up inside the span that builds the training state
(``state.create``, or the facade's ``facade.init_state`` around it) and not
inside a compile event: shapes, shardings, the dispatch of init and
placement. Source: the program's start-up ledger (``startup_ledger``)."""

from chipbench import startup_ledger


def read(ctx):
    return startup_ledger.state_seconds(ctx)
