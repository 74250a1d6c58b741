"""The program's own account of set-up, read once a run: the start-up ledger
of ``observe.trace`` (``startup_report``) from its origin (the program's
``runtime.initialize``, the first thing ``run.main`` asks of it) to the
window's opening, ``ctx.window.marks[0]``: ``loop.run_steps`` stamps it with
``time.perf_counter()``, the ledger's clock and ``setup_s``'s (in the
stoke-loop job the first mark is the first batch's yield, milliseconds after
the opening).

``report(ctx)`` caches the report for the six readers
(``layer_metrics/startup_*.py``, ``setup_outside_program_s.py``) and prints
one ``{"startup": ...}`` line to stderr, as ``program_trace`` does (the
harness's lines cannot be extended). A program without the ledger (the
parent of PR 37) gives None, and every reader then gives None.
"""

from __future__ import annotations

import json
import sys

STATE_SPANS = ("facade.init_state", "state.create")  # outermost first
INPUT_SPANS = ("loader.start_workers", "prefetch.start")

_CACHE: dict = {}


def report(ctx) -> dict | None:
    if "report" in _CACHE:
        return _CACHE["report"]
    from pytorch_distributedtraining_tpu.observe import trace

    read = getattr(trace, "startup_report", None)
    found = None
    if read is not None and ctx.window.marks:
        found = read(until=ctx.window.marks[0])
        print(json.dumps({"startup": found}), file=sys.stderr, flush=True)
    _CACHE["report"] = found
    return found


def value(ctx, key: str):
    """One number of the report's top level."""
    found = report(ctx)
    return None if found is None else found[key]


def state_seconds(ctx):
    """Seconds inside the span that builds the training state, compile
    events out: ``facade.init_state`` where the facade builds it (it holds
    ``state.create``), else ``state.create``."""
    found = report(ctx)
    for name in STATE_SPANS:
        entry = found and found["by_name"].get(name)
        if entry:
            return entry["seconds"] - entry["compile_seconds"]
    return None


def input_seconds(ctx):
    """Seconds of the top-level ``loader.start_workers`` and
    ``prefetch.start`` spans of any thread (the loader's inside the
    prefetcher's, on its feeder thread, is not counted twice)."""
    found = report(ctx)
    if found is None:
        return None
    rows = [
        row for row in found["phases"] + found["background"]
        if row["name"] in INPUT_SPANS
    ]
    return sum(row["seconds"] for row in rows) if rows else None
