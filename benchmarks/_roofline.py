"""Shared roofline guard for the benchmark suite.

Every bench computes a deliberately generous physical upper bound for its
own metric (1 PFLOP/s chip compute, 2 TB/s HBM — both above any v5e-class
part) and refuses to publish a value above it: such a value is always an
instrument failure (e.g. async dispatch that never really synced), never
a measurement.

Two failure styles:
  - guard(..., soft=False): print the violation line and SystemExit(5) —
    for benches where one broken number poisons the whole run.
  - guard(..., soft=True): raise RuntimeError instead, for callers with
    per-arm isolation (ladder.py) where the other arms' numbers must
    survive the violating one.

The violation line carries no "# " prefix, so it survives any filter
that drops progress lines.
"""

from __future__ import annotations

VIOLATION_PREFIX = "ROOFLINE VIOLATION"


def verify_finite(value: float, label: str, exc=SystemExit) -> float:
    """Untimed post-window verification: a real finite host value proves
    the timed work executed. Callers fetch AFTER stopping the clock, and
    the roofline guard bounds any residual lie. ``exc`` lets callers
    with per-arm isolation (ladder) raise a catchable error instead."""
    import math

    if not math.isfinite(value):
        raise exc(f"non-finite {label} after timing: {value}")
    return value


def guard(
    label: str,
    value: float,
    unit: str,
    bound: float,
    detail: str,
    soft: bool = False,
) -> None:
    """No-op when value <= bound; otherwise publish the cause and fail."""
    if value <= bound:
        return
    msg = (
        f"{VIOLATION_PREFIX}: {label} {value:.0f} {unit} exceeds the "
        f"{bound:.0f} {unit} bound ({detail}) — timing loop is broken, "
        f"refusing to publish"
    )
    print(msg, flush=True)
    if soft:
        raise RuntimeError(msg)
    raise SystemExit(5)
