"""The table of peaks and the guard that refuses a rate above them.

``peaks.json`` is keyed by ``device_kind`` as jax reports it; a kind that is
not in the table is an error, never a default, and no environment variable
overrides it (``observe.goodput.PEAK_FLOPS`` has ``GRAFT_PEAK_FLOPS``).
"""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_TABLE}: add a "
            "sourced row"
        )
    return table[device_kind]


def mfu_pct(flops_per_s: float, device_kind: str, chips: int) -> float:
    """Model FLOP/s over the chips' bf16 peak, in percent."""
    return 100.0 * flops_per_s / (
        chips * peaks_for(device_kind)["bf16_flops_per_s"]
    )


def above_physical_bound(flops_per_s: float, device_kind: str, chips: int):
    """None when the rate is possible; else why not (the idea of
    ``benchmarks/_roofline.py guard``: such a rate is a broken timing loop,
    never a measurement)."""
    peak = chips * peaks_for(device_kind)["bf16_flops_per_s"]
    if flops_per_s <= peak:
        return None
    return (
        f"{flops_per_s:.4g} model FLOP/s exceeds the {peak:.4g} FLOP/s bf16 "
        f"peak of {chips} x {device_kind}"
    )
