"""Device time of the traced steps by KIND of attention layer: the scopes
``attention_sliding`` (a causal window) and ``attention_global`` (the whole
causal past) that ``models/smallthinker.py`` puts inside ``attention``, and
the time of the Pallas kernels under each.

``scope_trace.SCOPES`` is a closed list in a file a later PR may not edit,
so these two are reduced here, the same way: ``program_trace``'s join (the
run's profile, the program's own compiled texts, an executed instruction's
scope, the window of whole steps) and ``scope_trace.is_kernel``. A scope
counts wherever it appears in an instruction's ``op_name``, in any phase:
forward, the rematerialised forward and backward together. Loads the
profile once more, after the window. Where there is no profile, no device
plane, no text to join or no such scope in the program (the parent's, any
other model's), ``analysis`` is None or reads zeros and nothing raises."""

from __future__ import annotations

import json
import sys
import time

from chipbench import program_trace, scope_trace
from chipbench.trace_reduce import CONTAINER_FAMILIES, op_family

KINDS = ("attention_sliding", "attention_global")
_CACHE: dict = {}


def reduce_kinds(trace, programs: dict, lo: float, hi: float) -> dict:
    """Seconds of device 0's ops in ``[lo, hi]`` under each scope of
    ``KINDS``, and of the kernels among them (with their count)."""
    scope_s = dict.fromkeys(KINDS, 0.0)
    kernel_s = dict.fromkeys(KINDS, 0.0)
    kernel_events = dict.fromkeys(KINDS, 0)
    modules = trace.modules.get(0, [])
    for ev, mod in program_trace.with_modules(
        trace.ops.get(0, []), modules, lo, hi
    ):
        if op_family(ev.name) in CONTAINER_FAMILIES:
            continue
        program = programs.get(mod)
        name = program_trace.instruction_name(ev.name)
        if program is None or name not in program["instructions"]:
            continue
        toks = program_trace.tokens(program_trace.scope_of(program, name))
        kinds = [kind for kind in KINDS if kind in toks]
        if not kinds:
            continue
        seconds = min(ev.end, hi) - max(ev.start, lo)
        kernel = scope_trace.is_kernel(program, name)
        for kind in kinds:
            scope_s[kind] += seconds
            if kernel:
                kernel_s[kind] += seconds
                kernel_events[kind] += 1
    return {
        "scope_s": scope_s, "kernel_s": kernel_s,
        "kernel_events": kernel_events,
    }


def analysis(ctx) -> dict | None:
    """The run's times by attention kind, made once and printed once to
    stderr."""
    if "analysis" in _CACHE:
        return _CACHE["analysis"]
    result = None
    path = program_trace.find_xplane()
    if path is not None:
        t0 = time.perf_counter()
        profile = program_trace.load(path)
        window = program_trace.traced_steps(profile.trace)
        programs = {}
        if window is not None and 0 in profile.trace.ops:
            programs, _ = program_trace.program_modules()
        if programs:
            result = {
                "steps": window["steps"],
                **reduce_kinds(
                    profile.trace, programs, window["lo"], window["hi"]
                ),
                "analysis_s": time.perf_counter() - t0,
            }
            print(json.dumps({"attention_kinds": result}), file=sys.stderr,
                  flush=True)
    _CACHE["analysis"] = result
    return result


def ms_per_step(ctx, kind: str):
    """Milliseconds per optimizer step under the scope ``kind``; None where
    the trace holds nothing under it."""
    found = analysis(ctx)
    if not found or not found["steps"] or not found["scope_s"][kind] > 0:
        return None
    return 1e3 * found["scope_s"][kind] / found["steps"]


def roofline_pct(ctx, kind: str):
    """The least time the chip could take for what the kernels under
    ``kind`` had to do in one optimizer step (the larger of FLOPs over the
    bf16 peak and bytes over the HBM peak of ``peaks.json``; FLOPs and bytes
    by the family's ``kernel_costs`` under the same name: a layer kind's
    attention does not depend on the routing, so the counters it is handed
    are zeros) over the time those kernels took in the device trace, in
    percent. None without the family's costs, without a device or without
    kernel time under ``kind``."""
    from chipbench.peaks import peaks_for

    costs_of = ctx.counters.get("kernel_costs")
    found = analysis(ctx)
    if costs_of is None or ctx.device_kind is None or not found:
        return None
    if not found["steps"] or not found["kernel_s"][kind] > 0:
        return None
    costs = costs_of(0.0, 0.0)
    if kind not in costs:
        return None
    flops, nbytes = costs[kind]
    peaks = peaks_for(ctx.device_kind)
    least = max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (found["kernel_s"][kind] / found["steps"])
