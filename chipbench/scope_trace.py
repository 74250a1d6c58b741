"""Device time of the traced steps under scopes that ``program_trace``'s
closed lists do not know (``mla``, ``router``, ``dispatch``, ``experts``,
``combine``, ``shared_expert``), and the time of the Pallas kernels under
``experts`` and ``attention``, for the readers a configuration brings.

Reuses ``program_trace``'s join and nothing else of it: the run's profile
(``find_xplane``, ``load``), the program's own compiled texts
(``program_modules``), an executed instruction's scope (``scope_of``) and
the window of whole steps (``traced_steps``). A scope counts wherever it
appears in an instruction's ``op_name``, in any phase: forward, the
rematerialised forward and backward together. A kernel is an instruction
that is a ``custom-call`` (a Pallas kernel compiles to one) or a fusion
that holds one. Loads the profile a second time (``program_trace`` keeps
only its result): a few seconds, after the window. Where there is no
profile, no device plane or no text to join, ``analysis`` is None and
nothing raises."""

from __future__ import annotations

import json
import sys
import time

from chipbench import program_trace
from chipbench.trace_reduce import CONTAINER_FAMILIES, op_family

SCOPES = (
    "mla", "attention", "router", "dispatch", "experts", "combine",
    "shared_expert",
)
KERNEL_SCOPES = ("experts", "attention")
KERNEL_OPCODE = "custom-call"
_CACHE: dict = {}


def is_kernel(module: dict, name: str) -> bool:
    instr = module["instructions"].get(name)
    if instr is None:
        return False
    return any(
        i.opcode == KERNEL_OPCODE
        for i in [instr, *program_trace._fused(module, name)]
    )


def reduce_scopes(trace, programs: dict, lo: float, hi: float) -> dict:
    """Seconds of device 0's ops in ``[lo, hi]`` by scope of ``SCOPES``
    (nested scopes each get the op), and of the kernels by scope of
    ``KERNEL_SCOPES``."""
    by_scope = dict.fromkeys(SCOPES, 0.0)
    kernel = dict.fromkeys(KERNEL_SCOPES, 0.0)
    kernel_events = dict.fromkeys(KERNEL_SCOPES, 0)
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
        seconds = min(ev.end, hi) - max(ev.start, lo)
        toks = program_trace.tokens(program_trace.scope_of(program, name))
        for scope in SCOPES:
            if scope in toks:
                by_scope[scope] += seconds
        if is_kernel(program, name):
            for scope in KERNEL_SCOPES:
                if scope in toks:
                    kernel[scope] += seconds
                    kernel_events[scope] += 1
    return {
        "scope_s": by_scope, "kernel_s": kernel, "kernel_events": kernel_events,
    }


def analysis(ctx) -> dict | None:
    """The run's scope times, made once and printed once to stderr."""
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
                **reduce_scopes(
                    profile.trace, programs, window["lo"], window["hi"]
                ),
                "analysis_s": time.perf_counter() - t0,
            }
            print(json.dumps({"scope_trace": result}), file=sys.stderr,
                  flush=True)
    _CACHE["analysis"] = result
    return result


def ms_per_step(ctx, pick):
    """``pick(analysis)`` seconds -> milliseconds per optimizer step."""
    found = analysis(ctx)
    if not found or not found["steps"]:
        return None
    return 1e3 * pick(found) / found["steps"]


def roofline_pct(ctx, kernel: str, scope: str):
    """The least time the chip could take for what the ``kernel`` calls of
    the traced whole steps had to do (the larger of FLOPs over the bf16
    peak and bytes over the HBM peak of ``peaks.json``; FLOPs and bytes by
    the family's arithmetic for the rows that arrived IN THOSE STEPS, from
    the job's per-step counters: routing moves during a window) over the
    time its kernels took in the device trace, in percent. None without
    the counters (a job that keeps none) or without kernel time under
    ``scope``."""
    from chipbench.peaks import peaks_for

    per_step = ctx.counters.get("routing_per_step")
    costs_of = ctx.counters.get("kernel_costs")
    found = analysis(ctx)
    if not per_step or costs_of is None or not found or not found["steps"]:
        return None
    if ctx.device_kind is None or not found["kernel_s"][scope] > 0:
        return None
    # the trace ends with the window's last execution; its whole steps are
    # the ``steps`` executions before that one (program_trace.traced_steps)
    n = found["steps"]
    traced = {k: v[-(n + 1):-1] for k, v in per_step.items()}
    mean = lambda k: sum(traced[k]) / max(1, len(traced[k]))  # noqa: E731
    flops, nbytes = costs_of(
        mean("assignments_landed"), mean("experts_active")
    )[kernel]
    peaks = peaks_for(ctx.device_kind)
    least = max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (found["kernel_s"][scope] / n)
