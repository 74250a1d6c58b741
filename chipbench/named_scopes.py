"""Device time of the traced steps under scopes that the CALLER names: the
join of ``scope_trace.py`` and ``attention_kinds.py`` with no list of its
own, so that a configuration's new ``jax.named_scope`` needs a reader of a
few lines and no third copy of the join (ROADMAP.md, Reach 1 (d); a later
``benchmark`` PR folds those two files into this one).

Reuses ``program_trace``'s join and nothing else of it: the run's profile
(``find_xplane``, ``load``), the program's own compiled texts
(``program_modules``), an executed instruction's scope (``scope_of``) and
the window of whole steps (``traced_steps``); a kernel is what
``scope_trace.is_kernel`` says. A scope counts wherever it appears in an
instruction's ``op_name``, in any phase: forward, the rematerialised forward
and backward together. The profile is loaded once more, after the window,
and once for all callers. Where there is no profile, no device plane, no
text to join or no such scope in the program (the parent's, any other
model's), ``seconds`` is None or reads zeros and nothing raises."""

from __future__ import annotations

import json
import sys
import time

from chipbench import program_trace, scope_trace
from chipbench.trace_reduce import CONTAINER_FAMILIES, op_family

_CACHE: dict = {}


def reduce_named(trace, programs: dict, lo: float, hi: float, scopes) -> dict:
    """Seconds of device 0's ops in ``[lo, hi]`` under each of ``scopes``
    (nested scopes each get the op), under any of them (``any_s``: an op
    under two counts once), and of the kernels under each (with their
    count). ``touched_s`` is printed for the reader of a finding, never
    a metric: the seconds of every op that holds ANY instruction under
    the scope, so a fusion named for another scope's root counts whole
    (XLA fuses a norm into its neighbours' pass over the stream): what
    the scope costs lies between ``scope_s`` and ``touched_s``."""
    scope_s = dict.fromkeys(scopes, 0.0)
    touched_s = dict.fromkeys(scopes, 0.0)
    kernel_s = dict.fromkeys(scopes, 0.0)
    kernel_events = dict.fromkeys(scopes, 0)
    any_s, seen = 0.0, {}
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
        if (mod, name) not in seen:  # an instruction runs once a step
            seen[mod, name] = (
                program_trace.tokens(program_trace.scope_of(program, name)),
                {
                    tok for instr in program_trace._fused(program, name)
                    for tok in program_trace.tokens(instr.op_name)
                },
            )
        toks, inside = seen[mod, name]
        under = [scope for scope in scopes if scope in toks]
        seconds = min(ev.end, hi) - max(ev.start, lo)
        for scope in scopes:
            if scope in toks or scope in inside:
                touched_s[scope] += seconds
        if not under:
            continue
        any_s += seconds
        kernel = scope_trace.is_kernel(program, name)
        for scope in under:
            scope_s[scope] += seconds
            if kernel:
                kernel_s[scope] += seconds
                kernel_events[scope] += 1
    return {
        "scope_s": scope_s, "any_s": any_s, "touched_s": touched_s,
        "kernel_s": kernel_s, "kernel_events": kernel_events,
    }


def joined():
    """``(trace, programs, window)`` of the run, loaded once; None where
    there is no profile, no device plane or no text to join."""
    if "joined" not in _CACHE:
        found = None
        path = program_trace.find_xplane()
        if path is not None:
            profile = program_trace.load(path)
            window = program_trace.traced_steps(profile.trace)
            if window is not None and 0 in profile.trace.ops:
                programs, _ = program_trace.program_modules()
                if programs:
                    found = (profile.trace, programs, window)
        _CACHE["joined"] = found
    return _CACHE["joined"]


def seconds(scopes: tuple) -> dict | None:
    """The run's times under ``scopes``, made once for each tuple of them
    and printed once to stderr (``{"named_scopes": ...}``)."""
    scopes = tuple(scopes)
    if scopes not in _CACHE:
        result, t0 = None, time.perf_counter()
        found = joined()
        if found is not None:
            trace, programs, window = found
            result = {
                "scopes": list(scopes), "steps": window["steps"],
                **reduce_named(
                    trace, programs, window["lo"], window["hi"], scopes
                ),
                "analysis_s": time.perf_counter() - t0,
            }
            print(json.dumps({"named_scopes": result}), file=sys.stderr,
                  flush=True)
        _CACHE[scopes] = result
    return _CACHE[scopes]


def ms_per_step(ctx, scopes: tuple):
    """Milliseconds per optimizer step of the ops under any of ``scopes``;
    None where the trace holds nothing under them."""
    found = seconds(scopes)
    if not found or not found["steps"] or not found["any_s"] > 0:
        return None
    return 1e3 * found["any_s"] / found["steps"]
