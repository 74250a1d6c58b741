"""What the program says about its own work, read from the run's profile:
the device time of the traced steps by phase and component, the matmul
share, exposed collective time by what the collective does, the device's
idle gaps by the program's innermost span, and the self time of the
facade's and the loader's spans.

The program names its work in two places. Inside the compiled programs,
``jax.named_scope`` (``optimizer``, ``clip``, ``loss``, ``attention``,
``window_layout``, ...), Flax module paths and autodiff's ``jvp(...)`` /
``transpose(...)`` / ``rematted_computation`` are in every instruction's
``op_name``. On the host, every span of ``observe.trace`` is a
``TraceAnnotation("graft/<name>")``. A TPU v5e trace (looked at by hand, PR
24) names an ``XLA Ops`` event by its full HLO text and gives it three stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``): no
``op_name`` and no category. So an op's scope is found by its instruction
name in the program's own compiled text
(``observe.profiling.program_texts``, compiled after the window through the
persistent cache). ``XLA Modules`` events carry ``run_id``, as do the host
plane's ``CompleteCallbacks`` events: an execution is paired with the moment
the host learns of its end by that number. The two planes share one clock
(measured, PR 24: rate 1 to 2e-4, offset under 3 ms, in sixteen profiles), so
host spans are laid against device gaps as they stand; ``completion_lag``
keeps watch on that.

Times are taken as ``trace_reduce`` takes them (device 0, seconds inside an
op's interval, containers skipped, exposed = collective intervals with no
compute op running), but for the window: a profile that opens while a step
runs holds only the rest of that execution, and ``trace_reduce.step_window``
counts it as a whole step, so its per-step milliseconds read short. Here the
window opens at the SECOND start of a step program. The arithmetic works on
plain lists, so a hand-made trace and a small HLO text check it
(``tests/test_program_trace.py``).

``analysis(ctx)`` loads the profile itself, once per run, and prints one
line of its own to stderr (the harness's lines cannot be extended). Where
there is no profile, no device plane, or a program without spans or texts
(the parent of PR 24), what cannot be read is ``None`` and nothing raises.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
import time

from chipbench import trace_reduce
from chipbench.trace_reduce import (
    CONTAINER_FAMILIES, Event, Trace, attribute_gaps, is_collective,
    module_name, op_family, step_window, subtract, total, union,
)

SPAN_PREFIX = "graft/"  # the program's own spans (observe.trace)
# the programs that run once per optimizer step, in any job (each job's
# STEP_MODULES; no cell runs two of them)
STEP_MODULES = ("jit__step", "jit_apply_updates", "jit_eager_step")
PHASES = ("forward", "backward", "recompute", "optimizer", "unnamed")
# a scope that names a component outright, in order of precedence
COMPONENT_SCOPES = (
    "attention", "window_layout", "loss", "feat_loss", "head", "embed",
    "upsample", "clip", "adamw", "grad_accum", "grad_sync", "metrics",
)
# a Flax module's name -> the class of module it is
MODULE_CLASSES = (
    (re.compile(r"^(mlp_\w+|fc\d+)$"), "mlp"),
    (re.compile(r"^(c_attn|c_proj|qkv|proj)$"), "projection"),
    (re.compile(r"^(ln_\w+|norm\w*)$"), "norm"),
    (re.compile(r"^(conv|conv_\w+|Conv_\d+)$"), "conv"),
)
MATMUL_OPCODES = ("convolution", "dot")
ASSEMBLE, REDUCE, UNKNOWN = "assemble", "reduce", "unknown"
# what a collective-permute's data is traced back through to its origin
PASS_THROUGH = (
    "collective-permute-start", "collective-permute-done",
    "get-tuple-element", "bitcast", "copy", "tuple",
)
REACH = 4  # how far a scope or an origin is looked for, in instructions
# the facade's spans whose self time (duration less children) is its own
# Python; every dispatch of a program is inside a child of one of these
FACADE_PYTHON_SPANS = (
    "facade.model", "facade.loss", "facade.backward", "facade.step",
    "facade.detach_and_sync_loss", "facade.fused_step",
    "facade.step.flush_micros", "facade.step.materialize_lazies",
)
FACADE_BATCH_SPANS = ("facade.backward", "facade.fused_step")
LOADER_SPANS = ("loader.collect", "loader.collate")
COMPLETE_EVENT = "CompleteCallbacks"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"


# -- the compiled text -----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Instruction:
    opcode: str
    op_name: str  # "" where the instruction carries no metadata
    calls: str  # the fused computation of a fusion, else ""
    operands: tuple  # names of the instructions it reads
    dims: tuple  # of the result (a tuple's first array)


def _shape_end(text: str) -> int:
    """Index just past the result shape ``text`` starts with: a tuple
    shape is parenthesised and may hold spaces, any other holds none."""
    if not text.startswith("("):
        return text.index(" ")
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i + 1
    raise ValueError(text[:80])


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_DIMS = re.compile(r"\w+\[([\d,]*)\]")


def parse_hlo(text: str) -> dict:
    """``compiled.as_text()`` -> ``{"module": name, "instructions": {name:
    Instruction}, "computations": {name: [instruction names]}, "users":
    {name: [names of the instructions that read it]}, "weight_dims": the
    shapes of the entry computation's ``state.params`` / ``params``
    arguments (and of one layer of a stacked one)}``. Instruction names
    are unique in a module."""
    head = re.search(r"^HloModule (\S+?),", text, re.M)
    instructions, computations, users, current = {}, {}, {}, None
    weight_dims = set()
    for line in text.splitlines():
        comp = _COMPUTATION.match(line)
        if comp:
            current = computations.setdefault(comp.group(1), [])
            continue
        match = _INSTRUCTION.match(line)
        if not match or current is None:
            continue
        name, rest = match.groups()
        try:
            end = _shape_end(rest)
            shape, rest = rest[:end], rest[end:].lstrip()
            opcode, tail = rest.split("(", 1)
        except ValueError:
            continue
        op_name = re.search(r'op_name="([^"]*)"', tail)
        calls = re.search(r"\bcalls=%([\w.\-]+)", tail)
        dims = _DIMS.search(shape)
        instr = instructions[name] = Instruction(
            opcode=opcode.strip(),
            op_name=op_name.group(1) if op_name else "",
            calls=calls.group(1) if calls else "",
            operands=tuple(
                re.findall(r"%([\w.\-]+)", tail.split("), ", 1)[0])
            ),
            dims=tuple(
                int(d) for d in dims.group(1).split(",") if d
            ) if dims else (),
        )
        current.append(name)
        for operand in instr.operands:
            users.setdefault(operand, []).append(name)
        if instr.opcode == "parameter" and re.match(
            r"(state\.)?params\b", instr.op_name
        ) and instr.dims:
            weight_dims.update(d for d in (instr.dims, instr.dims[1:]) if d)
    return {
        "module": head.group(1) if head else "",
        "instructions": instructions, "computations": computations,
        "users": users, "weight_dims": weight_dims, "scopes": {},
    }


def _fused(module: dict, name: str) -> list:
    """The instructions of a fusion's fused computation (nested ones
    included); empty for any other instruction."""
    out, todo = [], [module["instructions"][name].calls]
    while todo:
        comp = todo.pop()
        for inner in module["computations"].get(comp, ()):
            instr = module["instructions"][inner]
            out.append(instr)
            if instr.calls:
                todo.append(instr.calls)
    return out


def _named(op_name: str) -> bool:
    """An ``op_name`` that is a scope path; an argument's is its own name
    (``state.params['h_0']...``) and names no work."""
    return "/" in op_name


def scope_of(module: dict, name: str) -> str:
    """The ``op_name`` an executed instruction is accounted under: its own
    (a fusion's is its root's), else the first named instruction of its
    fused computation, else that of the nearest instruction that reads it,
    else of the nearest it reads, ``REACH`` instructions away at most.
    (What the compiler adds carries no metadata: the copy or slice that
    prefetches an operand belongs to the op that uses it.)"""
    found = module["scopes"].get(name)
    if found is None:
        found = module["scopes"][name] = _scope_of(module, name)
    return found


def _scope_of(module: dict, name: str) -> str:
    instructions = module["instructions"]
    instr = instructions.get(name)
    if instr is None:
        return ""
    if _named(instr.op_name):
        return instr.op_name
    for inner in _fused(module, name):
        if _named(inner.op_name):
            return inner.op_name
    for neighbours in (
        lambda n: module["users"].get(n, ()),
        lambda n: instructions[n].operands,
    ):
        seen, frontier = {name}, [name]
        for _ in range(REACH):
            frontier = [
                m for n in frontier for m in neighbours(n)
                if m in instructions and m not in seen
            ]
            seen.update(frontier)
            for other in frontier:
                if _named(instructions[other].op_name):
                    return instructions[other].op_name
    return ""


def is_matmul(module: dict, name: str) -> bool:
    """The instruction, or any instruction of its fused computation, is a
    convolution or a dot (XLA:TPU spells both ``convolution``)."""
    instr = module["instructions"].get(name)
    if instr is None:
        return False
    return any(
        i.opcode in MATMUL_OPCODES for i in [instr, *_fused(module, name)]
    )


# -- classification of an op_name ---------------------------------------------


def tokens(op_name: str) -> list:
    """``jit(_step)/transpose(jvp(GPT2))/h_0/attention/mul`` ->
    ``[jit, _step, transpose, jvp, GPT2, h_0, attention, mul]``."""
    return [t for t in re.split(r"[/()]+", op_name) if t]


def phase(op_name: str) -> str:
    toks = tokens(op_name)
    if "optimizer" in toks:
        return "optimizer"
    if "rematted_computation" in toks:
        return "recompute"
    if "transpose" in toks or "grad_accum" in toks:
        return "backward"
    if "jvp" in toks or component(op_name) != "unnamed":  # or a model scope
        return "forward"
    return "unnamed"


def component(op_name: str) -> str:
    toks = tokens(op_name)[:-1]  # the last is the primitive, not a scope
    for scope in COMPONENT_SCOPES:
        if scope in toks:
            return "loss" if scope == "feat_loss" else scope
    for tok in reversed(toks):  # the innermost module decides
        for pattern, cls in MODULE_CLASSES:
            if pattern.match(tok):
                return cls
    return "update" if "optimizer" in toks else "unnamed"


def collective_purpose(module: dict, name: str) -> str:
    """What a collective does with the data. ``assemble`` brings data to
    where it is used: an all-gather, or a collective-permute that passes a
    buffer along unchanged (the ring a partitioned matmul gathers its
    operand by). ``reduce`` sums contributions: a reduce-scatter,
    all-reduce or all-to-all (XLA:TPU spells a reduce-scatter as the latter
    two), or a collective-permute that carries partial sums (its data comes
    from a matmul, an add or a zero accumulator: the ring a partitioned
    matmul reduce-scatters its result by). Whose data it is, a parameter's
    or a gradient's or an activation's, is a second question
    (``weight_shaped``): in the one four-chip cell every collective is an
    activation's, so the names say what is done and not what for."""
    instructions = module["instructions"]
    instr = instructions.get(name)
    if instr is None:
        return UNKNOWN
    base = re.sub(r"(-start|-done)$", "", instr.opcode)
    if base == "all-gather":
        return ASSEMBLE
    if base in ("reduce-scatter", "all-reduce", "all-to-all"):
        return REDUCE
    if base != "collective-permute":
        return UNKNOWN
    seen, frontier = {name}, [name]
    for _ in range(2 * REACH):
        frontier = [
            m for n in frontier for m in instructions[n].operands
            if m in instructions and m not in seen
        ]
        seen.update(frontier)
        for origin in frontier:
            opcode = instructions[origin].opcode
            if opcode in ("add", "broadcast", "constant") or is_matmul(
                module, origin
            ):
                return REDUCE
        frontier = [
            m for m in frontier if instructions[m].opcode in PASS_THROUGH
        ]
    return ASSEMBLE


def weight_shaped(module: dict, name: str) -> bool:
    """The collective's buffer has the shape of a parameter (or of one
    layer of a stacked one), and not an activation's."""
    instr = module["instructions"].get(name)
    return instr is not None and instr.dims in module["weight_dims"]


# -- loading -------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Span:
    name: str  # without the prefix
    start: float
    end: float
    thread: str
    args: dict


@dataclasses.dataclass
class Profile:
    """What this module needs of an ``.xplane.pb``: ``trace`` as
    ``trace_reduce`` has it (no host spans), the ``run_id`` of device 0's
    module executions (parallel to ``trace.modules[0]``), the program's
    spans, and the host's clock when it learns that a ``run_id`` on device
    0 has ended."""

    trace: Trace
    run_ids: list
    spans: list
    completed: dict


def load(path: str) -> Profile:
    from jax.profiler import ProfileData

    lines_wanted = {
        "XLA Ops": "ops", "Async XLA Ops": "async_ops",
        "XLA Modules": "modules",
    }
    trace = Trace(ops={}, async_ops={}, modules={}, host_spans=[])
    runs, spans, completed = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        match = _DEVICE_PLANE.match(plane.name)
        if match:
            dev = int(match.group(1))
            for line in plane.lines:
                kind = lines_wanted.get(line.name)
                if kind is None:
                    continue
                into = getattr(trace, kind).setdefault(dev, [])
                for ev in line.events:
                    into.append(_event(ev))
                    if kind == "modules" and dev == 0:
                        runs.append((into[-1].start, _stat(ev, "run_id")))
        elif plane.name == _HOST_PLANE:
            for number, line in enumerate(plane.lines):
                # a line is a thread; Python's threads can share a name
                thread = f"{line.name}#{number}"
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        e = _event(ev)
                        spans.append(Span(
                            name[len(SPAN_PREFIX):], e.start, e.end,
                            thread, dict(ev.stats),
                        ))
                    elif name == COMPLETE_EVENT:
                        stats = dict(ev.stats)
                        if stats.get("device_ordinal", 0) == 0:
                            completed.setdefault(
                                stats.get("run_id"), ev.start_ns * 1e-9
                            )
    for per_device in (trace.ops, trace.async_ops, trace.modules):
        for events in per_device.values():
            events.sort(key=lambda e: e.start)
    runs.sort()
    spans.sort(key=lambda s: (s.start, -s.end))
    return Profile(
        trace=trace, run_ids=[run_id for _, run_id in runs], spans=spans,
        completed=completed,
    )


def _event(ev) -> Event:
    return Event(
        ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
    )


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def find_xplane() -> str | None:
    """The run's profile. ``run.main`` points ``GRAFT_RUN_DIR`` at
    ``<out_dir>/run`` and traces into ``<out_dir>/trace`` (cleared before
    the run), so it is beside the telemetry directory; None where no such
    directory is there. (``ReadContext`` carries no path: PERF.md section
    7 asks for ``xplane_path`` on it.)"""
    beside = os.path.join(
        os.path.dirname(os.environ.get("GRAFT_RUN_DIR", "")), "trace"
    )
    found = sorted(glob.glob(
        os.path.join(beside, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


# -- the device's time, by what the program called it ----------------------------


def with_modules(events, modules, lo: float, hi: float):
    """``(event, module name)`` for each of ``events`` that touches ``[lo,
    hi]``: an op belongs to the program execution whose interval holds its
    start (instruction names repeat between programs); ``""`` where none
    does. ``modules`` are sorted by start."""
    starts = [m.start for m in modules]
    for ev in events:
        if ev.end <= lo or ev.start >= hi:
            continue
        i = bisect.bisect_right(starts, ev.start) - 1
        inside = i >= 0 and ev.start < modules[i].end
        yield ev, module_name(modules[i].name) if inside else ""


def instruction_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def reduce_device(trace: Trace, programs: dict, lo: float, hi: float) -> dict:
    """Seconds of device 0's ops in ``[lo, hi]`` by phase and component,
    in matmuls, and its exposed collective seconds by purpose and phase.
    ``programs`` is ``{module name: parse_hlo(...)}``."""
    table: dict = {}
    compute, matmul_s, unjoined_s, weight_shaped_s = [], 0.0, 0.0, 0.0
    waits = {ASSEMBLE: [], REDUCE: [], UNKNOWN: []}
    transfers = {ASSEMBLE: [], REDUCE: [], UNKNOWN: []}
    wait_phase = []  # (interval, purpose, phase) of the ops line's collectives
    modules = trace.modules.get(0, [])
    for ev, mod in with_modules(trace.ops.get(0, []), modules, lo, hi):
        fam = op_family(ev.name)
        if fam in CONTAINER_FAMILIES:
            continue
        span = (max(ev.start, lo), min(ev.end, hi))
        seconds = span[1] - span[0]
        program = programs.get(mod)
        name = instruction_name(ev.name)
        if program is None or name not in program["instructions"]:
            unjoined_s += seconds
            op_name = ""
        else:
            op_name = scope_of(program, name)
            if is_matmul(program, name):
                matmul_s += seconds
        key = (phase(op_name), component(op_name))
        table[key] = table.get(key, 0.0) + seconds
        if is_collective(fam):
            purpose = collective_purpose(program, name) if program else UNKNOWN
            waits[purpose].append(span)
            wait_phase.append((span, purpose, key[0]))
            if program and weight_shaped(program, name):
                weight_shaped_s += seconds
        else:
            compute.append(span)
    for ev, mod in with_modules(trace.async_ops.get(0, []), modules, lo, hi):
        if not is_collective(op_family(ev.name)):
            continue
        program, name = programs.get(mod), instruction_name(ev.name)
        purpose = collective_purpose(program, name) if program else UNKNOWN
        transfers[purpose].append((max(ev.start, lo), min(ev.end, hi)))
    compute_u = union(compute)
    everything = [s for group in (*waits.values(), *transfers.values())
                  for s in group]
    left = subtract(union(everything), compute_u)  # exposed, all purposes
    exposed_s = total(left)
    exposed = dict.fromkeys(waits, 0.0)
    exposed_phase: dict = {}
    # a moment goes first to the collective op the core is executing (the
    # ops line), then to a transfer in flight: a partition of ``left``
    for span, purpose, ph in wait_phase:
        taken = total([span]) - total(subtract([span], left))
        exposed[purpose] += taken
        key = f"{purpose}/{ph}"
        exposed_phase[key] = exposed_phase.get(key, 0.0) + taken
    left = subtract(left, union([s for g in waits.values() for s in g]))
    for purpose in (ASSEMBLE, REDUCE, UNKNOWN):
        mine = union(transfers[purpose])
        rest = subtract(left, mine)
        taken = total(left) - total(rest)
        exposed[purpose] += taken
        key = f"{purpose}/in_flight"
        exposed_phase[key] = exposed_phase.get(key, 0.0) + taken
        left = rest
    busy = union(compute + [s for g in waits.values() for s in g])
    return {
        "table": table, "busy": busy, "busy_s": total(busy),
        "ops_s": sum(table.values()), "matmul_s": matmul_s,
        "unjoined_s": unjoined_s, "exposed_s": exposed_s,
        "collective_weight_shaped_s": weight_shaped_s,
        "exposed": exposed, "exposed_phase": exposed_phase,
    }


# -- the two planes' clocks -------------------------------------------------------


def completion_lag(profile: Profile) -> dict | None:
    """Milliseconds from an execution's end on device 0 to the host's
    ``CompleteCallbacks`` for the same ``run_id``. The host learns of an
    end after it happened, so on one clock the lag is positive, its minimum
    and median a millisecond or two whenever in the profile the pair falls
    (a busy host learns later: the maximum says nothing). Fitted as a line
    (PR 24) the planes' rate was 1 to 2e-4 and the offset under 3 ms in
    sixteen profiles, so host spans need no map; a negative minimum, or a
    median of tens of milliseconds, would say that has changed."""
    lags = sorted(
        1e3 * (profile.completed[r] - m.end)
        for m, r in zip(profile.trace.modules.get(0, []), profile.run_ids)
        if r in profile.completed
    )
    if not lags:
        return None
    return {
        "pairs": len(lags), "lag_ms_min": lags[0],
        "lag_ms_median": lags[len(lags) // 2], "lag_ms_max": lags[-1],
    }


# -- host spans ------------------------------------------------------------------


def self_times(spans) -> dict:
    """Per span name, seconds inside its spans and not inside a span
    nested in them on the same thread (``spans`` sorted by start, longest
    first)."""
    out: dict = {}
    stacks: dict = {}
    for span in spans:
        stack = stacks.setdefault(span.thread, [])
        while stack and stack[-1].end <= span.start:
            stack.pop()
        length = span.end - span.start
        out[span.name] = out.get(span.name, 0.0) + length
        if stack and span.end <= stack[-1].end:
            parent = stack[-1].name
            out[parent] = out.get(parent, 0.0) - length
        stack.append(span)
    return out


def main_thread(spans) -> str | None:
    """The thread that dispatches: the one with most dispatch and facade
    spans."""
    counts: dict = {}
    for s in spans:
        if s.name.endswith(".dispatch") or s.name.startswith("facade."):
            counts[s.thread] = counts.get(s.thread, 0) + 1
    return max(counts, key=counts.get) if counts else None


def gaps_by_span(gaps, spans, top: int = 6) -> list:
    """``trace_reduce.attribute_gaps`` over the program's spans."""
    named = attribute_gaps(gaps, [Event(s.name, s.start, s.end) for s in spans])
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])][:top]


# -- one analysis a run -----------------------------------------------------------

_CACHE: dict = {}


def program_modules() -> tuple:
    """``({module name: parse_hlo(...)}, facts)`` of the programs this
    process ran, from the program's own ``program_texts``; empty where the
    program has none (before PR 24)."""
    from pytorch_distributedtraining_tpu.observe import profiling

    texts_of = getattr(profiling, "program_texts", None)
    if texts_of is None:
        return {}, {"texts": 0}
    t0 = time.perf_counter()
    modules = {}
    for text in texts_of():
        module = parse_hlo(text)
        modules.setdefault(module["module"], module)
    return modules, {
        "texts": len(modules), "texts_s": time.perf_counter() - t0,
    }


def analyse(profile: Profile, programs: dict) -> dict:
    """Everything the readers read, from a loaded profile."""
    out: dict = {
        "device": None, "spans": None, "clock": None, "gaps": None,
        "steps": None,
    }
    trace, spans = profile.trace, profile.spans
    if spans:
        selfs = self_times(spans)
        collates = [s for s in spans if s.name == "loader.collate"]
        out["spans"] = {
            "names": sorted({s.name for s in spans}),
            "self_ms": {k: 1e3 * v for k, v in sorted(selfs.items())},
            "facade_self_s": sum(
                selfs.get(n, 0.0) for n in FACADE_PYTHON_SPANS
            ),
            "facade_batches": sum(
                s.name in FACADE_BATCH_SPANS for s in spans
            ),
            "loader_produce_s": sum(
                s.end - s.start for s in spans if s.name in LOADER_SPANS
            ),
            "loader_batches": len(collates),
            "loader_worker_s": sum(
                float(s.args.get("worker_s", 0.0)) for s in collates
            ),
        }
    window = traced_steps(trace)
    if window is None or 0 not in trace.ops:
        return out
    lo, hi, steps = window["lo"], window["hi"], window["steps"]
    out["clock"] = completion_lag(profile)
    device = reduce_device(trace, programs, lo, hi)
    # the same trace as the harness reads it, from its own window: what
    # ``device_ms_per_step`` prints, and how far a clipped first step
    # makes it read short
    h_lo, h_hi, h_steps = step_window(trace, STEP_MODULES)
    harness_ms = 1e3 * trace_reduce.reduce_device(
        trace, 0, h_lo, h_hi
    )["busy_s"] / h_steps
    device_ms = 1e3 * device["busy_s"] / steps
    out["steps"] = {
        **window, "device_ms_per_step": device_ms,
        "harness_device_ms_per_step": harness_ms,
        "harness_short_pct": 100.0 * (1.0 - harness_ms / device_ms),
    }
    gaps = subtract([(lo, hi)], device.pop("busy"))
    thread = main_thread(spans)
    out["gaps"] = {
        "idle_s": total(gaps),
        "by_span": gaps_by_span(
            gaps, [s for s in spans if s.thread == thread]
        ),
    }
    table = device.pop("table")
    by_phase = {p: 0.0 for p in PHASES}
    by_component: dict = {}
    for (ph, comp), seconds in table.items():
        by_phase[ph] += seconds
        by_component[comp] = by_component.get(comp, 0.0) + seconds
    out["device"] = {
        "steps": steps, "window_s": hi - lo, "joined": bool(programs),
        "phase_s": by_phase, "component_s": by_component,
        "table_ms_per_step": {
            f"{ph}/{comp}": 1e3 * s / steps
            for (ph, comp), s in sorted(table.items(), key=lambda kv: -kv[1])
        },
        **device,
    }
    return out


def traced_steps(trace: Trace) -> dict | None:
    """The window of the per-step numbers on device 0: from the SECOND
    start of a step program to the last, and the whole steps between. A
    profile that opens while a step runs holds that execution from the
    profile's opening on (``first_execution_traced_share`` of its length),
    and ``trace_reduce.step_window`` counts it as a whole step: per-step
    milliseconds then read ``(n - 1 + share) / n`` of what they are (3-5%
    short in PR 22's records, which took it for a clock). So the first
    execution is left out, clipped or not. None for fewer than three."""
    runs = [
        m for m in trace.modules.get(0, [])
        if module_name(m.name) in STEP_MODULES
    ]
    if len(runs) < 3:
        return None
    lengths = sorted(m.end - m.start for m in runs[1:])
    median = lengths[len(lengths) // 2]
    return {
        "lo": runs[1].start, "hi": runs[-1].start, "steps": len(runs) - 2,
        "executions": len(runs),
        "first_execution_traced_share": min(
            1.0, (runs[0].end - runs[0].start) / median
        ) if median > 0 else None,
    }


def analysis(ctx) -> dict | None:
    """The run's analysis, made once (the first reader pays) and printed
    once to stderr. None where the run left no profile."""
    if "analysis" in _CACHE:
        return _CACHE["analysis"]
    result = None
    path = find_xplane()
    if path is not None:
        t0 = time.perf_counter()
        profile = load(path)
        programs, facts = ({}, {"texts": 0})
        if 0 in profile.trace.ops:
            programs, facts = program_modules()
        result = analyse(profile, programs)
        result["facts"] = {
            **facts, "xplane": path,
            "analysis_s": time.perf_counter() - t0,
        }
        print(json.dumps({"program_trace": result}), file=sys.stderr,
              flush=True)
    _CACHE["analysis"] = result
    return result


def device_value(ctx, pick):
    """``pick(device facts)`` per optimizer step in milliseconds, for the
    readers of the device trace; None without a device plane or without
    the program's texts to join it to."""
    found = analysis(ctx)
    device = found and found["device"]
    if not device or not device["joined"]:
        return None
    return 1e3 * pick(device) / device["steps"]
