"""From the profiler's ``.xplane.pb`` to numbers: busy union, idle share,
exposed collective time, the idle gaps by host span, the op families by time.

Reads the file jax's profiler writes, with ``jax.profiler.ProfileData`` and
nothing else (no profile plug-in, no converted ``trace.json.gz``). What a
TPU v5e trace holds (looked at by hand, PR 22): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per program execution,
named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO
instruction, named by its full HLO text ``%name.N = shape op(...), kind=...``,
no category stat) and ``Async XLA Ops`` (one event per asynchronous pair, from
``-start`` to ``-done``); and one plane ``/host:CPU`` whose thread lines hold
the ``TraceAnnotation`` spans. Host and device events share one clock, to
within a fraction of a millisecond.

All intervals are ``(start_s, end_s)`` from the start of the profile. The
arithmetic works on plain lists, so a hand-made :class:`Trace` checks it.
"""

from __future__ import annotations

import dataclasses
import re

SPAN_PREFIX = "cb/"  # the benchmark's own host spans
# families whose device time is communication; kept from
# observe.opcost's collective prefixes
COLLECTIVE_FAMILIES = (
    "all-gather", "all-reduce", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)
# control flow: their time is their bodies' ops', which the line also holds
CONTAINER_FAMILIES = ("while", "conditional", "call")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """What the reduction needs of a profile. ``ops``, ``async_ops`` and
    ``modules`` are per device ordinal."""

    ops: dict
    async_ops: dict
    modules: dict
    host_spans: list


# -- interval arithmetic ------------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint cover of ``intervals``."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a, b) -> list:
    """The part of union ``a`` that union ``b`` does not cover (both sorted
    and disjoint)."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


# -- names ---------------------------------------------------------------------


def op_family(hlo_text: str) -> str:
    """``%fusion.93 = bf16[...] fusion(...), kind=kOutput, calls=...`` ->
    ``fusion/kOutput``: the instruction's name without its number, and the
    fusion kind where the text gives one."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"\.\d+$", "", name)
    kind = re.search(r"\bkind=(\w+)", hlo_text)
    return f"{name}/{kind.group(1)}" if kind else name


def is_collective(family: str) -> bool:
    return family.startswith(COLLECTIVE_FAMILIES)


def module_name(event_name: str) -> str:
    """``jit__step(12573960010487406730)`` -> ``jit__step``."""
    return event_name.split("(", 1)[0]


# -- loading -------------------------------------------------------------------


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``. A profile with no ``/device:TPU:<n>`` plane
    (a CPU rehearsal) gives a Trace with host spans and no device events."""
    from jax.profiler import ProfileData

    lines_wanted = {
        "XLA Ops": "ops", "Async XLA Ops": "async_ops",
        "XLA Modules": "modules",
    }
    trace = Trace(ops={}, async_ops={}, modules={}, host_spans=[])
    for plane in ProfileData.from_file(path).planes:
        match = _DEVICE_PLANE.match(plane.name)
        if match:
            dev = int(match.group(1))
            for line in plane.lines:
                if line.name in lines_wanted:
                    getattr(trace, lines_wanted[line.name]).setdefault(
                        dev, []
                    ).extend(_event(ev) for ev in line.events)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                trace.host_spans.extend(
                    _event(ev) for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX)
                )
    for per_device in (trace.ops, trace.async_ops, trace.modules):
        for events in per_device.values():
            events.sort(key=lambda e: e.start)
    trace.host_spans.sort(key=lambda e: e.start)
    return trace


def _event(ev) -> Event:
    return Event(
        ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
    )


# -- reduction -----------------------------------------------------------------


def step_window(trace: Trace, step_modules, device: int = 0):
    """``(lo, hi, periods)``: from the start of the first execution of a
    step program (one of the names ``step_modules``) on ``device`` to the
    start of the last, and how many whole steps that covers. None when
    fewer than two executions were traced."""
    starts = [
        e.start for e in trace.modules.get(device, [])
        if module_name(e.name) in step_modules
    ]
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


def reduce_device(trace: Trace, device: int, lo: float, hi: float) -> dict:
    """Seconds of ``[lo, hi]`` on one device: busy (union of the executed
    ops), in collectives, and in collectives with no compute op running."""
    compute, waits, transfers = [], [], []
    families: dict = {}
    for ev in trace.ops.get(device, []):
        if ev.end <= lo or ev.start >= hi:
            continue
        fam = op_family(ev.name)
        span = (max(ev.start, lo), min(ev.end, hi))
        if fam in CONTAINER_FAMILIES:
            continue  # busy through its body's ops; no work of its own
        families[fam] = families.get(fam, 0.0) + span[1] - span[0]
        (waits if is_collective(fam) else compute).append(span)
    for ev in trace.async_ops.get(device, []):
        # an asynchronous pair's transfer runs from -start to -done while
        # other ops execute: collective time, and busy only where an op
        # of the XLA Ops line runs too
        if is_collective(op_family(ev.name)) and ev.end > lo and ev.start < hi:
            transfers.append((max(ev.start, lo), min(ev.end, hi)))
    busy = union(compute + waits)
    collective = waits + transfers
    compute_u, collective_u = union(compute), union(collective)
    return {
        "busy": busy,
        "busy_s": total(busy),
        "collective_s": total(collective_u),
        "exposed_collective_s": total(subtract(collective_u, compute_u)),
        "families": families,
    }


def attribute_gaps(gaps, spans) -> dict:
    """Idle seconds by what the host was doing: each moment of a gap goes
    to the innermost (shortest) host span that covers it, and to
    ``(no span)`` where none does."""
    inner_first = sorted(spans, key=lambda s: s.end - s.start)
    by_name: dict = {}
    for gap in gaps:
        left = [gap]
        for span in inner_first:
            if not left:
                break
            if span.end <= gap[0] or span.start >= gap[1]:
                continue
            rest = subtract(left, [(span.start, span.end)])
            taken = total(left) - total(rest)
            if taken > 0:
                by_name[span.name] = by_name.get(span.name, 0.0) + taken
            left = rest
        if left:
            by_name["(no span)"] = by_name.get("(no span)", 0.0) + total(left)
    return by_name


def reduce(trace: Trace, step_modules, top: int = 10) -> dict | None:
    """The whole reduction over the traced steps. Device 0 sets the window
    and carries the breakdown; busy is also averaged over all devices, and
    the worst device's idle share is given. None when the trace does not
    hold two executions of a step program."""
    window = step_window(trace, step_modules)
    if window is None or 0 not in trace.ops:
        return None
    lo, hi, periods = window
    length = hi - lo
    per_device = {
        dev: reduce_device(trace, dev, lo, hi) for dev in sorted(trace.ops)
    }
    first = per_device[0]
    gaps = subtract([(lo, hi)], first["busy"])
    gap_names = attribute_gaps(gaps, trace.host_spans)
    ranked = lambda d: [  # noqa: E731
        [name, seconds]
        for name, seconds in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "window_s": length,
        "steps": periods,
        "devices": len(per_device),
        "busy_s": first["busy_s"],
        "busy_mean_s": sum(d["busy_s"] for d in per_device.values())
        / len(per_device),
        "idle_share": 1.0 - first["busy_s"] / length,
        "idle_share_worst": max(
            1.0 - d["busy_s"] / length for d in per_device.values()
        ),
        "step_period_s": length / periods,
        "collective_s": first["collective_s"],
        "exposed_collective_s": first["exposed_collective_s"],
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
        "breakdown": {
            "device_ops": ranked(first["families"]),
            "idle_gaps": ranked(gap_names),
        },
    }
