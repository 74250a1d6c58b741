"""Compiled-HLO collective auditing: prove a sharded program's wire plan.

The runtime tests prove sharded configs converge; this module proves the
*compiler* emitted the communication pattern a policy promises — catching
GSPMD silently replicating (a constraint backing off to a full-tensor
all-reduce plus full-size update math), which a loss curve cannot see.
The reference stack has no equivalent: torch DDP/fairscale hand-write
their NCCL calls, so "which collectives run" is static; under XLA it is a
compiler decision and deserves an assertion surface (SURVEY §5 aux
tooling).

Backend note: the XLA:CPU pass pipeline lacks the reduce-scatter-creator
rewrite, so a ZeRO-2 grad constraint compiles there as its logical form —
a (possibly tuple-combined) full all-reduce followed by ``dynamic-slice``
to the shard — while XLA:TPU emits a literal ``reduce-scatter``. Audits
that must hold on both backends should accept either form; see
``has_logical_reduce_scatter``.

Everything here parses ``compiled.as_text()`` through ONE tokenizer
(:func:`tokenize_hlo`): instructions are continuation-merged (long operand
lists may wrap across physical lines) and tagged with their enclosing
computation, so ops inside fusion bodies attribute correctly. The three
audits below and the ``analyze`` rule registry all consume the same
tokens — there is no per-audit line parsing.

Typical use::

    hlo = step.compiled_text(state, batch)       # or any .compile().as_text()
    inv = collective_inventory(hlo)
    assert any(op.kind == "all-gather" for op in inv)
"""

from __future__ import annotations

import re
from dataclasses import dataclass

_OP_RE = re.compile(
    r"\b(all-reduce|reduce-scatter|all-gather|collective-permute|"
    r"all-to-all)(?:-start)?\("
)
_SHAPE_RE = re.compile(r"\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_PCT_NAME_RE = re.compile(r"%([\w.-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.$-]+)")


def _elems(group: str) -> int:
    n = 1
    for d in group.split(","):
        if d:
            n *= int(d)
    return n


# -- the shared tokenizer -----------------------------------------------------


@dataclass(frozen=True)
class HloInstruction:
    """One instruction in an HLO text module, continuation-merged.

    ``text`` is the full instruction with wrapped operand lines joined by a
    space; ``computation`` names the enclosing computation (fusion bodies
    are their own computations in HLO text, so "is this op inside a
    fusion?" is a string compare, not a heuristic).
    """

    name: str         # result name, leading % stripped
    computation: str  # enclosing computation ("" before the first header)
    text: str         # merged instruction text, stripped

    def first_operand(self, op_token: str) -> str | None:
        """Name of the first operand of ``op_token`` in this instruction."""
        return _first_operand(self.text, op_token)

    def result_elems(self, op_token: str) -> list[int]:
        """Element counts of every shape group left of ``op_token``
        (tuple-shaped results report each member)."""
        lhs = self.text.split(op_token, 1)[0]
        return [_elems(g) for g in _SHAPE_RE.findall(lhs)]


def tokenize_hlo(hlo_text: str) -> tuple:
    """Parse HLO text into :class:`HloInstruction` tokens, in module order.

    Handles both HLO text styles (``%name = ...`` long form and bare-name
    short form), tracks computation boundaries (``name (...) -> ... {`` /
    ``}``), and merges physical continuation lines — an instruction whose
    operand list wraps is ONE token. Non-instruction lines (module header,
    computation headers/braces) produce no tokens.
    """
    out: list[HloInstruction] = []
    parts: list[str] | None = None  # accumulating instruction, or None
    name = ""
    comp = ""

    def flush():
        nonlocal parts
        if parts is not None:
            out.append(HloInstruction(name, comp, " ".join(parts)))
            parts = None

    for line in hlo_text.splitlines():
        stripped = line.strip()
        if line.rstrip().endswith("{") and "->" in line:
            # computation header: `[ENTRY] %name (params) -> shape {`
            flush()
            comp = (
                line.split("(")[0].replace("ENTRY", "").strip().lstrip("%")
            )
            continue
        if stripped == "}":
            flush()
            continue
        d = _DEF_RE.match(line)
        if d is not None:
            flush()
            name = d.group(1)
            parts = [stripped]
        elif parts is not None and stripped:
            parts.append(stripped)  # continuation of a wrapped operand list
    flush()
    return tuple(out)


def _first_operand(line: str, op_token: str) -> str | None:
    """Name of the first operand of ``op_token`` on ``line``.

    Handles both HLO text styles: the long form prints ``%name`` (possibly
    after an inline tuple-type annotation), the short form prints bare
    names with no types.
    """
    after = line.split(op_token, 1)[1]
    m = _PCT_NAME_RE.search(after)
    if m is not None:
        return m.group(1)
    tok = after.split(",")[0].split(")")[0].strip()
    return tok or None


# -- collective inventory -----------------------------------------------------


@dataclass(frozen=True)
class CollectiveOp:
    """One collective in a compiled HLO module."""

    kind: str        # all-reduce | reduce-scatter | all-gather | ...
    max_elems: int   # largest result-tensor element count (tuple-aware)
    line: str        # the HLO instruction text, for debugging failed asserts

    def __repr__(self) -> str:  # keep pytest output readable
        return f"CollectiveOp({self.kind}, {self.max_elems})"


def collective_inventory(hlo_text: str) -> list[CollectiveOp]:
    """Parse a compiled HLO module's collectives with result sizes.

    Sizes come from the *result* type on the left of the op token
    (per-partition shapes in an SPMD module); tuple-shaped combined
    collectives report the largest member. Works on
    ``compiled.as_text()`` output.
    """
    out = []
    for ins in tokenize_hlo(hlo_text):
        m = _OP_RE.search(ins.text)
        if m is None:
            continue
        sizes = ins.result_elems(m.group(0))
        out.append(
            CollectiveOp(m.group(1), max(sizes) if sizes else 1, ins.text)
        )
    return out


# Result-type tokens a quantized gradient collective may carry on the
# wire. ``f16`` is here with a caveat: XLA:CPU's float-support
# legalization rewrites f8 collectives to f16 (the same backend behavior
# :func:`has_logical_reduce_scatter` documents for its pattern), so on
# the CPU test backend an fp8 wire shows up as f16 — on TPU the f8
# dtypes appear directly. bf16 is deliberately NOT narrow: nothing in
# the quantized transport emits it, so a bf16 grad collective means the
# wire format silently fell back to plain mixed-precision traffic.
WIRE_NARROW_DTYPES = frozenset(
    {"s8", "u8", "f8e4m3fn", "f8e4m3", "f8e4m3b11fnuz", "f8e5m2", "f16"}
)

_DTYPE_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


@dataclass(frozen=True)
class WireCollective:
    """One collective with its wire dtype and total payload elements."""

    kind: str    # all-reduce | reduce-scatter | all-gather | all-to-all | ...
    dtype: str   # result dtype token ("s8", "f16", "f32", "f8e4m3fn", ...)
    elems: int   # total result elements (tuple members SUMMED, not maxed)
    line: str    # the HLO instruction text, for debugging failed asserts

    def __repr__(self) -> str:  # keep pytest output readable
        return f"WireCollective({self.kind}, {self.dtype}, {self.elems})"


def wire_inventory(hlo_text: str) -> list[WireCollective]:
    """Parse a module's collectives with their wire dtypes.

    The dtype comes from the *result* type left of the op token — for a
    tuple-shaped result (XLA:CPU decomposes ``all-to-all`` into one tuple
    member per peer) every member shares the dtype and ``elems`` sums
    them, so ``elems * itemsize`` approximates the bytes the op moves per
    partition. The bytes-on-wire audit
    (``analyze.hlo_rules.wire_backoff``) is built on this inventory.
    """
    out = []
    for ins in tokenize_hlo(hlo_text):
        m = _OP_RE.search(ins.text)
        if m is None:
            continue
        lhs = ins.text.split(m.group(0), 1)[0]
        groups = _DTYPE_SHAPE_RE.findall(lhs)
        dtype = groups[0][0] if groups else ""
        elems = sum(_elems(g) for _, g in groups) if groups else 1
        out.append(WireCollective(m.group(1), dtype, elems, ins.text))
    return out


def max_all_reduce_elems(hlo_text: str) -> int:
    """Largest all-reduce result in the module (0 when none).

    The headline audit number for ZeRO-2+: after the TPU reduce-scatter
    rewrite, no *gradient-sized* all-reduce should remain — only scalar
    loss/grad-norm reductions.
    """
    sizes = [
        op.max_elems
        for op in collective_inventory(hlo_text)
        if op.kind == "all-reduce"
    ]
    return max(sizes, default=0)


# ops that forward their first operand's value unchanged (modulo
# layout/shape/dtype) — a dynamic-slice reading *through* one of these
# still slices the all-reduce's result
_PASSTHROUGH_OPS = (
    "get-tuple-element(",
    "bitcast(",
    "bitcast-convert(",
    "copy(",
    "reshape(",
    "transpose(",
    "convert(",
    # async completion: -done's first operand is the -start's token and its
    # value is the reduction result
    "all-reduce-done(",
)


def has_logical_reduce_scatter(hlo_text: str, shard_elems: int) -> bool:
    """True when the module reduce-scatters — literally, or in the CPU
    pipeline's unfused form: an all-reduce whose result (possibly through
    get-tuple-element / bitcast / reshape-style pass-through ops) is
    ``dynamic-slice``'d down to a ``shard_elems``-sized shard.

    The slice must actually *read the all-reduce's output*: a module that
    happens to contain some unrelated shard-sized dynamic-slice (an
    embedding lookup, an all-gather window) plus a full-tensor all-reduce
    is exactly the GSPMD-backed-off-to-replication pattern this audit
    exists to catch, and must return False.
    """
    inv = collective_inventory(hlo_text)
    if any(op.kind == "reduce-scatter" for op in inv):
        return True
    if not any(op.kind == "all-reduce" for op in inv):
        return False

    # pass 1 (HLO prints def-before-use within a computation): seed with
    # all-reduce result names, propagate through pass-through ops, and
    # record every shard-sized dynamic-slice plus every fusion call —
    # XLA:CPU routinely fuses the slice, so the chain is
    # all-reduce → fusion(operands incl. partition-id) → body dynamic-slice
    ar_names: set[str] = set()
    ds_comps: list[tuple[str, str]] = []  # (computation, operand)
    fusion_calls: list[tuple[list[str], str]] = []  # (operands, called comp)
    for ins in tokenize_hlo(hlo_text):
        m = _OP_RE.search(ins.text)
        if m is not None and m.group(1) == "all-reduce":
            ar_names.add(ins.name)
            continue
        for op_token in _PASSTHROUGH_OPS:
            if op_token in ins.text:
                if ins.first_operand(op_token) in ar_names:
                    ar_names.add(ins.name)
                break
        if " fusion(" in ins.text:
            args = ins.text.split(" fusion(", 1)[1].split("kind=")[0]
            called = _CALLS_RE.search(ins.text)
            fusion_calls.append(
                (_PCT_NAME_RE.findall(args), called.group(1) if called else "")
            )
        if "dynamic-slice(" in ins.text:
            if any(
                e == shard_elems
                for e in ins.result_elems("dynamic-slice(")
            ):
                ds_comps.append(
                    (ins.computation, ins.first_operand("dynamic-slice(") or "")
                )

    # pass 2: a shard-sized slice counts when it reads an all-reduce result
    # directly, or sits in a fusion body whose caller feeds it one
    # (fusion-granularity precision: good enough to reject slices in
    # fusions with no reduction input at all — the coincidental case)
    for _, operand in ds_comps:
        if operand in ar_names:
            return True
    ar_fed = {
        called
        for operands, called in fusion_calls
        if called and any(o in ar_names for o in operands)
    }
    return any(comp in ar_fed for comp, _ in ds_comps)


# -- hierarchical (two-level) collective audit -------------------------------

_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[0-9, ]*\}(?:,\{[0-9, ]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def replica_groups(line: str) -> list | None:
    """Parse one collective's ``replica_groups`` attribute into explicit
    id groups. Handles both HLO spellings: the literal form
    ``{{0,1},{2,3}}`` and the iota form ``[G,S]<=[dims](T(perm))`` —
    reshape(transpose(iota(prod(dims)), perm), (G, S)). None when the
    line carries no parsable groups (flat/implicit grouping)."""
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m is not None:
        out = []
        for grp in m.group(1).split("},{"):
            ids = [int(t) for t in grp.strip("{} ").split(",") if t.strip()]
            if ids:
                out.append(ids)
        return out or None
    m = _GROUPS_IOTA_RE.search(line)
    if m is not None:
        import numpy as _np

        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(t) for t in m.group(3).split(",") if t]
        ids = _np.arange(int(_np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(t) for t in m.group(4).split(",") if t]
            ids = ids.transpose(perm)
        return [list(map(int, row)) for row in ids.reshape(g, s)]
    return None


def partition_slice_ids(mesh, dcn_axis: str) -> list:
    """Slice (DCN) coordinate of every SPMD partition id, in order.

    Partition ids follow the mesh's flattened device order (the
    computation's device assignment), so partition ``p``'s slice is the
    ``dcn_axis`` coordinate of flat position ``p`` in ``mesh.devices``.
    """
    import numpy as _np

    shape = _np.asarray(mesh.devices).shape
    ax = list(mesh.axis_names).index(dcn_axis)
    return [
        int(_np.unravel_index(p, shape)[ax])
        for p in range(int(_np.prod(shape)))
    ]


# collective kinds that carry gradient payload during a sync (all-gather
# re-assembles the scattered shard; collective-permute never reduces)
_REDUCE_KINDS = frozenset({"all-reduce", "reduce-scatter", "all-to-all"})


@dataclass(frozen=True)
class HierarchyFinding:
    """One collective classified against the slice boundary."""

    kind: str
    dtype: str
    elems: int        # per-partition result elements (tuple members summed)
    crossing: bool    # replica groups span >= 2 slices
    grouped: bool     # replica_groups were parsable (False = implicit/flat)
    line: str

    def __repr__(self) -> str:  # keep pytest output readable
        where = "dcn" if self.crossing else "ici"
        return f"HierarchyFinding({self.kind}, {self.dtype}, {self.elems}, {where})"


@dataclass(frozen=True)
class HierarchyAudit:
    """Verdict: do the DCN crossings carry only reduce-scattered bytes?

    The two-level contract: with a within-slice (ICI) axis of size k, any
    collective whose replica groups cross the slice boundary must operate
    on at most ``ceil(grad_elems / k)`` elements (+ one k of padding per
    op) — the payload AFTER the within-slice reduce-scatter. A crossing
    collective at full ``grad_elems`` is a flat ring over DCN, the exact
    pattern :func:`hierarchy_audit` exists to reject. ``dcn_bytes`` sums
    the per-partition bytes of every crossing collective — the number the
    hier bench publishes against its flat twin.
    """

    dcn_axis: str
    ici_size: int
    grad_elems: int
    findings: tuple

    @property
    def crossing(self) -> tuple:
        return tuple(f for f in self.findings if f.crossing)

    @property
    def max_crossing_elems(self) -> int:
        return max((f.elems for f in self.crossing), default=0)

    @property
    def dcn_bytes(self) -> int:
        from .opcost import dtype_bytes

        return sum(f.elems * dtype_bytes(f.dtype) for f in self.crossing)

    @property
    def shard_elems_bound(self) -> int:
        """Largest f32 payload one DCN crossing may carry: the
        reduce-scattered shard plus a padding allowance (buckets pad to
        the ICI width)."""
        if self.ici_size <= 1:
            return self.grad_elems
        return -(-self.grad_elems // self.ici_size) + self.ici_size

    @property
    def flat_rings(self) -> tuple:
        """Crossing reduce collectives that exceed the scattered-shard
        *bytes* (``shard_elems_bound`` x 4). The bound is byte-
        denominated because DCN cares about bytes: a quantized wire's
        crossing (``CompressedGradStep``'s s8/f8 all-to-all runs at full
        element count but 1/4 the width) is the hierarchy's narrow form,
        not a flat ring — while an f32 ring at full size always trips."""
        from .opcost import dtype_bytes

        bound_bytes = self.shard_elems_bound * 4
        return tuple(
            f
            for f in self.crossing
            if f.kind in _REDUCE_KINDS
            and f.elems * dtype_bytes(f.dtype) > bound_bytes
        )

    @property
    def ok(self) -> bool:
        """True when no DCN crossing exceeds the reduce-scattered bound.

        Vacuously true on a single-slice mesh (nothing crosses) and for
        modules with no parsable crossing collectives.
        """
        return not self.flat_rings


def hierarchy_audit(
    hlo_text: str, mesh, *, grad_elems: int, dcn_axis: str | None = None
) -> HierarchyAudit:
    """Classify a compiled step's collectives against the slice boundary.

    ``grad_elems`` is the total gradient element count of the step (sum
    over param leaves) — the payload a flat dp ring would carry in one
    crossing. ``dcn_axis`` defaults to the mesh's registered slice axis
    (:func:`runtime.mesh.slice_axis`); a mesh without one has no slice
    boundary and audits vacuously clean. Collectives whose
    ``replica_groups`` are unparsable/implicit span ALL partitions and
    are conservatively classed as crossing when the mesh has >1 slice.
    """
    if dcn_axis is None:
        from ..runtime.mesh import slice_axis as _slice_axis

        dcn_axis = _slice_axis(mesh)
    findings: list[HierarchyFinding] = []
    if dcn_axis is None:
        return HierarchyAudit(
            dcn_axis="", ici_size=1, grad_elems=int(grad_elems), findings=()
        )
    slices = partition_slice_ids(mesh, dcn_axis)
    n_slices = len(set(slices))
    ici_size = 1
    for a in mesh.axis_names:
        if a != dcn_axis and a in ("dp", "fsdp"):
            ici_size *= int(mesh.shape.get(a, 1))
    for w in wire_inventory(hlo_text):
        groups = replica_groups(w.line)
        if groups is None:
            crossing = n_slices > 1
            grouped = False
        else:
            crossing = any(
                len({slices[i] for i in grp if i < len(slices)}) > 1
                for grp in groups
            )
            grouped = True
        findings.append(
            HierarchyFinding(
                w.kind, w.dtype, w.elems, crossing, grouped, w.line
            )
        )
    return HierarchyAudit(
        dcn_axis=dcn_axis,
        ici_size=ici_size,
        grad_elems=int(grad_elems),
        findings=tuple(findings),
    )


def counts(hlo_text: str) -> dict[str, int]:
    """{kind: occurrences} — the one-line summary used by benchmarks."""
    agg: dict[str, int] = {}
    for op in collective_inventory(hlo_text):
        agg[op.kind] = agg.get(op.kind, 0) + 1
    return agg


# -- compute/communication overlap audit -------------------------------------


@dataclass(frozen=True)
class OverlapFinding:
    """One collective's overlap posture in a compiled module.

    ``async_form``: the compiler split it into ``-start``/``-done`` pairs
    (the precondition for the latency-hiding scheduler to move compute in
    between). ``hidden_ops``: instructions actually scheduled between the
    start and its done — 0 means the pair is back-to-back and the
    collective still sits on the critical path despite being async.
    """

    kind: str
    name: str
    async_form: bool
    hidden_ops: int
    line: str

    @property
    def schedulable(self) -> bool:
        return self.async_form and self.hidden_ops > 0

    def __repr__(self) -> str:  # keep pytest output readable
        form = "async" if self.async_form else "sync"
        return f"OverlapFinding({self.kind}, {form}, hidden={self.hidden_ops})"


@dataclass(frozen=True)
class OverlapAudit:
    """Module-level verdict over every collective's OverlapFinding."""

    findings: tuple

    @property
    def total(self) -> int:
        return len(self.findings)

    @property
    def blocking(self) -> tuple:
        """Collectives stuck on the critical path (sync, or empty pairs)."""
        return tuple(f for f in self.findings if not f.schedulable)

    @property
    def ok(self) -> bool:
        """True when every collective can be hidden behind compute."""
        return not self.blocking


def overlap_audit(hlo_text: str) -> OverlapAudit:
    """Audit whether a module's collectives are schedulable off the
    critical path.

    A collective printed in its synchronous form (``all-reduce(`` rather
    than ``all-reduce-start(``) blocks: XLA executes it inline, so the DDP
    grad reduction serializes with backward compute. An async pair only
    helps if the scheduler actually placed work between ``-start`` and
    ``-done`` — this counts the instructions in that window (parameters
    excluded) per pair. Works on ``compiled.as_text()`` output.
    """
    instrs = tokenize_hlo(hlo_text)
    findings = []
    for i, ins in enumerate(instrs):
        m = _OP_RE.search(ins.text)
        if m is None:
            continue
        kind = m.group(1)
        if f"{kind}-start(" not in ins.text:
            findings.append(
                OverlapFinding(kind, ins.name, False, 0, ins.text)
            )
            continue
        done_token = f"{kind}-done("
        hidden = 0
        for nxt in instrs[i + 1:]:
            if (
                done_token in nxt.text
                and nxt.first_operand(done_token) == ins.name
            ):
                break
            if " parameter(" not in nxt.text:
                hidden += 1
        findings.append(
            OverlapFinding(kind, ins.name, True, hidden, ins.text)
        )
    return OverlapAudit(tuple(findings))


def collectives_schedulable(hlo_text: str) -> bool:
    """True when every collective in the module can overlap with compute.

    Vacuously True for a module with no collectives (single-device step).
    """
    return overlap_audit(hlo_text).ok


# -- pipeline wire audit ------------------------------------------------------

_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")
_PAIRS_ATTR_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


@dataclass(frozen=True)
class PipelineAudit:
    """Verdict: does a compiled step's wire plan match its schedule table?

    The pipeline executor runs one scan per schedule *segment* and emits
    the fwd/bwd ``ppermute`` hop only in segments that move data on that
    channel — so the ``collective-permute`` instruction count is a
    schedule fingerprint (GPipe's disjoint phases: 2; 1F1B's steady state:
    more). ``fwd_instructions``/``bwd_instructions`` classify each
    instruction's ``source_target_pairs`` against the schedule's ring for
    that channel mapped onto concrete device ids (-1 = no mesh supplied,
    classification skipped).
    """

    schedule: str
    expected_permutes: int
    found_permutes: int
    expected_fwd: int
    expected_bwd: int
    fwd_instructions: int
    bwd_instructions: int
    unmatched: tuple  # HLO lines whose pair set matched neither channel

    @property
    def count_ok(self) -> bool:
        return self.found_permutes == self.expected_permutes

    @property
    def pairs_ok(self) -> bool:
        """Channel-level check (requires a mesh; vacuous without one)."""
        if self.fwd_instructions < 0:
            return True
        return (
            not self.unmatched
            and self.fwd_instructions == self.expected_fwd
            and self.bwd_instructions == self.expected_bwd
        )

    @property
    def ok(self) -> bool:
        return self.count_ok and self.pairs_ok


def _channel_device_pairs(mesh, axis_name: str, logical_pairs) -> frozenset:
    """Map a channel's logical (rank, rank) pairs to SPMD partition-id pairs.

    The SPMD partitioner emits ONE collective-permute covering every
    cross-section of the other mesh axes (each dp/fsdp replica permutes
    within its own pp ring), so the instruction's pair list is the union
    over those cross-sections. ``source_target_pairs`` name partitions —
    flat positions in ``mesh.devices``, as in :func:`partition_slice_ids`
    — not device ids: ``create_device_mesh`` lays a 2x2 of TPU chips out
    in ring order (ids 0, 1, 3, 2), and the two then differ.
    """
    import numpy as _np

    ax = list(mesh.axis_names).index(axis_name)
    positions = _np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    rings = _np.moveaxis(positions, ax, -1).reshape(-1, mesh.shape[axis_name])
    return frozenset(
        (int(ring[a]), int(ring[b])) for ring in rings for a, b in logical_pairs
    )


def pipeline_audit(hlo_text: str, schedule, mesh=None, axis_name: str = "pp"):
    """Audit a compiled pipeline step against its schedule table.

    ``schedule`` is a ``parallel.PipelineSchedule``. Counts the module's
    ``collective-permute`` instructions against
    ``schedule.expected_collective_permutes`` and — when ``mesh`` is given
    — checks every instruction's ``source_target_pairs`` is exactly the
    fwd or bwd channel ring (wrap pairs present iff the schedule is
    interleaved), with per-channel instruction counts matching the
    segment table. Run it on ``PipelineStep.compiled_text(...)``.
    """
    found: list[tuple[frozenset, str]] = []
    for ins in tokenize_hlo(hlo_text):
        m = _OP_RE.search(ins.text)
        if m is None or m.group(1) != "collective-permute":
            continue
        pm = _PAIRS_ATTR_RE.search(ins.text)
        pairs = frozenset(
            (int(a), int(b)) for a, b in _PAIR_RE.findall(pm.group(1))
        ) if pm else frozenset()
        found.append((pairs, ins.text))

    expected_fwd = sum(1 for _, _, f, _ in schedule.segments if f)
    expected_bwd = sum(1 for _, _, _, b in schedule.segments if b)
    nf = nb = -1
    unmatched: list[str] = []
    if mesh is not None:
        fset = _channel_device_pairs(
            mesh, axis_name, schedule.permute_pairs("fwd")
        )
        bset = _channel_device_pairs(
            mesh, axis_name, schedule.permute_pairs("bwd")
        )
        nf = nb = matched = 0
        for pairs, line in found:
            if fset == bset and pairs == fset:
                matched += 1
            elif pairs == fset:
                nf += 1
            elif pairs == bset:
                nb += 1
            else:
                unmatched.append(line)
        if fset == bset:
            # n_stages=2 full ring: both channels are {(0,1),(1,0)} so the
            # pair set can't tell them apart — only the total is checkable
            if matched == expected_fwd + expected_bwd:
                nf, nb = expected_fwd, expected_bwd
            else:
                nf, nb = matched, 0
    return PipelineAudit(
        schedule=schedule.name,
        expected_permutes=schedule.expected_collective_permutes,
        found_permutes=len(found),
        expected_fwd=expected_fwd,
        expected_bwd=expected_bwd,
        fwd_instructions=nf,
        bwd_instructions=nb,
        unmatched=tuple(unmatched),
    )
