"""Source-plane rules: host-side SPMD hazards the artifact planes miss.

The trace/hlo/runtime planes inspect what one process compiled or
measured; every rule here inspects what the *repo* says — the
:class:`~.astlint.SourceFacts` in ``ctx.source``. That is where
multi-controller SPMD's classic failure lives: rank-conditioned Python
gating a collective hangs the pod with no error on any rank, a hazard
invisible in any single rank's jaxpr or HLO (each rank's program is
individually fine; the *set* of programs diverges).

Rule catalog (severities documented in docs/STATIC_ANALYSIS.md):

- ``host-divergent-collective`` ERROR — a branch conditioned on
  ``process_index()`` / rank / host-id dominates a collective, barrier,
  or membership-generation call. The finding is a deadlock witness: it
  names the divergent branch condition and the gated call. Intentional
  asymmetric protocols (the launcher's single-publisher generation
  publish) carry a ``# graftcheck: ok(host-divergent-collective)``
  pragma — the pragma in the source is the audit trail.
- ``blocking-host-sync`` WARN — ``.block_until_ready()`` / ``.item()``
  / ``float()`` / ``np.asarray()`` on device values inside a timed loop,
  outside a cadence guard. A sync that feeds a timer stamp within the
  next few lines is the *correct* warm-then-time fence idiom and is
  exempt. Library scope only (package + drivers): benchmark scripts
  block on purpose — that is how you time.
- ``stdlib-only-violation`` ERROR — a module contracted as
  stdlib-importable (membership, fleet tooling, opcost/slo math, the
  serve router, the planner artifact layer, fault injection) imports
  jax/flax/optax/jaxlib at module level. Generalizes the old
  ``test_import_hygiene`` hand-rolled walker into a named rule.
- ``fault-site-drift`` ERROR — ``fault_point("x.y")`` /
  ``rules_for("x.y")`` sites vs the ``resilience.faults.SITES``
  registry vs the docs/RESILIENCE.md site table, all directions: a site
  called but unregistered can never fire from a plan; a site registered
  but never consumed is dead chaos surface; an undocumented site is
  invisible to whoever writes the fault plan.
- ``import-time-env-read`` WARN — a ``GRAFT_*`` env read that executes
  at import time in library code: the value freezes at first import, so
  a launcher that sets the knob after importing (or a test that
  monkeypatches the environment) silently reads the stale value.
  Script-style entry points (benchmarks/) are exempt — their import *is*
  their invocation.
- ``knob-undocumented`` ERROR / ``knob-dead`` WARN /
  ``knob-twin-mismatch`` ERROR — the GRAFT_* registry
  (:mod:`.knobs`) vs docs/KNOBS.md and the TPUConfig twin declarations.
- ``collective-lockstep`` ERROR — compiled programs must issue an
  identical ordered collective sequence on every rank: the per-rank
  sequences are reconstructed from HLO replica groups
  (``observe.hlo``), and any rank missing an op the others issue gets a
  named witness. This is the HLO half of the host-divergence join —
  ``host-divergent-collective`` catches the Python side before compile,
  this catches whatever made it into an executable.

Every rule returns ``[]`` when its facts are absent (``ctx.source`` is
None on artifact-plane runs), per the registry contract.
"""

from __future__ import annotations

import os

from .astlint import NON_STDLIB_IMPORTS, SourceFacts, collect_facts
from .findings import Finding, Severity
from .knobs import build_registry, config_twins, load_knobs_md
from .registry import AnalysisContext, rule, run_rules

# library scope for the WARN-class hygiene rules: importable code only.
# benchmarks/ and __graft_entry__.py are script entry points —
# still scanned (their env reads feed the knob registry, their gated
# collectives are real hazards) but exempt from import-time and
# host-sync hygiene, whose hazard model is "someone imports this".
_LIBRARY_PREFIXES = ("pytorch_distributedtraining_tpu/", "drivers/")

# a host sync this close above a timer call is a warm-then-time fence
_FENCE_WINDOW_LINES = 4

# modules contracted to import without jax present (stdlib + numpy).
# The launcher supervises and the fleet/serve tooling routes on hosts
# where the jax wheel may be broken mid-incident. Grow this list, never
# shrink it silently.
STDLIB_ONLY_MODULES = (
    "pytorch_distributedtraining_tpu/runtime/membership.py",
    "pytorch_distributedtraining_tpu/runtime/recovery_drill.py",
    "pytorch_distributedtraining_tpu/observe/trace.py",
    "pytorch_distributedtraining_tpu/observe/sink.py",
    "pytorch_distributedtraining_tpu/observe/goodput.py",
    "pytorch_distributedtraining_tpu/observe/slo.py",
    "pytorch_distributedtraining_tpu/observe/opcost.py",
    "pytorch_distributedtraining_tpu/observe/numerics.py",
    "pytorch_distributedtraining_tpu/observe/fleet.py",
    "pytorch_distributedtraining_tpu/serve/router.py",
    "pytorch_distributedtraining_tpu/serve/fleet.py",
    "pytorch_distributedtraining_tpu/analyze/plan.py",
    "pytorch_distributedtraining_tpu/analyze/astlint.py",
    "pytorch_distributedtraining_tpu/analyze/knobs.py",
    "pytorch_distributedtraining_tpu/resilience/faults.py",
    "pytorch_distributedtraining_tpu/resilience/outage.py",
    "pytorch_distributedtraining_tpu/parallel/reshard.py",
)

RESILIENCE_DOC = "docs/RESILIENCE.md"


def _in_library(path: str) -> bool:
    return path.startswith(_LIBRARY_PREFIXES)


def _facts(ctx) -> SourceFacts | None:
    src = ctx.source
    return src if isinstance(src, SourceFacts) else None


# -- host divergence ----------------------------------------------------------


@rule(
    "host-divergent-collective",
    "source",
    "rank-conditioned branch dominates a collective/barrier/generation "
    "call — a pod-wide deadlock witness",
)
def _host_divergent_collective(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    out = []
    for g in facts.gated_calls():
        if g.acknowledged:
            continue
        where = f"{g.path}:{g.call_line}"
        out.append(Finding(
            rule="host-divergent-collective",
            severity=Severity.ERROR,
            loc=f"source:{where}",
            message=(
                f"`{g.call}` is only reached under `if {g.gate_src}` "
                f"(line {g.gate_line}): ranks on the other side of that "
                "branch never issue it, and every rank that does blocks "
                "forever waiting for them"
            ),
            evidence=(
                f"gate {g.path}:{g.gate_line} `{g.gate_src}` -> "
                f"{g.call}() at {where}"
                + (f" in {g.func}()" if g.func else "")
                + "; if the asymmetry is the protocol (single publisher, "
                "follower-only wait), annotate the line with "
                "`# graftcheck: ok(host-divergent-collective)`"
            ),
        ))
    return out


@rule(
    "blocking-host-sync",
    "source",
    "device-value host sync inside a timed loop outside a cadence "
    "guard — the sync's latency lands inside the measurement",
)
def _blocking_host_sync(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    out = []
    for s in facts.host_syncs():
        if s.guarded or s.acknowledged or not _in_library(s.path):
            continue
        timers = facts.modules[s.path].timer_lines
        is_fence = any(
            0 < t - s.line <= _FENCE_WINDOW_LINES for t in timers
        )
        if is_fence:
            continue
        out.append(Finding(
            rule="blocking-host-sync",
            severity=Severity.WARN,
            loc=f"source:{s.path}:{s.line}",
            message=(
                f"`{s.kind}` blocks the host inside the timed loop at "
                f"line {s.loop_line}: the device pipeline drains every "
                "iteration and the stall is billed to the step time"
            ),
            evidence=(
                "guard it with a cadence check (`step % every == 0`), "
                "move it past the timed window, or annotate with "
                "`# graftcheck: ok(blocking-host-sync)` if the sync is "
                "the point"
            ),
        ))
    return out


# -- contracts ----------------------------------------------------------------


@rule(
    "stdlib-only-violation",
    "source",
    "a module contracted as stdlib-importable imports jax/flax at "
    "module level",
)
def _stdlib_only_violation(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    contract = ctx.extras.get("stdlib_only_modules", STDLIB_ONLY_MODULES)
    out = []
    for path in contract:
        mod = facts.modules.get(path)
        if mod is None:
            continue
        for imp, line in mod.toplevel_imports:
            root = imp.split(".")[0]
            if root in NON_STDLIB_IMPORTS:
                out.append(Finding(
                    rule="stdlib-only-violation",
                    severity=Severity.ERROR,
                    loc=f"source:{path}:{line}",
                    message=(
                        f"imports `{imp}` at module level but is "
                        "contracted stdlib-only: it must import on hosts "
                        "with no (or a broken) jax wheel — the "
                        "launcher, fleet tooling"
                    ),
                    evidence=(
                        "reach jax-side modules through "
                        "`sys.modules.get(...)` (see membership._tracer) "
                        "or a function-local import"
                    ),
                ))
    return out


@rule(
    "fault-site-drift",
    "source",
    "fault_point()/rules_for() sites vs resilience.faults.SITES vs the "
    "RESILIENCE.md site table, all directions",
)
def _fault_site_drift(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    if "fault_registry" in ctx.extras:
        registered = frozenset(ctx.extras["fault_registry"])
    else:
        from ..resilience.faults import SITES as registered  # stdlib-only
    if "fault_docs" in ctx.extras:
        documented = frozenset(ctx.extras["fault_docs"])
    elif facts.root:
        documented = _documented_fault_sites(facts.root)
        if documented is None:
            return [Finding(
                rule="fault-site-drift",
                severity=Severity.ERROR,
                loc=f"source:{RESILIENCE_DOC}",
                message="the fault-site table is missing",
                evidence=f"expected `| `x.y` | ... |` rows in {RESILIENCE_DOC}",
            )]
    else:
        return []  # snippet facts with no docs to compare against

    consumed: dict = {}
    for s in facts.fault_sites():
        consumed.setdefault(s.site, f"{s.path}:{s.line}")

    out = []
    for site in sorted(set(consumed) - registered):
        out.append(Finding(
            rule="fault-site-drift",
            severity=Severity.ERROR,
            loc=f"source:{consumed[site]}",
            message=(
                f"site `{site}` is consumed here but absent from "
                "resilience.faults.SITES — no fault plan can ever "
                "trigger it, and plan validation will reject the name"
            ),
            evidence="add it to SITES (and the RESILIENCE.md table)",
        ))
    for site in sorted(registered - set(consumed)):
        out.append(Finding(
            rule="fault-site-drift",
            severity=Severity.ERROR,
            loc="source:resilience/faults.py",
            message=(
                f"site `{site}` is registered in SITES but no "
                "fault_point()/rules_for() consumes it — dead chaos "
                "surface: plans naming it validate and then do nothing"
            ),
            evidence="wire a consumer or drop the registration",
        ))
    for site in sorted(registered - documented):
        out.append(Finding(
            rule="fault-site-drift",
            severity=Severity.ERROR,
            loc=f"source:{RESILIENCE_DOC}",
            message=(
                f"site `{site}` is registered but has no row in the "
                f"{RESILIENCE_DOC} site table — invisible to whoever "
                "writes the fault plan"
            ),
            evidence="add a `| `site` | what fires |` row",
        ))
    for site in sorted(documented - registered):
        out.append(Finding(
            rule="fault-site-drift",
            severity=Severity.ERROR,
            loc=f"source:{RESILIENCE_DOC}",
            message=(
                f"site `{site}` is documented but not in "
                "resilience.faults.SITES — the doc promises chaos the "
                "registry rejects"
            ),
            evidence="drop the stale row or register the site",
        ))
    return out


def _documented_fault_sites(root: str) -> frozenset | None:
    """Backticked `x.y` first-cell tokens of RESILIENCE.md table rows."""
    import re

    path = os.path.join(root, RESILIENCE_DOC)
    try:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
    except OSError:
        return None
    row_re = re.compile(r"^\|\s*`([a-z][a-z0-9_]*\.[a-z][a-z0-9_]*)`\s*\|")
    sites = frozenset(
        m.group(1)
        for line in text.splitlines()
        if (m := row_re.match(line.strip()))
    )
    return sites or None


@rule(
    "import-time-env-read",
    "source",
    "GRAFT_* env read executing at import time in library code — the "
    "value freezes before any launcher/test can set it",
)
def _import_time_env_read(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    out = []
    for r in facts.env_reads():
        if r.func is not None or r.in_main_guard or not _in_library(r.path):
            continue
        out.append(Finding(
            rule="import-time-env-read",
            severity=Severity.WARN,
            loc=f"source:{r.path}:{r.line}",
            message=(
                f"`{r.name}` is read at import time: whoever imports "
                "this module first freezes the value — launchers that "
                "set the knob per generation and tests that monkeypatch "
                "the environment read stale state"
            ),
            evidence="move the read into the function (or property) "
                     "that consumes it",
        ))
    return out


# -- the knob registry --------------------------------------------------------


def _knob_state(ctx, facts):
    """(registry, knobs_md_rows|None, twins) honoring fixture extras."""
    registry = ctx.extras.get("knob_registry")
    if registry is None and facts.root:
        registry = build_registry(facts, root=facts.root)
    if registry is None:
        # snippet facts: build a reader-only registry (no repo files)
        reads: dict = {}
        for r in facts.env_reads():
            reads.setdefault(r.name, []).append(r)
        from .knobs import Knob
        registry = {
            name: Knob(
                name=name, defaults=(),
                readers=tuple(f"{r.path}:{r.line}" for r in rs),
                consumers=(), twin=None, doc=None,
            )
            for name, rs in reads.items()
        }
    if "knobs_md" in ctx.extras:
        rows = ctx.extras["knobs_md"]
    elif facts.root:
        rows = load_knobs_md(facts.root)
    else:
        rows = None  # snippet with no expectation — knob rules skip
    if "config_twins" in ctx.extras:
        twins = ctx.extras["config_twins"]
    elif facts.root:
        twins = config_twins(facts.root)
    else:
        twins = {}
    return registry, rows, twins


@rule(
    "knob-undocumented",
    "source",
    "a GRAFT_* env read with no row in docs/KNOBS.md",
)
def _knob_undocumented(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    registry, rows, _ = _knob_state(ctx, facts)
    if rows is None and not facts.root and "knobs_md" not in ctx.extras:
        return []
    if rows is None:
        return [Finding(
            rule="knob-undocumented",
            severity=Severity.ERROR,
            loc="source:docs/KNOBS.md",
            message="docs/KNOBS.md is missing — the knob registry has "
                    "nothing to drift against",
            evidence="generate it: python -m "
                     "pytorch_distributedtraining_tpu.analyze --source "
                     "--write-knobs",
        )]
    out = []
    for name in sorted(registry):
        k = registry[name]
        if k.readers and name not in rows:
            out.append(Finding(
                rule="knob-undocumented",
                severity=Severity.ERROR,
                loc=f"source:{k.readers[0]}",
                message=(
                    f"`{name}` is read here but has no row in "
                    "docs/KNOBS.md — a knob nobody can discover"
                ),
                evidence="regenerate the table: python -m "
                         "pytorch_distributedtraining_tpu.analyze "
                         "--source --write-knobs",
            ))
    return out


@rule(
    "knob-dead",
    "source",
    "a knob documented in docs/KNOBS.md that nothing reads anymore",
)
def _knob_dead(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    registry, rows, _ = _knob_state(ctx, facts)
    if rows is None:
        return []
    out = []
    for name in sorted(rows):
        k = registry.get(name)
        if k is not None and k.readers:
            continue
        out.append(Finding(
            rule="knob-dead",
            severity=Severity.WARN,
            loc="source:docs/KNOBS.md",
            message=(
                f"`{name}` has a doc row but no source read: either the "
                "consumer was deleted (drop the row) or the knob was "
                "renamed (the old spelling now silently does nothing)"
            ),
            evidence="regenerate docs/KNOBS.md after fixing",
        ))
    return out


@rule(
    "knob-twin-mismatch",
    "source",
    "a TPUConfig env-twin declaration that cannot resolve: unmappable "
    "field or a twin knob nothing reads",
)
def _knob_twin_mismatch(ctx: AnalysisContext):
    facts = _facts(ctx)
    if facts is None:
        return []
    registry, _, twins = _knob_state(ctx, facts)
    if not twins:
        return []
    out = []
    for name in sorted(twins):
        field = twins[name]
        if field is None:
            out.append(Finding(
                rule="knob-twin-mismatch",
                severity=Severity.ERROR,
                loc="source:stoke/config.py",
                message=(
                    f"TPUConfig declares env twin `{name}` but no field "
                    "matches the name — the comment promises a "
                    "precedence that cannot exist"
                ),
                evidence="rename the twin or the field so they pair",
            ))
            continue
        k = registry.get(name)
        if k is None or not k.readers:
            out.append(Finding(
                rule="knob-twin-mismatch",
                severity=Severity.ERROR,
                loc="source:stoke/config.py",
                message=(
                    f"TPUConfig.{field} declares env twin `{name}` but "
                    "nothing reads it — the documented env-wins "
                    "precedence never happens"
                ),
                evidence="read the twin where the field is consumed "
                         "(stoke/facade.py) or drop the declaration",
            ))
    return out


# -- collective lockstep ------------------------------------------------------


@rule(
    "collective-lockstep",
    "source",
    "every rank must issue the identical ordered collective sequence — "
    "per-rank sequences reconstructed from HLO replica groups",
)
def _collective_lockstep(ctx: AnalysisContext):
    # analyze_step threads extras as attributes; source_report as a dict
    programs = (
        getattr(ctx, "lockstep_programs", None)
        or ctx.extras.get("lockstep_programs")
    )
    if programs is None:
        programs = [("step", ctx.hlo_text)] if ctx.hlo_text else []
    if not programs:
        return []
    n_ranks = (
        getattr(ctx, "lockstep_ranks", None)
        or ctx.extras.get("lockstep_ranks")
    )
    if n_ranks is None and ctx.mesh is not None:
        n_ranks = int(getattr(ctx.mesh, "size", 0) or ctx.mesh.devices.size)
    if not n_ranks or n_ranks < 2:
        return []

    from ..observe import hlo as H  # jax-free, but keep analyze import lazy

    out = []
    for label, text in programs:
        seqs = _rank_sequences(H, text, n_ranks)
        shapes: dict = {}
        for r, seq in seqs.items():
            shapes.setdefault(tuple(seq), []).append(r)
        if len(shapes) <= 1:
            continue
        # witness: the largest cohort is "the program"; every other
        # cohort diverges from it at some first position
        major = max(shapes, key=lambda s: len(shapes[s]))
        for seq, ranks in sorted(shapes.items(), key=lambda kv: kv[1]):
            if seq == major:
                continue
            i = _first_divergence(major, seq)
            missing = major[i] if i < len(major) else "<end>"
            got = seq[i] if i < len(seq) else "<end>"
            out.append(Finding(
                rule="collective-lockstep",
                severity=Severity.ERROR,
                loc=f"source:hlo:{label}",
                message=(
                    f"program `{label}` is not in lockstep: rank(s) "
                    f"{_fmt_ranks(ranks)} issue {len(seq)} collectives "
                    f"vs {len(major)} on rank(s) "
                    f"{_fmt_ranks(shapes[major])}; first divergence at "
                    f"op #{i + 1} — expected `{missing}`, rank(s) "
                    f"{_fmt_ranks(ranks)} have `{got}`"
                ),
                evidence=(
                    "a collective whose replica_groups exclude some "
                    "ranks deadlocks every included rank; check for "
                    "rank-conditioned tracing (the "
                    "host-divergent-collective rule finds the Python "
                    "side)"
                ),
            ))
    return out


def _rank_sequences(H, hlo_text: str, n_ranks: int) -> dict:
    """{rank: [op kind, ...]} in program order, from replica groups.

    An op with no ``replica_groups`` attribute (or flattened ``{}``)
    involves every rank. Groups partitioning a *subset* of ranks involve
    exactly their members — which is how a divergent program shows up.
    """
    seqs = {r: [] for r in range(n_ranks)}
    for op in H.collective_inventory(hlo_text):
        groups = H.replica_groups(op.line)
        if not groups or not any(groups):
            ranks = range(n_ranks)
        else:
            ranks = sorted(
                {r for g in groups for r in g if 0 <= r < n_ranks}
            )
        for r in ranks:
            seqs[r].append(op.kind)
    return seqs


def _first_divergence(a: tuple, b: tuple) -> int:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return min(len(a), len(b))


def _fmt_ranks(ranks) -> str:
    rs = sorted(ranks)
    if len(rs) > 6:
        return f"{{{rs[0]}..{rs[-1]} ({len(rs)} ranks)}}"
    return "{" + ",".join(map(str, rs)) + "}"


# -- the whole-repo entry point ----------------------------------------------


def source_report(
    root: str | None = None,
    ignore=None,
    extras: dict | None = None,
    facts: SourceFacts | None = None,
):
    """Run every source-plane rule over the repo; returns a Report.

    This is what ``python -m ...analyze --source`` and the
    ``__graft_entry__`` source phase call. Parse errors in production
    source surface as findings —
    a file the linter cannot read is a file nobody vetted.
    """
    if facts is None:
        facts = collect_facts(root)
    ctx = AnalysisContext(source=facts, extras=dict(extras or {}))
    report = run_rules(ctx, planes=("source",), ignore=ignore)
    for path, msg in facts.parse_errors:
        report.findings.append(Finding(
            rule="source-parse",
            severity=Severity.ERROR,
            loc=f"source:{path}",
            message=f"cannot parse: {msg}",
        ))
    return report
