"""AST fact extraction for graftcheck's source plane.

The trace/hlo/runtime planes see what jax and XLA see; none of them see
the *host-side Python* that orchestrates membership generations, serve
draining, elastic grow-back, and hierarchical degradation — the layer
where multi-controller SPMD's classic failure lives: rank-conditioned
control flow gating a collective hangs the pod with no error anywhere.

This module is the substrate: it parses every production source file in
the repo (package, drivers, benchmarks, ``__graft_entry__``;
tests and examples are excluded — they seed violations on purpose) and
extracts the facts the rules in :mod:`.source_rules` evaluate:

- module-level imports (for the stdlib-only contract),
- every ``GRAFT_*`` env read, with its default, enclosing function, and
  whether it executes at import time,
- ``fault_point("x.y")`` literal sites,
- rank-conditioned branches (``process_index()`` / ``rank`` / host-id
  tests) and the collective/barrier/generation calls they dominate,
- blocking host syncs (``.block_until_ready()`` / ``.item()`` /
  ``float()`` / ``np.asarray``) inside timed loops, and whether a
  cadence guard covers them.

Stdlib-only by contract itself (``ast`` + ``os``): the ``--source`` CLI
pass must not pay a jax import for a whole-repo lint.

Acknowledged sites: a trailing ``# graftcheck: ok(rule-name)`` comment
on the gate line or the call line records that a human audited the site
(e.g. the launcher's single-publisher generation publish). Facts carry
the pragma; rules skip acknowledged sites — the pragma in the source IS
the audit trail.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

# one canonical spelling, shared with the knob registry and the docs
ENV_PREFIX = "GRAFT_"

_PRAGMA_RE = re.compile(r"#\s*graftcheck:\s*ok\(([a-z0-9_-]+)\)")

# identifiers that mark a branch condition as rank-/host-divergent.
# Exact-match on Name ids and Attribute attrs — "ranking" never matches.
RANK_HINTS = frozenset({
    "rank",
    "node_rank",
    "local_rank",
    "host_id",
    "process_index",
    "process_idx",
    "controller",
    "is_controller",
    "coordinator",
    "is_coordinator",
})

# env knobs whose value IS a rank/host identity — reading one inside a
# branch test divides the fleet exactly like process_index() does
RANK_ENV_HINTS = frozenset({
    "GRAFT_RANK",
    "GRAFT_NODE_RANK",
    "GRAFT_HOST_ID",
    "GRAFT_FLEET_RANK",
    "GRAFT_FLEET_REPLICA_ID",
})

# calls that must be issued by EVERY participating rank or the pod hangs:
# device collectives, host coordination barriers, and the membership
# generation protocol (publish blocks the waiters, wait blocks itself)
COLLECTIVE_CALLS = frozenset({
    "psum",
    "pmean",
    "pmax",
    "pmin",
    "psum_scatter",
    "all_gather",
    "all_to_all",
    "ppermute",
    "pshuffle",
    "coordination_barrier",
    "sync_global_devices",
    "broadcast_one_to_all",
    "process_allgather",
    "wait_generation",
    "publish_generation",
})

# host-sync call shapes: attribute calls always flagged inside a timed
# loop; name calls only when the argument mentions a device-value hint
HOST_SYNC_ATTRS = frozenset({"block_until_ready", "item"})
HOST_SYNC_NAMES = frozenset({"float", "asarray", "array", "device_get"})
DEVICE_VALUE_HINTS = ("loss", "metric", "grad", "logit", "state", "out", "tok")

# timing calls whose presence makes a loop a "timed window"
_TIMER_ATTRS = frozenset({"perf_counter", "monotonic", "perf_counter_ns"})

# guard-condition identifiers that mark a cadence gate ("every N steps")
_CADENCE_HINTS = ("every", "cadence", "interval", "stride", "period")

# modules whose module-level import breaks the stdlib-only contract
NON_STDLIB_IMPORTS = frozenset({"jax", "flax", "optax", "jaxlib"})


@dataclass(frozen=True)
class EnvRead:
    """One ``os.environ``-family read of a ``GRAFT_*`` knob."""

    name: str
    path: str          # repo-relative posix path
    line: int
    func: str | None   # enclosing function qualname; None = import time
    default: object    # literal default when statically visible, else None
    in_main_guard: bool = False  # inside ``if __name__ == "__main__"``


@dataclass(frozen=True)
class GatedCall:
    """A collective-ish call dominated by a rank-conditioned branch."""

    path: str
    gate_line: int
    gate_src: str      # the branch test, unparsed
    call: str          # the gated callable's name
    call_line: int
    func: str | None
    acknowledged: bool  # a graftcheck: ok(...) pragma covers the site


@dataclass(frozen=True)
class HostSync:
    """A blocking host sync inside a timed step/tick loop."""

    path: str
    kind: str          # "block_until_ready" | "item" | "float" | ...
    line: int
    loop_line: int
    guarded: bool      # a cadence guard covers the call
    acknowledged: bool


@dataclass(frozen=True)
class FaultSite:
    path: str
    site: str
    line: int


@dataclass
class ModuleFacts:
    """Everything the source rules need to know about one file."""

    path: str                       # repo-relative posix path
    module: str | None = None       # dotted module name (None for scripts)
    toplevel_imports: list = field(default_factory=list)  # (mod, line)
    env_reads: list = field(default_factory=list)         # [EnvRead]
    fault_sites: list = field(default_factory=list)       # [FaultSite]
    gated_calls: list = field(default_factory=list)       # [GatedCall]
    host_syncs: list = field(default_factory=list)        # [HostSync]
    timer_lines: set = field(default_factory=set)         # perf_counter() linenos
    constants: dict = field(default_factory=dict)         # NAME -> str value
    pragmas: dict = field(default_factory=dict)           # line -> {rule,...}


@dataclass
class SourceFacts:
    """The whole repo's facts, keyed by repo-relative path."""

    root: str
    modules: dict = field(default_factory=dict)  # path -> ModuleFacts
    parse_errors: list = field(default_factory=list)  # (path, message)

    def env_reads(self):
        for m in self.modules.values():
            yield from m.env_reads

    def fault_sites(self):
        for m in self.modules.values():
            yield from m.fault_sites

    def gated_calls(self):
        for m in self.modules.values():
            yield from m.gated_calls

    def host_syncs(self):
        for m in self.modules.values():
            yield from m.host_syncs


def repo_root() -> str:
    """The repo checkout this package lives in."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


# production source only: tests seed violations on purpose, examples are
# user-facing snippets, fixtures embed violating code as string literals
_SCAN_DIRS = ("pytorch_distributedtraining_tpu", "drivers", "benchmarks")
_SCAN_ROOT_FILES = ("__graft_entry__.py",)


def iter_source_files(root: str):
    """Yield repo-relative posix paths of every file the linter scans."""
    for name in _SCAN_ROOT_FILES:
        if os.path.exists(os.path.join(root, name)):
            yield name
    for d in _SCAN_DIRS:
        base = os.path.join(root, d)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(x for x in dirnames if x != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fn), root)
                    yield rel.replace(os.sep, "/")


def _module_name(rel_path: str) -> str | None:
    if not rel_path.startswith("pytorch_distributedtraining_tpu/"):
        return None
    mod = rel_path[: -len(".py")].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _pragmas(src: str) -> dict:
    out: dict = {}
    for i, line in enumerate(src.splitlines(), start=1):
        if "graftcheck" not in line:
            continue
        rules = set(_PRAGMA_RE.findall(line))
        if rules:
            out[i] = rules
    return out


def _names_in(node) -> set:
    """Every Name id and Attribute attr in a subtree (exact identifiers)."""
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


def _call_name(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _str_value(node, constants: dict) -> str | None:
    """A string literal, or a module constant resolving to one."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return constants.get(node.id)
    return None


def _literal_default(node):
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError, TypeError):
        return None


def _env_read_name(call: ast.Call, constants: dict) -> tuple | None:
    """(knob_name, default_node|None) when ``call`` reads an env var.

    Recognized shapes: ``os.environ.get(K[, d])``, ``os.getenv(K[, d])``,
    ``os.environ.setdefault(K, d)``, ``<expr>.get(K[, d])`` where K
    resolves to a ``GRAFT_*`` string (the ``(env or os.environ).get``
    idiom threads a test env dict through the same reader).
    """
    f = call.func
    if not isinstance(f, ast.Attribute) or not call.args:
        return None
    key = _str_value(call.args[0], constants)
    if key is None or not key.startswith(ENV_PREFIX):
        return None
    default = call.args[1] if len(call.args) > 1 else None
    if f.attr in ("get", "setdefault"):
        return key, default
    if f.attr == "getenv":
        return key, default
    return None


def _env_subscript_name(node: ast.Subscript, constants: dict) -> str | None:
    """``os.environ["GRAFT_X"]`` (read or write — both register the knob)."""
    base = node.value
    if isinstance(base, ast.Attribute) and base.attr == "environ":
        key = _str_value(node.slice, constants)
        if key and key.startswith(ENV_PREFIX):
            return key
    return None


def _is_timer_call(node) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, (ast.Attribute, ast.Name))
        and (
            node.func.attr if isinstance(node.func, ast.Attribute)
            else node.func.id
        ) in _TIMER_ATTRS
    )


def _is_cadence_guard(test) -> bool:
    """A branch test that rate-limits its body: a modulo, or a name that
    reads as a cadence knob (``every``, ``interval``, ...)."""
    for n in ast.walk(test):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod):
            return True
    for ident in _names_in(test):
        low = ident.lower()
        if any(h in low for h in _CADENCE_HINTS):
            return True
    return False


def _is_main_guard(test) -> bool:
    return (
        isinstance(test, ast.Compare)
        and isinstance(test.left, ast.Name)
        and test.left.id == "__name__"
    )


class _Collector(ast.NodeVisitor):
    def __init__(self, facts: ModuleFacts):
        self.f = facts
        self._func_stack: list = []    # qualname parts
        self._class_stack: list = []
        self._gate_stack: list = []    # (gate_line, gate_src) rank gates
        self._timed_loops: list = []   # loop lineno stack (timed only)
        self._guard_depth = 0          # cadence guards currently open
        self._main_guard_depth = 0

    # -- helpers -----------------------------------------------------------

    def _qualname(self) -> str | None:
        if not self._func_stack:
            return None
        return ".".join(self._func_stack)

    def _ack(self, *lines: int, rule_hint: str | None = None) -> bool:
        for ln in lines:
            rules = self.f.pragmas.get(ln)
            if rules and (rule_hint is None or rule_hint in rules):
                return True
        return False

    def _rank_conditioned(self, test) -> bool:
        idents = _names_in(test)
        if idents & RANK_HINTS:
            return True
        for n in ast.walk(test):
            if isinstance(n, ast.Call):
                got = _env_read_name(n, self.f.constants)
                if got and got[0] in RANK_ENV_HINTS:
                    return True
            elif isinstance(n, ast.Subscript):
                key = _env_subscript_name(n, self.f.constants)
                if key in RANK_ENV_HINTS:
                    return True
        return False

    # -- structure ---------------------------------------------------------

    def visit_ClassDef(self, node):
        self._class_stack.append(node.name)
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()
        self._class_stack.pop()

    def _visit_func(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Import(self, node):
        if not self._func_stack and not self._main_guard_depth:
            for a in node.names:
                self.f.toplevel_imports.append((a.name, node.lineno))
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if (
            not self._func_stack
            and not self._main_guard_depth
            and node.module
            and node.level == 0
        ):
            self.f.toplevel_imports.append((node.module, node.lineno))
        self.generic_visit(node)

    def visit_Assign(self, node):
        # module-level NAME = "literal" — resolves ENV_VAR-style indirection
        if not self._func_stack:
            if (
                len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                self.f.constants[node.targets[0].id] = node.value.value
        self.generic_visit(node)

    def visit_If(self, node):
        is_main = _is_main_guard(node.test)
        is_rank = self._rank_conditioned(node.test)
        is_cadence = _is_cadence_guard(node.test)
        if is_main:
            self._main_guard_depth += 1
        if is_rank:
            try:
                gate_src = ast.unparse(node.test)
            except Exception:  # pragma: no cover — unparse is total on 3.9+
                gate_src = "<unparseable>"
            self._gate_stack.append((node.lineno, gate_src))
        if is_cadence:
            self._guard_depth += 1
        self.generic_visit(node)
        if is_cadence:
            self._guard_depth -= 1
        if is_rank:
            self._gate_stack.pop()
        if is_main:
            self._main_guard_depth -= 1

    def _visit_loop(self, node):
        timed = any(_is_timer_call(n) for n in ast.walk(node))
        if timed:
            self._timed_loops.append(node.lineno)
        self.generic_visit(node)
        if timed:
            self._timed_loops.pop()

    visit_For = _visit_loop
    visit_While = _visit_loop
    visit_AsyncFor = _visit_loop

    # -- the call sink -----------------------------------------------------

    def visit_Subscript(self, node):
        key = _env_subscript_name(node, self.f.constants)
        if key:
            self.f.env_reads.append(EnvRead(
                name=key, path=self.f.path, line=node.lineno,
                func=self._qualname(), default=None,
                in_main_guard=self._main_guard_depth > 0,
            ))
        self.generic_visit(node)

    def visit_Call(self, node):
        name = _call_name(node)

        if _is_timer_call(node):
            self.f.timer_lines.add(node.lineno)

        got = _env_read_name(node, self.f.constants)
        if got is not None:
            key, default_node = got
            self.f.env_reads.append(EnvRead(
                name=key, path=self.f.path, line=node.lineno,
                func=self._qualname(),
                default=(
                    _literal_default(default_node)
                    if default_node is not None else None
                ),
                in_main_guard=self._main_guard_depth > 0,
            ))

        # fault_point("x.y") trips a site inline; rules_for("x.y") is the
        # monitor-driven form (the launcher polls the plan and plays the
        # fault itself) — both consume a registered site
        if name in ("fault_point", "rules_for") and node.args:
            site = _str_value(node.args[0], self.f.constants)
            if site is not None:
                self.f.fault_sites.append(
                    FaultSite(self.f.path, site, node.lineno)
                )

        if name in COLLECTIVE_CALLS and self._gate_stack:
            gate_line, gate_src = self._gate_stack[-1]
            self.f.gated_calls.append(GatedCall(
                path=self.f.path, gate_line=gate_line, gate_src=gate_src,
                call=name, call_line=node.lineno, func=self._qualname(),
                acknowledged=self._ack(gate_line, node.lineno),
            ))

        if self._timed_loops:
            sync_kind = None
            if isinstance(node.func, ast.Attribute) and (
                node.func.attr in HOST_SYNC_ATTRS
            ):
                sync_kind = node.func.attr
            elif name in HOST_SYNC_NAMES and node.args:
                try:
                    arg_src = ast.unparse(node.args[0]).lower()
                except Exception:  # pragma: no cover
                    arg_src = ""
                if any(h in arg_src for h in DEVICE_VALUE_HINTS):
                    sync_kind = name
            if sync_kind is not None:
                self.f.host_syncs.append(HostSync(
                    path=self.f.path, kind=sync_kind, line=node.lineno,
                    loop_line=self._timed_loops[-1],
                    guarded=self._guard_depth > 0,
                    acknowledged=self._ack(node.lineno),
                ))

        self.generic_visit(node)


def collect_file(root: str, rel_path: str) -> ModuleFacts | None:
    """Facts for one file; None when the file cannot be parsed (the
    caller records a parse error — a syntax error in production source
    is its own finding, not a crash)."""
    full = os.path.join(root, rel_path)
    with open(full, encoding="utf-8") as fh:
        src = fh.read()
    tree = ast.parse(src, filename=rel_path)
    facts = ModuleFacts(path=rel_path, module=_module_name(rel_path))
    facts.pragmas = _pragmas(src)
    _Collector(facts).visit(tree)
    return facts


def collect_facts(root: str | None = None, files=None) -> SourceFacts:
    """Parse the repo (or an explicit file list) into :class:`SourceFacts`."""
    root = root or repo_root()
    facts = SourceFacts(root=root)
    for rel in (files if files is not None else iter_source_files(root)):
        try:
            facts.modules[rel] = collect_file(root, rel)
        except (SyntaxError, OSError) as e:
            facts.parse_errors.append((rel, str(e)))
    return facts


def collect_snippet(code: str, path: str = "<fixture>") -> SourceFacts:
    """Facts for one in-memory snippet — the seeded-fixture entry point."""
    facts = SourceFacts(root="")
    mf = ModuleFacts(path=path, module=None)
    mf.pragmas = _pragmas(code)
    _Collector(mf).visit(ast.parse(code, filename=path))
    facts.modules[path] = mf
    return facts
