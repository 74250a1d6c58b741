"""GRAFT_* knob registry: every env read, its default, twin, and doc row.

~100 ``GRAFT_*`` env knobs accreted across the repo with only convention
keeping them documented and twinned to :class:`TPUConfig` fields. This
module makes the convention checkable: :func:`build_registry` folds the
source plane's :class:`~.astlint.EnvRead` facts into one
:class:`Knob` per name — where it is read, with what literal default,
which ``TPUConfig`` field twins it, and which doc mentions it — and
``docs/KNOBS.md`` is *generated* from that registry
(:func:`render_knobs_md`), so the table cannot drift silently: the
``knob-undocumented`` / ``knob-twin-mismatch`` / ``knob-dead`` rules in
:mod:`.source_rules` and the drift test in ``tests/test_source_rules.py``
both compare live facts against the committed table.

Stdlib-only (ast/os/re), same contract as :mod:`.astlint`.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

from .astlint import SourceFacts, collect_facts, repo_root

KNOBS_DOC = "docs/KNOBS.md"

_KNOB_RE = re.compile(r"\bGRAFT_[A-Z0-9_]+\b")
_ROW_RE = re.compile(r"^\|\s*`(GRAFT_[A-Z0-9_]+)`\s*\|")
_FIELD_RE = re.compile(r"^\s{4}(\w+)\s*:")

# knob *names* appear as string literals in places that are not reads:
# the registry itself, doc renderers, and test assertions. Only EnvRead
# facts (actual os.environ traffic) register a knob — these patterns
# never add noise, so no denylist is needed.


@dataclass(frozen=True)
class Knob:
    """One ``GRAFT_*`` env knob, aggregated across every read site."""

    name: str
    defaults: tuple      # distinct literal defaults, repr-sorted
    readers: tuple       # "path:line", sorted
    consumers: tuple     # top-level components reading it, sorted
    twin: str | None     # TPUConfig field name, when declared
    doc: str | None      # first docs/*.md (basename) mentioning the knob

    @property
    def default_cell(self) -> str:
        if not self.defaults:
            return "—"
        return ", ".join(f"`{d!r}`" for d in self.defaults)


def _consumer(path: str) -> str:
    """drivers/x.py -> drivers; pytorch_distributedtraining_tpu/stoke/... -> stoke."""
    parts = path.split("/")
    if len(parts) == 1:
        name = parts[0]
        return name[:-3] if name.endswith(".py") else name
    if parts[0] == "pytorch_distributedtraining_tpu":
        sub = parts[1]
        return sub[:-3] if sub.endswith(".py") else sub
    return parts[0]


def config_twins(root: str | None = None) -> dict:
    """{knob_name: TPUConfig field | None} declared in stoke/config.py.

    The config convention: a field's comment names its env twin as
    ``$GRAFT_X`` (or bare ``GRAFT_X`` for fallback-style twins like
    ``remat``). Twin → field resolution is by name: ``GRAFT_PP_MICRO``
    → ``pp_micro`` exactly, ``GRAFT_TRACE`` → ``trace_dir`` by unique
    prefix. A declared twin that maps to no field keeps ``None`` — the
    mismatch rule reports it.
    """
    root = root or repo_root()
    path = os.path.join(
        root, "pytorch_distributedtraining_tpu", "stoke", "config.py"
    )
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    tree = ast.parse(src)
    block = None
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "TPUConfig":
            block = src.splitlines()[node.lineno - 1: node.end_lineno]
            break
    if block is None:
        return {}
    fields = [
        m.group(1) for line in block
        if (m := _FIELD_RE.match(line)) is not None
    ]
    twins: dict = {}
    for line in block:
        for knob in _KNOB_RE.findall(line):
            if knob in twins:
                continue
            cand = knob[len("GRAFT_"):].lower()
            if cand in fields:
                twins[knob] = cand
                continue
            prefixed = [f for f in fields if f.startswith(cand)]
            twins[knob] = prefixed[0] if len(prefixed) == 1 else None
    return twins


def doc_mentions(root: str | None = None) -> dict:
    """{knob_name: first docs/*.md basename that mentions it}.

    KNOBS.md itself is excluded — it mentions everything by construction,
    which would make the "doc link" column a self-reference.
    """
    root = root or repo_root()
    docs_dir = os.path.join(root, "docs")
    out: dict = {}
    if not os.path.isdir(docs_dir):
        return out
    for fn in sorted(os.listdir(docs_dir)):
        if not fn.endswith(".md") or fn == os.path.basename(KNOBS_DOC):
            continue
        try:
            with open(os.path.join(docs_dir, fn), encoding="utf-8") as fh:
                text = fh.read()
        except OSError:
            continue
        for knob in set(_KNOB_RE.findall(text)):
            out.setdefault(knob, fn)
    return out


def build_registry(
    facts: SourceFacts | None = None, root: str | None = None
) -> dict:
    """{knob_name: Knob} for every GRAFT_* read in scanned source."""
    root = root or repo_root()
    if facts is None:
        facts = collect_facts(root)
    twins = config_twins(root)
    docs = doc_mentions(root)

    reads: dict = {}
    for r in facts.env_reads():
        reads.setdefault(r.name, []).append(r)

    registry: dict = {}
    # twins declared in config but never read still get a registry entry
    # (with no readers) so knob-dead can see them
    for name in sorted(set(reads) | set(twins)):
        rs = reads.get(name, [])
        defaults = sorted(
            {r.default for r in rs if r.default is not None},
            key=repr,
        )
        registry[name] = Knob(
            name=name,
            defaults=tuple(defaults),
            readers=tuple(sorted(f"{r.path}:{r.line}" for r in rs)),
            consumers=tuple(sorted({_consumer(r.path) for r in rs})),
            twin=twins.get(name),
            doc=docs.get(name),
        )
    return registry


_HEADER = """\
# GRAFT_* knob registry

Generated from the source plane's knob registry
(`pytorch_distributedtraining_tpu/analyze/knobs.py`) — do not edit the
table by hand. Regenerate with:

```bash
python -m pytorch_distributedtraining_tpu.analyze --source --write-knobs
```

Every `GRAFT_*` environment read in production source gets a row; the
`knob-undocumented` source rule fails the analyzer when a new read lands
without one, and `tests/test_source_rules.py::test_knobs_md_drift` fails
the suite. "twin" is the `TPUConfig` field the knob overrides (env wins
— precedence lives in `stoke/facade.py`); "—" means the knob is
env-only. "consumer" is the top-level component that reads it.

| knob | default | twin | consumer | doc |
|---|---|---|---|---|
"""


def render_knobs_md(registry: dict) -> str:
    lines = [_HEADER.rstrip("\n")]
    for name in sorted(registry):
        k = registry[name]
        twin = f"`TPUConfig.{k.twin}`" if k.twin else "—"
        consumers = ", ".join(k.consumers) if k.consumers else "—"
        doc = f"[{k.doc}]({k.doc})" if k.doc else "—"
        lines.append(
            f"| `{k.name}` | {k.default_cell} | {twin} | {consumers} | {doc} |"
        )
    return "\n".join(lines) + "\n"


def parse_knobs_md(text: str) -> dict:
    """{knob_name: raw row line} from a rendered KNOBS.md."""
    out: dict = {}
    for line in text.splitlines():
        m = _ROW_RE.match(line.strip())
        if m:
            out[m.group(1)] = line.strip()
    return out


def load_knobs_md(root: str | None = None) -> dict | None:
    """Parsed committed KNOBS.md, or None when the file is absent."""
    root = root or repo_root()
    path = os.path.join(root, KNOBS_DOC)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return parse_knobs_md(fh.read())


def write_knobs_md(root: str | None = None) -> str:
    """Regenerate docs/KNOBS.md in place; returns the path written."""
    root = root or repo_root()
    text = render_knobs_md(build_registry(root=root))
    path = os.path.join(root, KNOBS_DOC)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(text)
    return path
