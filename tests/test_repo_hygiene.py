"""What the repository's text says about its own files stays true.

Two checks over the files git would commit, both stdlib-only:

- the retired second benchmark (``bench`` + ``.py`` at the root, its knob
  file, its capture machine, its environment variables) is named nowhere
  but in the histories (``CHANGES.md``, ``ROADMAP.md``, the findings of
  ``PERF.md``, the ledger, the issue and the review);
- every repository path a document puts in backticks exists.
"""

import functools
import glob
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "pytorch_distributedtraining_tpu"
_TEXT = (".py", ".md", ".json", ".jsonl", ".toml", ".txt", ".cfg", ".sh",
         ".cc", ".cpp", ".h", ".yaml", ".yml", ".ini", "")
_SKIP_DIRS = {".git", ".jax_cache", "chiprun_out", ".scratch", "__pycache__",
              ".pytest_cache", ".hypothesis", "checkpoint"}


@functools.lru_cache(maxsize=None)
def _repo_files():
    """Tracked and not-yet-tracked files that git does not ignore; the
    tree walked with the ignore list's directories skipped where this is
    no git checkout."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "-co", "--exclude-standard"], cwd=REPO,
            capture_output=True, text=True, check=True, timeout=60,
        ).stdout.splitlines()
    except (OSError, subprocess.SubprocessError):
        out = []
        for base, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
            out += [
                os.path.relpath(os.path.join(base, f), REPO) for f in files
            ]
    return sorted(
        f for f in out
        if os.path.isfile(os.path.join(REPO, f))
        and os.path.splitext(f)[1] in _TEXT
    )


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8", errors="replace") as fh:
        return fh.read()


# -- the retired benchmark ---------------------------------------------------

# the histories may say what was there; this file has to spell the names
_HISTORIES = {
    "CHANGES.md", "ROADMAP.md", "PERF_LEDGER.jsonl", "ISSUE.md", "REVIEW.md",
    "tests/test_repo_hygiene.py",
}
# the regression sentry may be handed a trajectory file of that name
_LAST_GOOD_READERS = {
    f"{PKG}/observe/fleet.py", "benchmarks/regress.py",
    "benchmarks/trace_diff.py", f"{PKG}/analyze/runtime_rules.py",
    "tests/test_fleet.py",
}
_RETIRED = re.compile(
    r"(?<!\w)bench\.py(?!\w)|bench_knobs|resilience[./]capture"
)
_BENCH_KNOB = re.compile(r"GRAFT_BENCH_[A-Z0-9_]+")


def _outside_findings(text):
    """PERF.md without its section 6, the findings, which are history."""
    return re.sub(r"(?ms)^## 6\. Findings.*?(?=^## 7\.)", "", text)


def test_nothing_names_the_retired_benchmark():
    from pytorch_distributedtraining_tpu.analyze.knobs import build_registry

    read_somewhere = set(build_registry(root=REPO))
    problems = []
    for rel in _repo_files():
        if rel in _HISTORIES:
            continue
        text = _read(rel)
        if rel == "PERF.md":
            text = _outside_findings(text)
        for m in _RETIRED.finditer(text):
            problems.append(f"{rel}: names {m.group(0)!r}")
        for name in sorted(set(_BENCH_KNOB.findall(text)) - read_somewhere):
            problems.append(f"{rel}: {name} is read by no remaining file")
        if "BENCH_LAST_GOOD" in text and rel not in _LAST_GOOD_READERS:
            problems.append(f"{rel}: names BENCH_LAST_GOOD")
    assert not problems, "\n".join(problems)


# -- documents name files that exist -----------------------------------------

DOCS = ["README.md", "benchmarks/README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(
    r"(?<![\w./<$*{~-])((?:[\w.-]+/)*[\w.-]+\.(?:py|md|json|jsonl|toml|sh|cc|cpp))"
    r"(?![\w/*>}])"
)
_MODULE = re.compile(r"python3? (?:-\w+ )*-m ([A-Za-z_][\w.]*)")
# places a document may mean by a relative path, besides its own directory
_ROOTS = ("", PKG, "tests", "docs", "benchmarks", "chipbench")
# the reference repository's two scripts, which the documents cite by line
_REFERENCE_SCRIPTS = {"Stoke-DDP.py", "Fairscale-DDP.py"}


@functools.lru_cache(maxsize=None)
def _names_written_by_code():
    """File names that appear in a string of the repository's code or
    configuration: what a run writes or is handed (``calibration.json``,
    ``metrics.jsonl``), not a file of the tree."""
    names = set()
    for rel in _repo_files():
        if rel.endswith((".py", ".toml", ".gitignore")):
            names.update(
                re.findall(r"[\w.-]+\.(?:json|jsonl|md|toml|sh)\b", _read(rel))
            )
    return names


def _exists(cand, doc_dir, basenames):
    for root in (doc_dir, *_ROOTS):
        if os.path.exists(os.path.join(REPO, root, cand)):
            return True
    if "/" not in cand:
        return cand in basenames
    # `stoke/facade.py`-style paths inside a sub-package of the package
    return bool(glob.glob(os.path.join(REPO, PKG, "*", cand)))


def _module_exists(mod):
    head = mod.split(".")[0]
    if not (os.path.isdir(os.path.join(REPO, head))
            or os.path.isfile(os.path.join(REPO, head + ".py"))):
        return True  # not this repository's: pytest, http.server, ...
    path = os.path.join(REPO, *mod.split("."))
    return os.path.isfile(path + ".py") or os.path.isdir(path)


@pytest.mark.parametrize("doc", DOCS)
def test_documents_name_files_that_exist(doc):
    files = _repo_files()
    basenames = {os.path.basename(f) for f in files}
    runtime_names = _names_written_by_code()
    missing = []
    for span in _SPAN.findall(_read(doc)):
        for mod in _MODULE.findall(span):
            if not _module_exists(mod):
                missing.append(f"python -m {mod}")
        for cand in _PATH.findall(span):
            if cand in _REFERENCE_SCRIPTS:
                continue
            if "/" not in cand and cand in runtime_names - basenames:
                continue
            if not _exists(cand, os.path.dirname(doc), basenames):
                missing.append(cand)
    assert not missing, f"{doc} names files that do not exist: {sorted(set(missing))}"
