"""graftcheck source plane: seeded-snippet matrix, astlint facts, knob
registry drift, lockstep on real HLO, CLI, and the repo self-check.

Mirrors ``test_analyze.py``: each ``src-*`` fixture plants exactly one
hazard in a *source snippet* (plus rule inputs via extras) and must
produce exactly that finding. The repo self-check is the acceptance
criterion from the PR: ``--source`` exits 0 on the tree it ships in.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pytorch_distributedtraining_tpu.analyze import (
    ENV_IGNORE,
    ENV_MODE,
    Severity,
)
from pytorch_distributedtraining_tpu.analyze import __main__ as cli
from pytorch_distributedtraining_tpu.analyze.astlint import (
    collect_facts,
    collect_snippet,
    repo_root,
)
from pytorch_distributedtraining_tpu.analyze.fixtures import (
    SOURCE_FIXTURES,
    build_source_fixture,
)
from pytorch_distributedtraining_tpu.analyze.knobs import (
    KNOBS_DOC,
    build_registry,
    load_knobs_md,
    parse_knobs_md,
    render_knobs_md,
)
from pytorch_distributedtraining_tpu.analyze.source_rules import (
    STDLIB_ONLY_MODULES,
    source_report,
)

REPO = repo_root()


@pytest.fixture(autouse=True)
def _clean_analyze_env(monkeypatch):
    monkeypatch.delenv(ENV_MODE, raising=False)
    monkeypatch.delenv(ENV_IGNORE, raising=False)


# -- seeded-snippet matrix ----------------------------------------------------

SEEDED = sorted(set(SOURCE_FIXTURES) - {"src-clean"})


@pytest.mark.parametrize("name", SEEDED)
def test_seeded_source_fixture_produces_exactly_its_finding(name):
    facts, extras, expected = build_source_fixture(name)
    report = source_report(facts=facts, extras=extras)
    got = [(f.rule, f.severity) for f in report.findings]
    assert got == [expected], report.render()


def test_src_clean_fixture_has_no_findings():
    facts, extras, expected = build_source_fixture("src-clean")
    assert expected is None
    report = source_report(facts=facts, extras=extras)
    assert not report.findings, report.render()
    assert report.ok and report.exit_code == 0


def test_ignore_moves_source_findings_to_suppressed():
    facts, extras, _ = build_source_fixture("src-host-divergent")
    report = source_report(
        facts=facts, extras=extras, ignore={"host-divergent-collective"}
    )
    assert report.ok and not report.findings
    assert [f.rule for f in report.suppressed] == [
        "host-divergent-collective"
    ]


def test_env_ignore_suppresses_source_rules(monkeypatch):
    monkeypatch.setenv(ENV_IGNORE, "import-time-env-read")
    facts, extras, _ = build_source_fixture("src-import-env")
    report = source_report(facts=facts, extras=extras)
    assert report.ok and [f.rule for f in report.suppressed] == [
        "import-time-env-read"
    ]


def test_lockstep_witness_names_ranks_and_op():
    facts, extras, _ = build_source_fixture("src-lockstep-divergent")
    report = source_report(facts=facts, extras=extras)
    (hit,) = report.by_rule("collective-lockstep")
    # the seeded HLO's second all-reduce covers only ranks {0,2}: the
    # witness must name the divergent cohort, both lengths, and the op
    assert "{1,3}" in hit.message and "{0,2}" in hit.message
    assert "op #2" in hit.message and "all-reduce" in hit.message


# -- astlint fact units: the exemptions that keep the repo clean -------------


def test_pragma_acknowledges_divergent_collective():
    code = (
        "from .runtime.dist import coordination_barrier, rank\n"
        "def publish(state):\n"
        "    if rank() == 0:\n"
        "        coordination_barrier(  # graftcheck: ok(host-divergent-collective)\n"
        "            'gen', timeout_s=5.0)\n"
    )
    facts = collect_snippet(
        code, path="pytorch_distributedtraining_tpu/_px_.py"
    )
    gated = list(facts.gated_calls())
    assert gated and all(g.acknowledged for g in gated)
    report = source_report(facts=facts, extras={})
    assert not report.by_rule("host-divergent-collective"), report.render()


def test_warm_then_time_fence_is_not_a_blocking_sync():
    # sync THEN timer within the fence window: the correct idiom for
    # excluding async dispatch from a measurement — must stay quiet
    code = (
        "import time\n"
        "def timed(step, batches):\n"
        "    for b in batches:\n"
        "        out = step(b)\n"
        "        out.block_until_ready()\n"
        "        t0 = time.perf_counter()\n"
    )
    facts = collect_snippet(
        code, path="pytorch_distributedtraining_tpu/_px_.py"
    )
    report = source_report(facts=facts, extras={})
    assert not report.by_rule("blocking-host-sync"), report.render()


def test_cadence_guarded_sync_is_not_flagged():
    code = (
        "import time\n"
        "def timed(step, batches):\n"
        "    t0 = time.perf_counter()\n"
        "    for i, b in enumerate(batches):\n"
        "        loss = step(b)\n"
        "        if i % 50 == 0:\n"
        "            print(loss.item())\n"
    )
    facts = collect_snippet(
        code, path="pytorch_distributedtraining_tpu/_px_.py"
    )
    report = source_report(facts=facts, extras={})
    assert not report.by_rule("blocking-host-sync"), report.render()


def test_script_scope_skips_hygiene_rules():
    # same import-time env read, but in a benchmark script: the
    # library-scope rules must not police script-style entry points
    code = 'import os\n_D = os.environ.get("GRAFT_X_DEBUG", "0")\n'
    facts = collect_snippet(code, path="benchmarks/_px_bench.py")
    report = source_report(facts=facts, extras={})
    assert not report.by_rule("import-time-env-read"), report.render()


def test_rules_for_counts_as_fault_site_consumption():
    # monitor-driven sites (launch.worker) consume via plan.rules_for(),
    # not fault_point() — both must register, or drift false-positives
    code = (
        "def monitor(plan):\n"
        "    return plan.rules_for('launch.worker')\n"
    )
    facts = collect_snippet(
        code, path="pytorch_distributedtraining_tpu/_px_.py"
    )
    assert [s.site for s in facts.fault_sites()] == ["launch.worker"]


# -- knob registry + docs/KNOBS.md drift -------------------------------------


def test_knobs_md_drift():
    """The committed table must byte-match a fresh render.

    This is the net that catches a new ``GRAFT_*`` read landing without
    regenerating the doc: run
    ``python -m pytorch_distributedtraining_tpu.analyze --source
    --write-knobs`` to fix a failure here.
    """
    rendered = render_knobs_md(build_registry())
    path = os.path.join(REPO, KNOBS_DOC)
    with open(path, encoding="utf-8") as fh:
        committed = fh.read()
    assert committed == rendered, (
        f"{KNOBS_DOC} is stale — regenerate with --source --write-knobs"
    )


def test_knob_registry_covers_every_graft_read():
    facts = collect_facts(REPO)
    registry = build_registry(facts=facts)
    rows = load_knobs_md(REPO)
    assert rows is not None
    read_names = {r.name for r in facts.env_reads()}
    # 100% coverage both ways: every read has a row, every row is backed
    # by a read or a declared TPUConfig twin
    assert read_names <= set(rows)
    assert set(registry) == set(rows)


def test_render_parse_roundtrip():
    registry = build_registry()
    rows = parse_knobs_md(render_knobs_md(registry))
    assert set(rows) == set(registry)


# -- lockstep on a real compiled program -------------------------------------


def test_lockstep_passes_on_real_psum_program(devices8):
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.ops.collectives import shard_map

    n = 4
    mesh = jax.sharding.Mesh(devices8[:n], ("dp",))

    @jax.jit
    def step(x):
        return shard_map(
            lambda v: jax.lax.psum(v, "dp"),
            mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("dp"),
            out_specs=jax.sharding.PartitionSpec(),
        )(x)

    hlo = step.lower(jnp.ones((n, 8))).compile().as_text()
    facts = collect_snippet("x = 1\n")
    report = source_report(
        facts=facts,
        extras={"lockstep_programs": [("psum", hlo)], "lockstep_ranks": n},
    )
    assert not report.by_rule("collective-lockstep"), report.render()


# -- the repo self-check (the PR's acceptance criterion) ---------------------


def test_repo_source_plane_is_clean():
    report = source_report(REPO)
    assert report.ok and not report.findings, report.render()
    assert len(report.rules_run) == 9


def test_stdlib_only_contract_names_real_files():
    for path in STDLIB_ONLY_MODULES:
        assert os.path.exists(os.path.join(REPO, path)), path


# -- CLI ---------------------------------------------------------------------


def test_cli_source_exits_zero(capsys):
    assert cli.main(["--source"]) == 0
    out = capsys.readouterr().out
    assert "analyzing repo source (plane: source)" in out
    assert '"stage": "source"' in out  # JSON summary line


def test_cli_src_fixture_implies_source(capsys):
    rc = cli.main(["--fixture", "src-lockstep-divergent"])
    out = capsys.readouterr().out
    assert "analyzing source fixture 'src-lockstep-divergent'" in out
    assert "fixture expectation [error] collective-lockstep: hit" in out
    assert rc == 1


def test_cli_src_clean_fixture_exits_zero(capsys):
    assert cli.main(["--fixture", "src-clean"]) == 0
    assert "clean: no findings" in capsys.readouterr().out


def test_cli_unknown_src_fixture_exits_two(capsys):
    assert cli.main(["--fixture", "src-nonesuch"]) == 2


def test_cli_source_ignore_suppresses(capsys):
    rc = cli.main(
        ["--fixture", "src-import-env", "--ignore", "import-time-env-read"]
    )
    out = capsys.readouterr().out
    assert "suppressed via" in out
    # suppressed finding -> expectation MISSED -> exit 2, same contract
    # as the step-fixture path
    assert "MISSED" in out and rc == 2


def test_cli_list_rules_includes_source_plane(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in (
        "host-divergent-collective",
        "collective-lockstep",
        "knob-undocumented",
    ):
        assert name in out


def test_cli_list_fixtures_includes_src(capsys):
    assert cli.main(["--list-fixtures"]) == 0
    out = capsys.readouterr().out.split()
    assert "src-clean" in out and "src-lockstep-divergent" in out
