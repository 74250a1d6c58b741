"""The six readers of the program's start-up ledger, rehearsed on the CPU
(``rehearsal_startup``): each of the three jobs prints the metrics its cell
lists, and the ``{"startup": ...}`` line adds up to the process's start ->
the window's opening, beside the harness's own ``setup_s`` and listener."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import cells, startup_ledger

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_startup")
ALL = {
    "startup_trace_s", "startup_lower_s", "startup_executable_s",
    "startup_state_s", "setup_outside_program_s",
}
CELLS = {
    # cell -> its metrics, the span that builds its state, its loader's
    "tiny-gpt2.train": (ALL | {"startup_input_s"}, "state.create", False),
    "tiny-swinir.stoke-loop": (
        ALL | {"startup_input_s"}, "facade.init_state", True
    ),
    "tiny-swinir.fused-step": (ALL, "facade.init_state", False),
}


@pytest.fixture(scope="module", params=sorted(CELLS))
def run(request):
    """``(cell, result line, builder line, startup report)`` of a traced
    rehearsal."""
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", request.param,
         "--seed", "3", "--seconds", "2", "--trace", "1",
         "--rehearse", REHEARSAL],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    out = done.stdout.strip().splitlines()
    reports = [
        json.loads(line)["startup"] for line in done.stderr.splitlines()
        if line.startswith('{"startup"')
    ]
    assert len(reports) == 1  # made once, for six readers
    return request.param, json.loads(out[-1]), json.loads(out[-2]), reports[0]


def test_cell_reports_the_metrics_its_entry_lists(run):
    cell, line, _, _ = run
    assert line["correct"] is True
    assert set(line["rehearsed_metrics"]) == CELLS[cell][0]


def test_startup_line_adds_up_to_the_window_opening(run):
    _, _, builder, report = run
    total = sum(p["seconds"] for p in report["phases"]) + sum(
        g["seconds"] for g in report["gaps"]
    )
    assert total == pytest.approx(report["seconds"], rel=1e-9)
    assert sum(report["split"].values()) == pytest.approx(
        report["seconds"], rel=1e-9
    )
    # the process's start -> the opening holds setup_s (main() -> the end of
    # set-up) and the interpreter's start before it
    assert 0.0 <= report["seconds"] - builder["setup_s"] < 2.0
    assert report["gaps"][0]["after"] == "process start"
    assert report["gaps"][0]["before"] == "runtime.initialize"
    assert report["dropped"] == 0 and report["after_end_count"] == 0


def test_unions_lie_inside_the_harness_listener_sums(run):
    _, _, builder, report = run
    meter = builder["setup_compile"]
    executable = report["cache_read_s"] + report["xla_s"]
    assert executable == pytest.approx(meter["compile_s"], rel=0.1, abs=0.05)
    assert report["trace_s"] + report["lower_s"] <= meter["trace_lower_s"]
    assert report["trace_s"] > 0 and report["lower_s"] > 0


def test_the_cell_s_phases_are_in_the_report(run):
    cell, _, _, report = run
    _, state_span, has_loader = CELLS[cell]
    names = set(report["by_name"])
    assert {"runtime.initialize", "mesh.make", "state.create", state_span} <= names
    assert ("loader.start_workers" in names) == has_loader
    assert ("prefetch.start" in names) == ("startup_input_s" in CELLS[cell][0])
    cold = [n for n in names if n.endswith("compile+dispatch")]
    assert cold, names


def test_readers_give_nothing_for_a_program_without_the_ledger(monkeypatch):
    from pytorch_distributedtraining_tpu.observe import trace

    monkeypatch.delattr(trace, "startup_report")
    monkeypatch.setattr(startup_ledger, "_CACHE", {})
    ctx = types.SimpleNamespace(window=types.SimpleNamespace(marks=[1.0]))
    for name in sorted(ALL | {"startup_input_s"}):
        reader = cells.load_module("layer_metrics", name, (cells.HERE,))
        assert reader.read(ctx) is None, name
