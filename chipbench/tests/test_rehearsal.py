"""Each job end to end on the CPU at a tiny configuration, through the
harness's own command line (``--rehearse``): the result is labelled ``cpu``
and carries no metric. The rehearsal directory is a benchmark in small whose
configuration, cells, one job and one per-layer metric were added as files
plus manifest entries, without a change to ``chipbench/``'s own files."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal")


def run_cell(cell, *, trace, devices=1, rehearse=True, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [
        sys.executable, "-m", "chipbench.run", "--workload", cell,
        "--seed", "3", "--seconds", str(seconds), "--trace", str(trace),
    ]
    if rehearse:
        cmd += ["--rehearse", REHEARSAL]
    return subprocess.run(
        cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )


@pytest.mark.parametrize("cell,trace,devices,expected", [
    ("tiny-gpt2.train", 1, 1,
     {"cache_misses", "compile_s", "input_wait_ms_per_batch.tokens",
      "rehearsal_mark", "window_stall_pct.tokens"}),
    ("tiny-gpt2.zero3-4dev", 0, 4, {"setup_s", "tokens_per_s"}),
    ("tiny-swinir.stoke-loop", 1, 1,
     {"cache_misses", "compile_s", "facade_host_ms_per_batch",
      "facade_programs_per_batch", "input_wait_ms_per_batch.images",
      "window_stall_pct.images"}),
    ("tiny-swinir.fused-step", 0, 1, {"setup_s", "images_per_s"}),
])
def test_job_end_to_end_on_cpu(cell, trace, devices, expected):
    done = run_cell(cell, trace=trace, devices=devices)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 4
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["metrics"] == {}  # a rehearsal prints no device metric
    # a traced rehearsal has no device plane: what reads the trace is absent
    assert set(line["rehearsed_metrics"]) == expected


def test_no_tpu_no_result():
    """Outside a rehearsal, a run that finds no TPU prints no result."""
    done = run_cell("gpt2-125m.train", trace=0, rehearse=False)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert '"metrics"' not in done.stdout
