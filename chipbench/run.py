"""Run one cell of the benchmark once, in this one process.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the backend, fails (non-zero, no result) unless jax finds a TPU with
the chips the cell asks for, builds weights and data on the device from
``--seed``, warms up exactly the cell's programs, checks the step against
the plain reference, measures for ``--seconds``, and prints as its last line
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window's last steps, from the benchmark's host
spans and from its counters.

``--rehearse DIR`` runs a tiny cell of ``DIR`` (same layout as this
directory, manifest included) on whatever backend jax has, labels the result
with that platform and prints no metric: for the CPU tests of the harness.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

from chipbench import cells, instruments, trace_reduce
from chipbench.peaks import above_physical_bound

CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")
OUT_ROOT = os.path.join(cells.ROOT, "chiprun_out")
DEFAULT_TRACE_SECONDS = 2.0


@dataclasses.dataclass
class Env:
    """What a job is handed."""

    cell: cells.Cell
    family: object
    devices: list
    seed: int
    out_dir: str
    spans: instruments.Spans
    tracer: instruments.Tracer
    counters: dict = dataclasses.field(default_factory=dict)  # job's facts
    calls: dict = dataclasses.field(default_factory=dict)  # window's counts


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader is handed."""

    window: object
    trace: dict | None
    spans: instruments.Spans
    calls: dict
    counters: dict
    setup_compile: dict
    window_compile: dict
    memory_peak_bytes: int
    device_kind: str | None  # None in a rehearsal: no device metric
    chips: int


def start_backend(cell: cells.Cell, rehearse: bool):
    """The program's own start (libtpu's flags armed before the backend
    exists), the compilation cache at a fixed place in the checkout with no
    size cap whatever the machine's environment says (PERF.md, PR 21: the
    chip machine sets a 192 MiB cap that cycles), then the devices."""
    from pytorch_distributedtraining_tpu import runtime

    runtime.initialize()
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise SystemExit(
            f"chipbench needs a TPU, jax found {devices[0].platform}"
        )
    if len(devices) < cell.chips:
        raise SystemExit(
            f"cell {cell.name} needs {cell.chips} chip(s), jax found "
            f"{len(devices)}"
        )
    return devices[: cell.chips], len(devices)


def tenths(losses) -> tuple:
    """Means of the first and of the last tenth."""
    n = max(1, len(losses) // 10)
    return sum(losses[:n]) / n, sum(losses[-n:]) / n


def window_problems(window, window_compile: dict, workload: dict,
                    compared: dict) -> list:
    """What every cell requires of its window, whatever the job. What has a
    limit goes into ``compared`` as ``[number, limit]``; returned are the
    reasons of another kind. Where no reference follows the step's first
    updates (``follow_steps``: chipbench/first_steps.py), the loss has to
    fall over the window: the one thing that sees a step that leaves its
    state as it was."""
    problems = []
    compared["compilations_in_window"] = [window_compile["compiles"], 0]
    compared["steps_failed"] = [window.failed, 0]
    if not window.losses:
        problems.append(f"none of {window.attempted} steps came back")
    elif not window.failed and not workload.get("follow_steps"):
        first, last = tenths(window.losses)
        compared["loss_last_tenth_less_first"] = [last - first, 0.0]
        if last == first:  # at its limit, and still not fallen
            problems.append("the loss is where it was over the window")
    if not window.step_s > 0:
        problems.append(
            f"{len(window.gaps)} intervals between steps: no median pace"
        )
    return problems


def over_limit(compared: dict) -> list:
    """The numbers of ``compared`` that are not within their limits."""
    return [
        f"{name} {value} is over its limit {limit}"
        for name, (value, limit) in compared.items() if not value <= limit
    ]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    opt = parser.parse_args(argv)
    rehearse = opt.rehearse is not None

    cell = cells.load_cell(opt.workload, opt.rehearse)
    out_dir = os.path.join(OUT_ROOT, f"{cell.name}.{opt.seed}")
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    # the program's telemetry defaults to /tmp/graft-runs/<pid>: keep it here
    os.environ.setdefault("GRAFT_RUN_DIR", os.path.join(out_dir, "run"))

    devices, n_found = start_backend(cell, rehearse)
    meter = instruments.CompileMeter()
    traced = bool(opt.trace)
    env = Env(
        cell=cell, seed=opt.seed, devices=devices, out_dir=out_dir,
        family=cells.load_module(
            "families", cell.config["family"], cell.roots
        ),
        spans=instruments.Spans(traced),
        tracer=instruments.Tracer(
            traced, trace_dir,
            cell.workload.get("trace_seconds", DEFAULT_TRACE_SECONDS),
        ),
    )
    job_module = cells.load_module("jobs", cell.workload["job"], cell.roots)
    job = job_module.Job(env)
    try:
        setup = job.setup()
        setup_compile = meter.take()
        env.calls.clear()
        env.spans.seconds.clear()
        setup_s = time.perf_counter() - t_start
        window = job.run(opt.seconds)
        window_compile = meter.take()
        # the peak is the program's: read before a reference takes its room
        memory_peaks = instruments.memory_peaks(devices)
        t_check = time.perf_counter()
        problems = job.check(setup, window)
        check_s = time.perf_counter() - t_check
    finally:
        job.close()

    rate = window.rate  # at the window's median pace: see loop.py
    kind = devices[0].device_kind
    compared = env.counters.setdefault("compared", {})
    problems += window_problems(
        window, window_compile, cell.workload, compared
    )
    problems += over_limit(compared)
    if not rehearse:
        over = above_physical_bound(
            env.counters["flops_per_step"] / window.step_s, kind, cell.chips
        )
        if over:
            problems.append(over)

    reduction = None
    xplane = env.tracer.xplane_path()
    if xplane is not None:
        reduction = trace_reduce.reduce(
            trace_reduce.load(xplane), job_module.STEP_MODULES
        )
    peak_bytes = instruments.memory_peak_bytes(memory_peaks)
    context = ReadContext(
        window=window, trace=reduction, spans=env.spans, calls=env.calls,
        counters=env.counters, setup_compile=setup_compile,
        window_compile=window_compile, memory_peak_bytes=peak_bytes,
        device_kind=None if rehearse else kind, chips=cell.chips,
    )
    values = {"setup_s": setup_s, cell.workload["throughput_metric"]: rate}
    metrics = {}
    if traced:
        for entry in cell.per_layer:
            reader = cells.load_module(
                "layer_metrics", cells.reader_name(entry["name"]), cell.roots
            )
            value = reader.read(context)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in cell.end_to_end:
            metrics[entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"],
            }

    with open(os.path.join(out_dir, "losses.json"), "w") as f:
        json.dump({"cell": cell.name, "seed": opt.seed, "setup": setup,
                   "losses": window.losses}, f)
    # earlier lines: for the builder, not for the driver
    print(json.dumps({
        "cell": cell.name, "seed": opt.seed, "trace": opt.trace,
        "window_s": window.seconds, "steps": window.steps,
        "rate": rate, "rate_wall": window.units / window.seconds,
        "step_s": window.step_s, "stall_s": window.stall_s,
        "gaps": window.gaps, "tenths": window.tenths, "setup_s": setup_s,
        "check_s": check_s,
        "setup": setup, "setup_compile": setup_compile,
        "window_compile": window_compile, "calls": env.calls,
        "spans_s": env.spans.seconds, "problems": problems,
        "memory_peaks": memory_peaks,
        "loss_first": window.losses[0] if window.losses else None,
        "loss_last": window.losses[-1] if window.losses else None,
        "trace_reduction": None if reduction is None else {
            k: v for k, v in reduction.items() if k != "breakdown"
        },
    }), flush=True)

    device = {
        "platform": devices[0].platform, "kind": kind, "count": n_found,
        "memory_peak_bytes": peak_bytes,
    }
    line = {
        "correct": not problems, "attempted": window.attempted,
        "failed": window.failed,
        "metrics": {} if rehearse else metrics, "device": device,
    }
    if rehearse:
        line["rehearsed_metrics"] = sorted(metrics)
    if traced and reduction is not None:
        device["busy_s"] = reduction["busy_mean_s"]
        device["window_s"] = reduction["window_s"]
        line["breakdown"] = reduction["breakdown"]
    # each number compared beside its limit: the end of both streams
    line["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in compared.items()
    }
    for name, entry in line["compared"].items():
        print(f"compared {name}: {entry['value']} limit {entry['limit']}",
              file=sys.stderr)
    for problem in problems:
        print(f"not correct: {problem}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
