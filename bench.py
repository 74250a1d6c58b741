"""Headline benchmark: SwinIR-S training-step throughput on one TPU chip.

Measures the flagship config the reference actually trains
(`/root/reference/Stoke-DDP.py:206-208,159`: SwinIR-S x2, 64x64 LR patches,
batch 18/device) as images/sec through the compiled DDP train step (forward
+ backward + AdamW + grad clip, bf16 compute). The reference publishes no
numbers, so ``vs_baseline`` reports throughput against an
A100-class per-chip estimate: SwinIR-S x2 at 64x64 is ~21 GFLOPs/image
trained; an A100 at ~50% bf16 utilization (~150 TFLOP/s) gives ~7000
img/s, derated to 6000 for data/optimizer overhead. The ratio is the
trackable cross-round number; BASELINE.json's north star asks for >=0.70.

Prints ONE JSON result line: {"metric", "value", "unit", "vs_baseline"},
plus audit fields {"windows", "window_rates", "steps_per_window", "batch"}
so best-of-N records are distinguishable from single-window ones, plus the
overlap/compile provenance fields {"time_to_first_step_s", "feed",
"prefetch_depth", "overlap_fraction", "compile_cache"} — steady-state
images/sec is measured over windows that exclude compile+warmup, whose
cost is reported separately as time_to_first_step_s. The default feed
stages batches onto the mesh ahead of the step via
``DataLoader.device_iter`` (see docs/PERF.md); GRAFT_BENCH_FEED=resident
restores the zero-input-cost device-resident arm.
Progress lines prefixed with ``# `` are streamed (unbuffered) as the run
proceeds so a driver-side kill can never observe an empty output tail.

Failure envelope (the round-2 artifact was rc=124 with an *empty* tail
because the old parent buffered everything): the parent is an explicit
capture state machine — PROBE → CAPTURE → RIDE_OUTAGE → FALLBACK → EMIT
(`resilience/capture.py`) — with a hard self-deadline (default 50 min).
A down pool is wait-then-retry (RIDE_OUTAGE: probe every ~2 min), failure
classification is the shared `resilience/outage.py` classifier (broad
sentinel set; an unknown rc=1 rides as outage-class until the fast-fail
window has consumed two probe intervals), and every child line streams
the moment it appears. Terminal states:

- rc=0 with a fresh measured record (CAPTURE → EMIT), or
- rc=0 with a structured FALLBACK record when the pool stays dark past
  the budget: provenance-flagged (`"provenance": "FALLBACK"`,
  `"measured": false`), carrying the last-good on-chip number, a bounded
  CPU-envelope measurement (pool-independent proof the capture path still
  works), the outage evidence, and the state-machine path — five rounds
  of value-0.0 artifacts end here, or
- rc=1 with an error record for deterministic failures (broken platform,
  ImportError) and driver-side SIGTERM — never silence.

Fault injection: `GRAFT_FAULT_PLAN` (resilience/faults.py) can kill the
probe/bench children at the `bench.probe` / `bench.child` sites with pool
outage signatures, so the whole envelope — ride-out, classification,
fallback — is chaos-testable off-TPU.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

BASELINE_IMG_PER_SEC = 6000.0  # per-chip A100-class estimate; see docstring
BATCH = max(1, int(os.environ.get("GRAFT_BENCH_BATCH", "18")))  # Stoke-DDP.py:159
PATCH = 64  # Stoke-DDP.py:207 img_size
STEPS = max(1, int(os.environ.get("GRAFT_BENCH_STEPS", "200")))
WARMUP = max(1, int(os.environ.get("GRAFT_BENCH_WARMUP", "3")))

METRIC = "swinir_s_x2_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"

# Budget envelope. Four rounds of official captures died to pool outages,
# so the default budget is generous: a down pool is probed every
# PROBE_INTERVAL_S until it answers or until only MEASURE_RESERVE_S (the
# time a probe + compile + timed windows need) remains on the clock.
TOTAL_BUDGET_S = int(os.environ.get("GRAFT_BENCH_TOTAL", "3000"))
PROBE_TIMEOUT_S = int(os.environ.get("GRAFT_BENCH_PROBE", "70"))
PROBE_INTERVAL_S = int(os.environ.get("GRAFT_BENCH_PROBE_INTERVAL", "120"))
MEASURE_RESERVE_S = int(os.environ.get("GRAFT_BENCH_RESERVE", "300"))
ATTEMPTS = int(os.environ.get("GRAFT_BENCH_ATTEMPTS", "2"))
# 0 = no per-attempt cap (each attempt may use the whole remaining clock)
ATTEMPT_TIMEOUT_S = int(os.environ.get("GRAFT_BENCH_TIMEOUT", "0"))
RETRY_BACKOFF_S = int(os.environ.get("GRAFT_BENCH_BACKOFF", "5"))
# runtime.cache imports jax only inside enable_compile_cache, so the
# budget-bounded parent stays jax-free — as is resilience/ (the shared
# outage classifier, fault hooks, and the capture state machine).
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from pytorch_distributedtraining_tpu.runtime.cache import (  # noqa: E402
    cache_dir,
    cache_disabled,
)
from pytorch_distributedtraining_tpu.resilience import (  # noqa: E402
    CaptureMachine,
    CaptureState,
    OutageClass,
    build_fallback_record,
    classify,
    fault_point,
)

# CPU-envelope fallback: when the pool stays dark past the budget, a tiny
# CPU-platform run of the SAME capture path proves the instrument end-to-end
# and ships inside the FALLBACK artifact. Bounded so it can never eat a
# driver timeout; disable with GRAFT_BENCH_FALLBACK_CPU=0.
FALLBACK_CPU = os.environ.get("GRAFT_BENCH_FALLBACK_CPU", "1") != "0"
FALLBACK_CPU_BUDGET_S = float(
    os.environ.get("GRAFT_BENCH_FALLBACK_CPU_BUDGET", "600")
)

_DEADLINE = time.monotonic() + TOTAL_BUDGET_S
# Emit/exit state is only touched from the main thread and its signal
# handlers, which cannot interleave with each other mid-handler — a plain
# flag is correct where a non-reentrant lock could self-deadlock (a handler
# firing while the main thread holds the lock would block forever).
_DONE = False
_CHILD: subprocess.Popen | None = None


def _status(msg: str) -> None:
    """Stream a progress line immediately; the output tail is never empty."""
    sys.stdout.write(f"# {time.strftime('%H:%M:%S')} {msg}\n")
    sys.stdout.flush()


def _killpg(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        proc.kill()


def _kill_child() -> None:
    """Kill the live child's whole process group, if any.

    Without this, a signal-path exit would orphan a bench child that keeps
    holding the TPU claim (start_new_session detaches it from the driver's
    group), poisoning the next run with the very hung-backend failure this
    envelope exists to avoid.
    """
    proc = _CHILD
    if proc is None or proc.poll() is not None:
        return
    _killpg(proc)


_LAST_GOOD_PATH = os.environ.get("GRAFT_BENCH_LAST_GOOD") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_LAST_GOOD.json"
)

# The capture state machine: created at import so signal handlers can
# consult it (only the parent arms handlers; children never touch it).
_MACHINE = CaptureMachine()
# set on FALLBACK entry / deadline expiry: a re-entered fallback (SIGALRM
# during the CPU-envelope child) must emit immediately, not spawn again
_FALLBACK_QUICK = False


def _read_last_good() -> dict | None:
    """The newest rc=0 headline measurement this machine produced
    (self-maintained by _emit_result), or None."""
    try:
        with open(_LAST_GOOD_PATH) as fh:
            return json.load(fh)
    except Exception:
        return None


def _emit_error(reason: str) -> None:
    """Print the structured error record exactly once and exit rc=1.

    Runs from signal handlers too, possibly while the main thread is mid
    sys.stdout.write — so the record goes out via os.write(1, ...), the
    async-signal-safe path that cannot raise the BufferedWriter reentrancy
    error (which would die with an empty stdout tail, the exact round-2
    failure this envelope exists to prevent).
    """
    global _DONE
    if _DONE:
        return
    _DONE = True
    _kill_child()
    record = {
        "metric": METRIC,
        "value": 0.0,
        "unit": UNIT,
        "vs_baseline": 0.0,
        "error": reason[:500],
    }
    # context, not substitution: the newest rc=0 measurement this machine
    # produced. A deterministic failure at measurement time then still
    # records WHAT the code measured when the chip last answered.
    last_good = _read_last_good()
    if last_good is not None:
        record["last_measured"] = last_good
    os.write(1, ("\n" + json.dumps(record) + "\n").encode())
    os._exit(1)


def _cpu_envelope() -> dict | None:
    """Measure the tiny CPU-platform envelope for the FALLBACK artifact.

    Runs the very same child measurement path forced onto the CPU backend
    with a small batch/step count — pool-independent proof that the
    instrument still measures end-to-end, clearly labeled so the CPU
    number can never impersonate the per-chip metric. Bounded by both the
    fallback budget and the remaining clock; returns None when either is
    too tight or the child fails.
    """
    budget = min(FALLBACK_CPU_BUDGET_S, _remaining() - 30)
    if budget < 45:
        _status("fallback: no clock left for a CPU envelope")
        return None
    _status(f"fallback: measuring CPU envelope (budget {budget:.0f}s)")
    rc, out, diag = _run_child(
        {
            "_GRAFT_BENCH_CHILD": "1",
            "GRAFT_BENCH_PLATFORM": "cpu",
            "GRAFT_BENCH_BATCH": os.environ.get(
                "GRAFT_BENCH_FALLBACK_BATCH", "2"
            ),
            "GRAFT_BENCH_STEPS": os.environ.get(
                "GRAFT_BENCH_FALLBACK_STEPS", "4"
            ),
            "GRAFT_BENCH_WARMUP": "1",
            "GRAFT_BENCH_WINDOWS": "1",
        },
        budget,
    )
    line = _extract_json_line(out) if rc == 0 else None
    if line is None:
        cause = "timed out" if rc is None else f"rc={rc}"
        _status(
            f"fallback: CPU envelope failed ({cause}): "
            f"{_informative_tail(diag)[:200]}"
        )
        return None
    rec = json.loads(line)
    rec["platform"] = "cpu"
    rec["note"] = (
        "pool-independent envelope: tiny-batch CPU run proving the capture "
        "path end-to-end; NOT comparable to the per-chip metric"
    )
    return rec


def _emit_fallback(reason: str, outage: dict | None = None) -> None:
    """Print the structured FALLBACK record exactly once and exit rc=0.

    The pool staying dark past the budget is an environment outcome, not
    an instrument failure: the artifact embeds everything the capture DID
    establish — last-good on-chip number, a fresh CPU envelope, the outage
    evidence, the state-machine path — under explicit provenance flags
    (``"provenance": "FALLBACK"``, ``"measured": false``) so it can never
    be mistaken for a fresh measurement. This path ends the five-round
    value-0.0 artifact failure mode.
    """
    global _DONE, _FALLBACK_QUICK
    if _DONE:
        return
    _MACHINE.to(CaptureState.FALLBACK, reason)
    cpu_env = None
    if FALLBACK_CPU and not _FALLBACK_QUICK:
        _FALLBACK_QUICK = True  # a signal re-entry must not spawn again
        cpu_env = _cpu_envelope()
    outage = dict(outage or {})
    _MACHINE.to(CaptureState.EMIT, "fallback artifact")
    record = build_fallback_record(
        metric=METRIC,
        unit=UNIT,
        reason=reason,
        last_good=_read_last_good(),
        cpu_envelope=cpu_env,
        outage=outage,
        capture_path=_MACHINE.path(),
    )
    _DONE = True
    _kill_child()
    os.write(1, ("\n" + json.dumps(record) + "\n").encode())
    os._exit(0)


_ARM_ENVS = (  # envs that change WHICH arm is being measured
    "GRAFT_BENCH_OPT", "GRAFT_BENCH_ATTN",
    "GRAFT_BENCH_NORM", "GRAFT_BENCH_SOFTMAX", "GRAFT_BENCH_LOOP",
    "GRAFT_BENCH_SCAN_K", "GRAFT_BENCH_FEED", "GRAFT_BENCH_PREFETCH",
    "GRAFT_REMAT", "GRAFT_SCAN_LAYERS", "GRAFT_WIRE", "GRAFT_FP8",
    "GRAFT_BENCH_RECOVERY", "GRAFT_BENCH_SERVE",
    "GRAFT_BENCH_SERVE_FLEET", "GRAFT_BENCH_PLAN",
)


def _is_headline_config() -> bool:
    """True when this run measures the shipped configuration (committed
    knobs, stock batch, sustained methodology, real chip) — the only runs
    allowed to refresh the last-good record, so an outage record can never
    cite an ablation arm, a short-window run, or a CPU self-test as the
    headline's number."""
    return (
        os.environ.get("GRAFT_BENCH_KNOBS") != "0"
        and not os.environ.get("GRAFT_BENCH_PLATFORM")
        and BATCH == 18
        and STEPS >= 100
        and not any(os.environ.get(v) for v in _ARM_ENVS)
    )


def _regression_sentry(rec: dict) -> dict | None:
    """Publication-time perf-regression check (observe/fleet.py).

    Best-effort and lazily imported: the sentry compares this record
    against the BENCH_r*/BENCH_LAST_GOOD trajectory with robust
    median/MAD thresholds. Its verdict rides in the record (and gates
    the last-good refresh below); any failure to run it must never
    block publication.
    """
    try:
        from pytorch_distributedtraining_tpu.observe import fleet

        return fleet.regression_verdict(
            rec, fleet.load_trajectory(os.path.dirname(_LAST_GOOD_PATH))
        )
    except Exception:
        return None


def _emit_result(line: str) -> None:
    global _DONE
    if _DONE:
        return
    _DONE = True
    verdict = None
    try:
        rec = json.loads(line)
        verdict = _regression_sentry(rec)
        if verdict is not None:
            if verdict["status"] in ("drift", "regression"):
                # op-level attribution (benchmarks/trace_diff.py): name
                # WHERE the time went — which op class / collectives grew
                # vs the last-good record's opcost table. A regression
                # that blocks the last-good refresh below must carry this
                # block (or an explicit reason it couldn't be built).
                try:
                    sys.path.insert(
                        0,
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks",
                        ),
                    )
                    from trace_diff import attribute_records

                    last_good = _read_last_good()
                    verdict["attribution"] = (
                        attribute_records(last_good, rec)
                        if last_good
                        else {
                            "available": False,
                            "reason": "no last-good record to diff against",
                        }
                    )
                except Exception as e:  # noqa: BLE001 — never block publish
                    verdict["attribution"] = {
                        "available": False,
                        "reason": f"attribution failed: {e}",
                    }
            rec["regression"] = verdict
            line = json.dumps(rec)
            if verdict["status"] in ("drift", "regression"):
                attr = verdict.get("attribution") or {}
                _status(
                    f"regression sentry: {verdict.get('detail', verdict['status'])}"
                    + (
                        f" — {attr['detail']}"
                        if attr.get("available") and attr.get("detail")
                        else ""
                    )
                )
    except Exception:
        pass
    try:  # best-effort: remember the measurement for outage error records
        # a regressed record must NOT become the new last-good baseline —
        # that would ratchet the trajectory down and blind the sentry
        if _is_headline_config() and (
            verdict is None or verdict["status"] != "regression"
        ):
            rec = json.loads(line)
            rec["measured_at"] = time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            )
            rec["config"] = {
                "steps": STEPS,
                "batch": BATCH,
                "windows": int(os.environ.get("GRAFT_BENCH_WINDOWS", "3")),
            }
            with open(_LAST_GOOD_PATH, "w") as fh:
                json.dump(rec, fh)
    except Exception:
        pass
    os.write(1, ("\n" + line + "\n").encode())
    os._exit(0)


def _remaining() -> float:
    return _DEADLINE - time.monotonic()


def _run_child(
    extra_env: dict, timeout_s: float
) -> tuple[int | None, list[str], list[str]]:
    """Run this file as a child, streaming its output live.

    Returns (returncode, stdout_lines, diag_lines). returncode None means
    killed on timeout. stderr is pumped on its own pipe (streamed + kept
    for diagnostic tails) so runtime log chatter on fd 2 can never splice
    into — or be mistaken for — the stdout JSON result line: extraction
    uses stdout_lines only, diag_lines only feed error messages.
    """
    global _CHILD
    env = dict(os.environ)
    env.update(extra_env)
    env.setdefault("PYTHONUNBUFFERED", "1")
    timeout_s = max(5.0, timeout_s)
    # Mask the deadline signals across spawn→_CHILD assignment so a handler
    # firing in that window can't miss the just-created group and orphan a
    # TPU-holding child; pending signals deliver on unblock.
    mask = {signal.SIGTERM, signal.SIGALRM}
    signal.pthread_sigmask(signal.SIG_BLOCK, mask)
    try:
        proc = subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__)],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            start_new_session=True,  # kill the whole group on timeout
        )
        _CHILD = proc
    finally:
        signal.pthread_sigmask(signal.SIG_UNBLOCK, mask)
    out_lines: list[str] = []
    err_lines: list[str] = []

    echoed = [0]

    def _pump(stream, into: list[str], echo_hash_only: bool) -> None:
        for raw in stream:
            line = raw.rstrip("\n")
            into.append(line)
            if line.startswith("#"):
                _status(f"[child] {line.lstrip('# ')}")
            elif not echo_hash_only and line.strip() and echoed[0] < 8:
                echoed[0] += 1
                sys.stderr.write(f"[child-err] {line[:240]}\n")
                sys.stderr.flush()

    readers = [
        threading.Thread(
            target=_pump, args=(proc.stdout, out_lines, True), daemon=True
        ),
        threading.Thread(
            target=_pump, args=(proc.stderr, err_lines, False), daemon=True
        ),
    ]
    for r in readers:
        r.start()
    try:
        proc.wait(timeout=timeout_s)
        timed_out = False
    except subprocess.TimeoutExpired:
        _killpg(proc)
        proc.wait()
        timed_out = True
    for r in readers:
        r.join(timeout=5)
    _CHILD = None
    diag = out_lines + [l for l in err_lines if l.strip()][-5:]
    return (None if timed_out else proc.returncode), out_lines, diag


def _informative_tail(diag: list[str]) -> str:
    """Last diagnostic line that isn't XLA:CPU's same-machine AOT false
    positive (see runtime/cache.py) — that chatter would bury the real
    failure cause in the error record. When nothing else remains, the
    last progress line at least names the phase the child died in."""
    informative = [
        l for l in diag
        if l.strip()
        and "cpu_aot_loader" not in l
        and "machine features" not in l
    ]
    return next(
        (l for l in reversed(informative) if not l.startswith("#")),
        informative[-1] if informative else "no output",
    )


def _recovery_arm() -> None:
    """Recovery arm (GRAFT_BENCH_RECOVERY=1): measure time_to_recover_s.

    jax-free, pool-free: launches the elastic launcher on the recovery
    drill (``runtime/recovery_drill.py``) with a fault plan that (a)
    wedges the step-(K-1) checkpoint write inside the background writer —
    leaving a torn, uncommitted ``.tmp`` step dir — and (b) SIGKILLs the
    trainer at step K (``train.preempt``). The launcher classifies the
    kill as an external termination, shrinks the world to the survivors,
    and the drill resumes from the last COMMITTED checkpoint, resharding
    onto the smaller mesh. ``time_to_recover_s`` is first post-resume
    trained step minus last pre-crash trained step, from the drill's own
    JSONL event clock.
    """
    import tempfile

    workdir = tempfile.mkdtemp(prefix="graft-recovery-")
    out = os.path.join(workdir, "events.jsonl")
    ckpt = os.path.join(workdir, "ckpt")
    crash_step = int(os.environ.get("GRAFT_BENCH_RECOVERY_STEP", "4"))
    grow = os.environ.get("GRAFT_BENCH_RECOVERY_GROW", "") == "1"
    plan = {
        "faults": [
            # tear: bg writer for step K-1 sleeps past the kill, so its
            # .tmp staging dir never commits — the resume must skip it
            {"site": "ckpt.write", "action": "sleep", "arg": 600,
             "rank": 0, "attempt": 0, "match": {"step": crash_step - 1}},
            # preempt: SIGKILL rank 0 at step K's maybe_save
            {"site": "train.preempt", "action": "kill",
             "rank": 0, "attempt": 0, "match": {"step": crash_step}},
        ]
    }
    plan_path = os.path.join(workdir, "fault_plan.json")
    with open(plan_path, "w") as fh:
        json.dump(plan, fh)
    env = dict(os.environ)
    env.update(
        GRAFT_FAULT_PLAN=plan_path,
        GRAFT_DRILL_OUT=out,
        GRAFT_DRILL_CKPT=ckpt,
        GRAFT_DRILL_STEPS=str(crash_step + 2),
        GRAFT_LAUNCH_ESCALATE_S="5",
        GRAFT_RESTART_BACKOFF="0.1",
        JAX_PLATFORMS="cpu",  # the drill never needs the pool
        PYTHONUNBUFFERED="1",
    )
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    if grow:
        # grow-back leg: the shrunken generation dawdles so the launcher's
        # capacity probes can fire, then takes the graceful teardown and
        # the next generation resumes with mode=grow on the larger mesh
        env.setdefault("GRAFT_DRILL_GROW", "1")
        env.setdefault("GRAFT_DRILL_STEP_SLEEP_S", "0.25")
        env["GRAFT_DRILL_STEPS"] = str(crash_step + 12)
        env.setdefault("GRAFT_GROW_PROBES", "2")
        env.setdefault("GRAFT_GROW_PROBE_INTERVAL_S", "0.3")
        env.setdefault("GRAFT_GROW_MIN_INTERVAL_S", "3")
    from pytorch_distributedtraining_tpu.runtime import recovery_drill
    cmd = [
        sys.executable, "-m",
        "pytorch_distributedtraining_tpu.runtime.launch",
        "--nproc_per_node=2", "--max_restarts=2",
        "--elastic", "--min_world=1",
        *(["--grow"] if grow else []),
        recovery_drill.__file__,
    ]
    _status(
        f"recovery arm: tear ckpt@{crash_step - 1}, kill@{crash_step}, "
        f"elastic 2->? ranks" + (", then grow back" if grow else "")
    )
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        _emit_error("recovery arm: elastic launcher hung >900s")
        return
    wall_s = time.monotonic() - t0
    if proc.returncode != 0:
        tail = (proc.stderr or "")[-500:]
        _emit_error(
            f"recovery arm: launcher rc={proc.returncode}: {tail}"
        )
        return
    events = []
    try:
        with open(out) as fh:
            events = [json.loads(l) for l in fh if l.strip()]
    except (OSError, ValueError) as e:
        _emit_error(f"recovery arm: unreadable event stream: {e}")
        return
    skip = next((e for e in events if e["event"] == "skip"), None)
    if skip is not None:
        # capability gap (no local jax world on this image): a structured
        # skip record, rc 0 — never a red bench for a missing backend
        _emit_result(json.dumps({
            "metric": "time_to_grow_s" if grow else "time_to_recover_s",
            "skipped": True,
            "unit": "s",
            "reason": skip.get("reason", ""),
        }))
        return
    steps0 = [e for e in events if e["event"] == "step" and e["attempt"] == 0]
    resume = next((e for e in events if e["event"] == "resume"), None)
    if not steps0 or resume is None:
        _emit_error(
            f"recovery arm: no crash/resume observed in "
            f"{len(events)} events (fault plan never fired?)"
        )
        return
    gen = resume["attempt"]
    first_back = next(
        (e for e in events if e["event"] == "step" and e["attempt"] == gen),
        None,
    )
    done = next((e for e in events if e["event"] == "done"), None)
    if first_back is None or done is None:
        _emit_error("recovery arm: resumed generation produced no steps")
        return
    t_last = max(e["t"] for e in steps0)
    record = {
        "metric": "time_to_recover_s",
        "value": round(first_back["t"] - t_last, 3),
        "unit": "s",
        "recovery_mode": resume.get("mode") or "retry",
        "world_from": steps0[0]["world"],
        "world_to": resume["world"],
        "mesh_from": steps0[0]["fsdp"],
        "mesh_to": resume["fsdp"],
        "crash_step": crash_step,
        "resume_step": resume["step"],
        "torn_dirs_skipped": resume.get("torn_dirs", []),
        "committed_steps": done.get("committed", []),
        "launcher_wall_s": round(wall_s, 3),
    }
    if grow:
        g_resume = next(
            (e for e in events
             if e["event"] == "resume" and e.get("mode") == "grow"),
            None,
        )
        bit = next(
            (e for e in events if e["event"] == "grow_bitwise"), None
        )
        if g_resume is None:
            _emit_error(
                "recovery arm: grow generation never resumed (grow gate "
                "never fired?)"
            )
            return
        g_att = g_resume["attempt"]
        pre_grow = [
            e for e in events
            if e["event"] in ("step", "preempt_exit")
            and 0 < e["attempt"] < g_att
        ]
        first_grown = next(
            (e for e in events
             if e["event"] == "step" and e["attempt"] == g_att),
            None,
        )
        if not pre_grow or first_grown is None:
            _emit_error("recovery arm: grow generation produced no steps")
            return
        record["time_to_grow_s"] = round(
            first_grown["t"] - max(e["t"] for e in pre_grow), 3
        )
        record["grow_world_to"] = g_resume["world"]
        record["grow_mesh_to"] = g_resume["fsdp"]
        record["grow_resume_step"] = g_resume["step"]
        record["grow_bitwise_ok"] = bool(bit and bit.get("ok"))
    _emit_result(json.dumps(record))


def _serve_arm() -> None:
    """Serving arm (GRAFT_BENCH_SERVE=1): the latency-SLO record.

    Runs ``benchmarks/serve_bench.py`` in a child: continuous vs static
    batching over the same seeded open-loop trace, p50/p99 latency and
    TTFT, throughput, batch occupancy, the zero-steady-recompile
    assertion, and the in-process graftcheck verdict (which now also
    covers ``serve-slo-burn``). The child's record carries the request-
    lifecycle accounting: per-phase latency breakdowns, the p99 tail
    attribution, ``slo_burn_rate``, and ``telemetry_overhead_fraction``
    (the lifecycle bookkeeping's own measured cost, gated at 1% — the
    child exits 9 over it, surfaced here as an error record). Defaults
    to the pool-free CPU self-test (``GRAFT_BENCH_PLATFORM=cpu``)
    unless the caller pins a platform.
    """
    env = dict(os.environ)
    env.setdefault("GRAFT_BENCH_PLATFORM", "cpu")
    if env["GRAFT_BENCH_PLATFORM"] == "cpu":
        env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONUNBUFFERED"] = "1"
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", "serve_bench.py",
    )
    _status("serve arm: continuous vs static batching SLO bench")
    try:
        proc = subprocess.run(
            [sys.executable, script], env=env, capture_output=True,
            text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(script)),
        )
    except subprocess.TimeoutExpired:
        _emit_error("serve arm: serve_bench.py hung >600s")
        return
    if proc.returncode == 9:
        # the child's telemetry-overhead gate: lifecycle bookkeeping cost
        # more than 1% of the measured arm — the record was withheld
        tail = (proc.stdout or "").strip().splitlines()
        _emit_error(
            "serve arm: telemetry overhead over the 1% gate: "
            + (tail[-1] if tail else "")
        )
        return
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "")[-500:]
        _emit_error(f"serve arm: rc={proc.returncode}: {tail}")
        return
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("metric") == "serve_slo":
                # the harvest schema wants a scalar value alongside the
                # full record: headline = continuous-arm throughput
                rec.setdefault(
                    "value", rec["continuous"]["throughput_tok_s"]
                )
                rec.setdefault("unit", "tokens/sec")
                _emit_result(json.dumps(rec))
                return
    _emit_error("serve arm: no serve_slo record in child output")


def _plan_arm() -> None:
    """Planner A/B arm (GRAFT_BENCH_PLAN=1): does the ranking hold up?

    Runs ``benchmarks/plan_bench.py`` in a child on a small CPU mesh:
    the real planner search (AOT memory + static prune), then a
    stopwatch over every ranked survivor plus the default config. The
    record publishes ``plan_rank_of_measured_best`` and
    ``plan_predicted_vs_measured_ratio`` (headline value — the sentry
    tracks it, so cost-model drift that survives calibration shows up
    as a bench regression), plus the GRAFT_PLAN apply round-trip proof.
    """
    env = dict(os.environ)
    env.setdefault("GRAFT_BENCH_PLATFORM", "cpu")
    if env["GRAFT_BENCH_PLATFORM"] == "cpu":
        env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONUNBUFFERED"] = "1"
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", "plan_bench.py",
    )
    _status("plan arm: planner ranking vs measured A/B")
    try:
        proc = subprocess.run(
            [sys.executable, script], env=env, capture_output=True,
            text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(script)),
        )
    except subprocess.TimeoutExpired:
        _emit_error("plan arm: plan_bench.py hung >600s")
        return
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "")[-500:]
        _emit_error(f"plan arm: rc={proc.returncode}: {tail}")
        return
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("metric") == "plan_ab":
                _emit_result(json.dumps(rec))
                return
    _emit_error("plan arm: no plan_ab record in child output")


def _serve_fleet_arm() -> None:
    """Fleet-failover arm (GRAFT_BENCH_SERVE_FLEET=1): the router's
    never-hang record.

    Runs the serve-failover chaos drill (``runtime/recovery_drill.py``
    with ``GRAFT_DRILL_MODE=serve_failover``): three replica
    subprocesses behind a TCP membership store, an open-loop Poisson
    trace through the fleet router, one SIGKILL mid-decode and one
    graceful drain. The record carries ``time_to_failover_s`` (headline),
    the terminal-state census (migrated / replayed / shed), p99 latency
    during the failover window, and ``router_overhead_fraction`` — the
    router's own bookkeeping cost, priced under the same 1% gate as the
    telemetry plane (over it, the record is withheld as an error).
    """
    import tempfile

    workdir = tempfile.mkdtemp(prefix="graft-serve-fleet-")
    out = os.path.join(workdir, "events.jsonl")
    env = dict(os.environ)
    env.update(
        GRAFT_DRILL_MODE="serve_failover",
        GRAFT_DRILL_OUT=out,
        GRAFT_DRILL_CKPT=os.path.join(workdir, "scratch"),
        JAX_PLATFORMS=env.get("JAX_PLATFORMS", "cpu"),
        PYTHONUNBUFFERED="1",
    )
    _status(
        "serve fleet arm: 3-replica failover drill (SIGKILL + drain)"
    )
    cmd = [
        sys.executable, "-m",
        "pytorch_distributedtraining_tpu.runtime.recovery_drill",
    ]
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        _emit_error("serve fleet arm: failover drill hung >600s")
        return
    wall_s = time.monotonic() - t0
    events = []
    try:
        with open(out) as fh:
            events = [json.loads(l) for l in fh if l.strip()]
    except (OSError, ValueError):
        events = []
    skip = next((e for e in events if e["event"] == "skip"), None)
    if skip is not None:
        _emit_result(json.dumps({
            "metric": "serve_fleet_failover",
            "skipped": True,
            "unit": "s",
            "reason": skip.get("reason", ""),
        }))
        return
    trace = next(
        (e for e in events if e["event"] == "trace_done"), None
    )
    if proc.returncode != 0 or trace is None:
        tail = (proc.stderr or proc.stdout or "")[-500:]
        _emit_error(
            f"serve fleet arm: drill rc={proc.returncode}, "
            f"{len(events)} events: {tail}"
        )
        return
    overhead = trace.get("router_overhead_fraction")
    if overhead is not None and overhead > 0.01:
        # same philosophy as the telemetry gate: a router that costs more
        # than 1% of the serving wall is itself the regression
        _emit_error(
            f"serve fleet arm: router overhead {overhead:.2%} over the "
            "1% gate — record withheld"
        )
        return
    record = {
        "metric": "serve_fleet_failover",
        "value": round(trace.get("time_to_failover_s") or 0.0, 3),
        "unit": "s",
        "time_to_failover_s": round(
            trace.get("time_to_failover_s") or 0.0, 3
        ),
        "requests": trace.get("requests"),
        "outcomes": trace.get("outcomes"),
        "requests_migrated": trace.get("requests_migrated"),
        "requests_replayed": trace.get("requests_replayed"),
        "requests_shed": trace.get("requests_shed"),
        "failovers": trace.get("failovers"),
        "lifecycles_closed": trace.get("lifecycles_closed"),
        "over_deadline": trace.get("over_deadline"),
        "p50_latency_s": round(trace.get("p50_latency_s") or 0.0, 4),
        "p99_latency_s": round(trace.get("p99_latency_s") or 0.0, 4),
        "p99_latency_during_failover_s": round(
            trace.get("p99_latency_during_failover_s") or 0.0, 4
        ),
        "router_overhead_fraction": round(overhead or 0.0, 5),
        "survivor_pages_in_use": trace.get("survivor_pages_in_use"),
        "drill_wall_s": round(trace.get("wall_s") or 0.0, 3),
        "arm_wall_s": round(wall_s, 3),
    }
    _emit_result(json.dumps(record))


def _extract_json_line(lines: list[str]) -> str | None:
    """Last line that parses as the result record, if any."""
    for line in reversed(lines):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "metric" in rec and "value" in rec:
            return line
    return None


def main() -> None:
    if os.environ.get("_GRAFT_BENCH_CHILD") == "1":
        _unblock_inherited_mask()
        _bench()
        return
    if os.environ.get("_GRAFT_BENCH_PROBE") == "1":
        _unblock_inherited_mask()
        _probe()
        return
    if os.environ.get("GRAFT_BENCH_RECOVERY"):
        # the recovery arm is pool-free (CPU drill through the elastic
        # launcher) — no probe loop, no TPU claim, its own 900s bound
        _recovery_arm()
        return
    if os.environ.get("GRAFT_BENCH_SERVE_FLEET"):
        # pool-free like the recovery arm: replica subprocesses on the
        # CPU backend, the router's never-hang contract under chaos
        _serve_fleet_arm()
        return
    if os.environ.get("GRAFT_BENCH_SERVE"):
        # the serving arm defaults to the pool-free CPU self-test; its
        # child owns warmup/steady bookkeeping and the graftcheck verdict
        _serve_arm()
        return
    if os.environ.get("GRAFT_BENCH_PLAN"):
        # pool-free planner A/B: rank on the cost model, verify with a
        # stopwatch on a small CPU mesh
        _plan_arm()
        return

    # Hard guarantees: the alarm fires at the self-deadline; SIGTERM from a
    # driver-side `timeout` is converted into the error record before exit.
    def _on_alarm(*_):
        global _FALLBACK_QUICK
        _FALLBACK_QUICK = True  # no clock left for a CPU-envelope child
        if _MACHINE.state in (CaptureState.RIDE_OUTAGE, CaptureState.FALLBACK):
            # the deadline expired while riding a known pool outage: that
            # is the FALLBACK terminal state, not an instrument error
            _emit_fallback(
                f"self-deadline expired after {TOTAL_BUDGET_S}s riding a "
                f"pool outage"
            )
        _emit_error(
            f"self-deadline expired after {TOTAL_BUDGET_S}s "
            f"(TPU backend slow or hung)"
        )

    signal.signal(signal.SIGALRM, _on_alarm)
    signal.signal(signal.SIGTERM, lambda *_: _emit_error(
        "received SIGTERM (driver timeout) before a result was produced"
    ))
    signal.alarm(max(1, TOTAL_BUDGET_S))

    cap = f"{ATTEMPT_TIMEOUT_S}s" if ATTEMPT_TIMEOUT_S > 0 else "full-clock"
    # children decide the same through enable_compile_cache()
    cache_desc = "off" if cache_disabled() else cache_dir()
    _status(
        f"bench start: budget={TOTAL_BUDGET_S}s probe<={PROBE_TIMEOUT_S}s "
        f"attempts={ATTEMPTS}x{cap} cache={cache_desc}"
    )

    # Phase 1: bounded backend-init probes in a wait-then-retry loop. The
    # shared pool's outage windows (17 min - day+) were the
    # dominant capture failure, so a failed probe sleeps PROBE_INTERVAL_S
    # and retries for as long as the clock still fits a sleep + probe +
    # MEASURE_RESERVE_S of actual measurement. Each individual probe stays
    # bounded at PROBE_TIMEOUT_S so a hung claim loop can't eat the clock.
    wait_t0 = time.monotonic()
    probe_n = 0
    fast_fails = 0
    while True:
        probe_n += 1
        t0 = time.monotonic()
        rc, out, diag = _run_child(
            {"_GRAFT_BENCH_PROBE": "1"},
            min(PROBE_TIMEOUT_S, _remaining() - 10),
        )
        probe_dt = time.monotonic() - t0
        tail = _informative_tail(diag)[:300]
        if rc == 0:
            break
        waited = time.monotonic() - wait_t0
        cause = (
            f"hung >{PROBE_TIMEOUT_S:.0f}s" if rc is None else f"rc={rc}"
        )
        # Shared classifier (resilience/outage.py): OUTAGE failures — a
        # hung probe, UNAVAILABLE/DEADLINE_EXCEEDED/connection text in the
        # tail, the CPU-fallback refusal (rc=3/4), a driver rc=124 — ride
        # the wait loop; they resolve when the window opens. UNKNOWN
        # (bare rc=1, no signature) also rides, but only until the
        # fast-fail window has consumed two probe intervals (ADVICE r5
        # #4: an outage whose text lost its sentinel to a truncated tail
        # must not fast-fail as 'deterministic'). DETERMINISTIC failures
        # (ImportError, a typoed platform) get a couple of retries for
        # flap-transients, then fail fast with their own cause instead of
        # burning the whole budget relabeled "pool unavailable".
        cls = classify(rc, tail)
        outage_class = cls is OutageClass.OUTAGE or (
            cls is OutageClass.UNKNOWN and waited < 2 * PROBE_INTERVAL_S
        )
        fast_fails = 0 if outage_class else fast_fails + 1
        if fast_fails >= 3:
            _emit_error(
                f"TPU backend probe failed deterministically "
                f"({fast_fails}x {cause}, not a pool outage): {tail}"
            )
        if outage_class:
            _MACHINE.to(
                CaptureState.RIDE_OUTAGE,
                f"probe {probe_n} {cause} ({cls.value})",
            )
        sleep_s = max(0.0, PROBE_INTERVAL_S - probe_dt)
        if _remaining() < sleep_s + PROBE_TIMEOUT_S + MEASURE_RESERVE_S:
            # budget exhausted riding the outage: the FALLBACK terminal
            # state — a structured rc=0 artifact, never value-0.0/rc=1
            _emit_fallback(
                f"TPU pool unavailable for {waited:.0f}s across {probe_n} "
                f"probes (last: {cause}); last output: {tail}",
                outage={
                    "probes": probe_n,
                    "waited_s": round(waited),
                    "last_cause": cause,
                    "last_class": cls.value,
                    "last_tail": tail,
                },
            )
        _status(
            f"probe {probe_n} {cause} [{cls.value}]; pool down "
            f"{waited:.0f}s, retrying in {sleep_s:.0f}s "
            f"({_remaining():.0f}s on clock)"
        )
        time.sleep(sleep_s)
    plat = next((l for l in out if l.startswith("platform=")), tail)
    _status(f"probe ok in {probe_dt:.1f}s (probe {probe_n}): {plat}")
    _MACHINE.to(CaptureState.CAPTURE, f"pool answered on probe {probe_n}")

    # Phase 2: the bench itself. Retries exist for fast flaky-init crashes;
    # a *timed-out* attempt consumed the budget (e.g. cold-cache compile),
    # so retrying colder-and-shorter is futile and only buries the
    # informative tail — stop instead. Each attempt gets everything on the
    # clock (minus a reserve to emit the record) rather than a fixed slice,
    # so a cold compile that fits the total budget is never killed early.
    err = "unknown"
    last_cls = OutageClass.UNKNOWN
    for attempt in range(1, ATTEMPTS + 1):
        budget = _remaining() - 10
        if ATTEMPT_TIMEOUT_S > 0:
            budget = min(ATTEMPT_TIMEOUT_S, budget)
        if budget < 30:
            err = f"budget exhausted before attempt {attempt} ({err})"
            break
        _status(f"attempt {attempt}/{ATTEMPTS} (timeout {budget:.0f}s)")
        rc, out, diag = _run_child({"_GRAFT_BENCH_CHILD": "1"}, budget)
        result = _extract_json_line(out)
        if rc == 0 and result is not None:
            _MACHINE.to(CaptureState.EMIT, "measured")
            _emit_result(result)
        tail = _informative_tail(diag)
        last_cls = classify(rc, tail)
        err = (
            f"attempt {attempt} "
            + ("timed out" if rc is None else f"rc={rc}")
            + f" [{last_cls.value}]: {tail[:300]}"
        )
        _status(err)
        if rc is None and budget >= _remaining() - 10:
            break  # timeout ate the whole clock; a colder retry can't win
            # (with an explicit per-attempt cap, clock may remain → retry)
        # A retry must fit backend init (probe-measured) + compile + run.
        if attempt < ATTEMPTS and _remaining() < probe_dt + 90:
            break
        if attempt < ATTEMPTS:
            time.sleep(RETRY_BACKOFF_S)
    if last_cls is OutageClass.OUTAGE:
        # the pool answered the probe, then dropped mid-capture and never
        # came back within the attempt budget: same terminal contract as
        # an all-probes-dark run — an honest FALLBACK artifact
        _emit_fallback(
            f"TPU pool dropped mid-capture: {err}",
            outage={"phase": "capture", "last_cause": err},
        )
    _emit_error(f"TPU bench failed: {err}")


def _unblock_inherited_mask() -> None:
    """Children inherit the parent's spawn-window signal mask (blocked
    SIGTERM/SIGALRM); clear it so an orphaned child — parent SIGKILLed
    before its handlers could run — still dies to a plain kill instead of
    holding the TPU claim until SIGKILL."""
    signal.pthread_sigmask(
        signal.SIG_UNBLOCK, {signal.SIGTERM, signal.SIGALRM}
    )


def _force_platform() -> None:
    """Honor GRAFT_BENCH_PLATFORM (envelope self-tests off-TPU). Package
    import is safe here: the import-hygiene test guarantees it initializes
    no backend."""
    from pytorch_distributedtraining_tpu.runtime.dist import (
        force_platform_from_env,
    )

    force_platform_from_env("GRAFT_BENCH_PLATFORM")


def _probe() -> None:
    """Child: init the backend and list devices, nothing else.

    Gates on the platform actually being a TPU (unless a platform was
    explicitly requested for envelope self-tests): a silent CPU fallback
    must fail the probe, not publish a CPU number as the per-chip metric.
    """
    # chaos hook BEFORE the jax import: a simulated pool outage
    # (GRAFT_FAULT_PLAN site bench.probe) dies here with its configured
    # signature, cheaply enough that the parent's whole ride-out +
    # fallback envelope is testable off-TPU in seconds
    fault_point("bench.probe")
    _force_platform()
    import jax

    devs = jax.devices()
    print(f"platform={devs[0].platform} n={len(devs)} {devs[0].device_kind}")
    if (
        not os.environ.get("GRAFT_BENCH_PLATFORM")
        and devs[0].platform != "tpu"
    ):
        print(f"# probe: refusing non-TPU platform {devs[0].platform}")
        sys.exit(3)


def _pipeline_probe_peak(pp: int, schedule: str, n_micro: int):
    """Compiled peak-memory plan of a small stacked-trunk PipelineStep.

    Probe-sized on purpose (tiny MLP blocks): the number is pipeline
    *provenance* for the bench record — the engine's residency behavior
    under this schedule — not the ESPCN step's footprint. Returns
    ``peak_bytes`` or None when the backend reports no memory analysis.
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.parallel import (
        PipelineStep,
        Policy,
        create_train_state,
        pipeline_state_shardings,
    )
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    v = 2 if schedule == "interleaved" else 1
    d, layers, batch_n = 64, pp * v, 8 * n_micro
    mesh = make_mesh(MeshSpec(pp=pp), devices=jax.devices()[:pp])

    def init_fn(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "h": {
                "w": jax.random.normal(k1, (layers, d, d)) * 0.1,
                "b": jnp.zeros((layers, d)),
            },
            "out": jax.random.normal(k2, (d, 1)) * 0.1,
        }, {}

    tx = optim.adamw(lr=1e-3)
    state, shardings = create_train_state(
        init_fn=init_fn, tx=tx, mesh=mesh, policy=Policy()
    )
    shardings = pipeline_state_shardings(shardings, state, mesh, "h")
    state = jax.device_put(state, shardings)
    step = PipelineStep(
        lambda p, x: jnp.tanh(x @ p["w"] + p["b"]),
        tx,
        mesh,
        Policy(),
        n_micro=n_micro,
        schedule=schedule,
        v=v,
        stages_key="h",
        head_fn=lambda o, y, mb, rng: jnp.mean((y @ o["out"] - mb[1]) ** 2),
        state_shardings=shardings,
        donate=False,
    )
    batch = (
        jnp.zeros((batch_n, d), jnp.float32),
        jnp.zeros((batch_n, 1), jnp.float32),
    )
    mem = step.memory_analysis(state, batch)
    return None if mem is None else mem.peak_bytes


def _bench() -> None:
    fault_point("bench.child")  # chaos hook: die mid-attempt on schedule
    t_child_start = time.perf_counter()  # time-to-first-step clock: backend
    # init + model build + compile + warmup all count (what a user waits)
    _force_platform()
    # arm the latency-hiding/async-collective flags BEFORE the first
    # jax.devices() below creates the backend (GRAFT_OVERLAP=0 opts out;
    # LIBTPU_INIT_ARGS is inert off-TPU, so the CPU envelope is unaffected)
    from pytorch_distributedtraining_tpu.runtime.dist import (
        enable_latency_hiding_scheduler,
    )

    enable_latency_hiding_scheduler()
    import numpy as np
    import jax
    import jax.numpy as jnp

    # Replicate the probe's platform gate: if the pool drops between the
    # probe and this attempt, jax silently falls back to CPU and the tiny
    # CPU throughput would be published as the official per-chip metric
    # with rc=0. Distinct rc=4 so the parent's error record names it.
    if (
        not os.environ.get("GRAFT_BENCH_PLATFORM")
        and jax.devices()[0].platform != "tpu"
    ):
        print(
            f"bench child refusing non-TPU platform "
            f"{jax.devices()[0].platform} (pool dropped after probe?)"
        )
        sys.exit(4)

    print("# child: backend up, building model", flush=True)

    # Persistent compile cache: entry counts before/after the compile
    # distinguish a hit from a miss in the emitted record.
    from pytorch_distributedtraining_tpu.runtime.cache import (
        cache_entry_count,
        enable_compile_cache,
    )

    cache_path = enable_compile_cache()
    cache_entries_before = cache_entry_count(cache_path)

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.losses import mse_loss
    from pytorch_distributedtraining_tpu.models import SwinIR
    from pytorch_distributedtraining_tpu.parallel import (
        DDP,
        TrainStep,
        create_train_state,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    # Ablation-winner knobs. Resolution order: env var > bench_knobs.json
    # (repo root, committed once on-chip A/B data picks a winner) >
    # built-in default. The json file makes the default-flip a data change.
    knobs = {}
    knobs_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_knobs.json"
    )
    # GRAFT_BENCH_KNOBS=0 ignores the file: the A/B chain pins every arm
    # with explicit env so a committed winner can't contaminate the
    # baseline or stack under the single-knob ablation arms
    if (
        os.environ.get("GRAFT_BENCH_KNOBS") != "0"
        and os.path.exists(knobs_path)
    ):
        try:
            with open(knobs_path) as fh:
                knobs = json.load(fh)
        except (json.JSONDecodeError, OSError) as e:
            # fail fast with the named cause: a raw traceback would burn
            # every retry attempt on the same unreadable file
            raise SystemExit(f"bench_knobs.json unreadable: {e}")
        unknown = set(knobs) - {
            "attn", "norm", "softmax", "opt", "loop", "scan_k", "feed",
            "remat", "scan_layers", "pp", "pp_schedule", "pp_micro", "wire",
        }
        if unknown:
            # a typoed key would otherwise silently no-op the default flip
            raise SystemExit(
                f"bench_knobs.json unknown keys {sorted(unknown)}; valid: "
                "attn, norm, softmax, opt, loop, scan_k, feed, remat, "
                "scan_layers, pp, pp_schedule, pp_micro, wire"
            )

    resolved = {}  # effective value + where it came from, for the log line

    def knob(env_name: str, file_key: str, default: str) -> str:
        env = os.environ.get(env_name)
        if env is not None:  # set-but-empty still wins: env is authoritative
            resolved[file_key] = (env, "env")
            return env
        if file_key in knobs:
            resolved[file_key] = (str(knobs[file_key]), "json")
            return str(knobs[file_key])
        resolved[file_key] = (default, "default")
        return default

    # remat policy + scan-over-layers (ISSUE 3). remat applies per Swin
    # layer/pair inside the model (the fine-grained form — Policy.remat
    # would blanket the whole loss fn); scan compiles one W-MSA/SW-MSA
    # pair per RSTB instead of depth layers. Both resolve through the same
    # env > json > default chain and are reported in the result JSON.
    from pytorch_distributedtraining_tpu.parallel.remat import resolve_remat

    remat_raw = knob("GRAFT_REMAT", "remat", "none")
    try:
        remat_impl = resolve_remat(remat_raw)
    except ValueError as e:
        raise SystemExit(f"remat: {e} (from {resolved['remat'][1]})")
    scan_layers_raw = knob("GRAFT_SCAN_LAYERS", "scan_layers", "0")
    scan_layers = scan_layers_raw.strip().lower() in ("1", "true", "on", "yes")
    model = SwinIR(
        dtype=jnp.bfloat16,  # reference config, bf16 MXU path
        attn_impl=knob("GRAFT_BENCH_ATTN", "attn", "auto"),
        norm_dtype=(
            jnp.bfloat16
            if knob("GRAFT_BENCH_NORM", "norm", "f32") == "bf16"
            else jnp.float32
        ),
        softmax_dtype=(
            jnp.bfloat16
            if knob("GRAFT_BENCH_SOFTMAX", "softmax", "f32") == "bf16"
            else jnp.float32
        ),
        remat=remat_impl,
        scan_layers=scan_layers,
    )
    # Stoke-DDP.py:253,164; "fused" = flat FusedAdamW (same numerics, one
    # ravelled vector update — kills the per-leaf op tail the profiler
    # measured at ~2.4 ms/step of the 3.7 ms full step). Resolve before
    # the attribution print so the arm shows up in result logs.
    opt_impl = knob("GRAFT_BENCH_OPT", "opt", "chain")
    if opt_impl not in ("chain", "fused"):
        # mirror the unknown-key guard: a typoed value must not benchmark
        # the chain arm under a non-chain label
        raise SystemExit(f"opt must be 'chain' or 'fused', got {opt_impl!r}")
    # "scan" rolls the timed steps into one on-device lax.scan — separates
    # the chip's step rate from this host's per-call dispatch cost (the
    # 1-core VM can be the bottleneck at ~3 ms/step)
    loop_impl = knob("GRAFT_BENCH_LOOP", "loop", "host")
    if loop_impl not in ("host", "scan"):
        raise SystemExit(f"loop must be 'host' or 'scan', got {loop_impl!r}")
    # "prefetch" feeds the timed loop through DataLoader.device_iter (async
    # sharded staging overlapping the running step — real input-pipeline
    # methodology); "resident" keeps the single device-resident batch of
    # earlier rounds (zero input cost — an upper bound, not a pipeline)
    feed_impl = knob("GRAFT_BENCH_FEED", "feed", "prefetch")
    if feed_impl not in ("prefetch", "resident"):
        raise SystemExit(
            f"feed must be 'prefetch' or 'resident', got {feed_impl!r}"
        )
    # quantized gradient wire (parallel/compressed.py): a non-off value
    # swaps the timed step for CompressedGradStep carrying gradients in
    # the named narrow format (int8 | int8_block | fp8_e4m3 | fp8_e5m2,
    # optional :BLOCK suffix); the record then carries wire_format /
    # wire_bytes and the convergence A/B gate below guards publication
    from pytorch_distributedtraining_tpu.parallel import wire_format

    wire_raw = knob("GRAFT_WIRE", "wire", "")
    try:
        wire_fmt = wire_format(wire_raw)
    except ValueError as e:
        raise SystemExit(f"wire: {e} (from {resolved['wire'][1]})")
    # GRAFT_FP8 is the facade/driver knob for the fp8 matmul path, which
    # the GPT-2/ViT trunks implement; the SwinIR flagship has no fp8
    # tagging, so a leaked value must not benchmark a mislabeled arm
    if os.environ.get("GRAFT_FP8", "").strip().lower() not in (
        "", "off", "none", "0", "false",
    ):
        raise SystemExit(
            "GRAFT_FP8 has no effect on the SwinIR flagship trunk (the "
            "fp8 matmul path covers GPT-2/ViT via precision."
            "fp8_dot_general_cls) — unset it; fp8 arms live in ladder.py "
            "and the facade"
        )
    # The quantized wire is a per-leaf path (block scales follow leaf
    # shape); FusedAdamW ravels grads flat and has no optax .update. When
    # the fused winner merely rode in from bench_knobs.json/default, the
    # wire arm overrides it to the tree chain — attributed below so the
    # knobs line never mislabels the arm. An explicit env contradiction is
    # the operator asking for both at once: refuse, don't pick.
    if wire_fmt is not None and opt_impl == "fused":
        if resolved["opt"][1] == "env":
            raise SystemExit(
                "GRAFT_WIRE and GRAFT_BENCH_OPT=fused contradict: the "
                "quantized wire needs the per-leaf optax chain "
                "(FusedAdamW's flat update has no per-leaf wire) — drop "
                "one of the two"
            )
        opt_impl = "chain"
        resolved["opt"] = ("chain", "wire-override")

    # timing-loop knobs parse HERE, before any compile time is spent —
    # same never-benchmark-a-mislabeled-arm convention as opt
    def int_env(name: str, default: str) -> int:
        raw = os.environ.get(name, default)
        try:
            return int(raw)
        except ValueError:
            raise SystemExit(f"{name} must be an int, got {raw!r}")

    windows = max(1, int_env("GRAFT_BENCH_WINDOWS", "3"))
    prefetch_depth = max(1, int_env("GRAFT_BENCH_PREFETCH", "2"))
    # knob-resolved (env > json > default) so a measured winning k can be
    # committed as data, like the opt/loop winners
    scan_k_str = knob("GRAFT_BENCH_SCAN_K", "scan_k", "0")
    try:
        scan_k_raw = int(scan_k_str)
    except ValueError:
        raise SystemExit(
            f"scan_k must be an int, got {scan_k_str!r} "
            f"(from {resolved['scan_k'][1]})"
        )
    # pipeline knobs (parallel/pipeline.py): pp>1 adds an untimed pipeline
    # probe (schedule bubble math + PipelineStep compiled memory plan) so
    # the record carries pp provenance; the timed ESPCN windows stay
    # single-device (the pipelined A/B lives in benchmarks/pipeline_bench)
    pp_str = knob("GRAFT_PP", "pp", "1")
    pp_schedule_impl = knob("GRAFT_PP_SCHEDULE", "pp_schedule", "1f1b")
    pp_micro_str = knob("GRAFT_PP_MICRO", "pp_micro", "0")
    try:
        pp_impl = int(pp_str)
        pp_micro_impl = int(pp_micro_str)
    except ValueError:
        raise SystemExit(
            f"pp/pp_micro must be ints, got {pp_str!r}/{pp_micro_str!r}"
        )
    if any(src != "default" for _, src in resolved.values()):
        # the EFFECTIVE config (env > json > default), not the raw file —
        # result logs must attribute numbers to what actually ran
        print(
            "# child: knobs "
            + " ".join(f"{k}={v}({s})" for k, (v, s) in resolved.items()),
            flush=True,
        )
    clip_norm = 0.1  # shared with the numerics block's clip_fraction
    if opt_impl == "fused":
        tx = optim.FusedAdamW(lr=5e-4, clip_grad_norm=clip_norm)
    else:
        tx = optim.adamw(lr=5e-4, clip_grad_norm=clip_norm)
    policy = DDP()
    # numerics plane (observe/numerics.py): ON by default in the bench
    # child like telemetry — the probe rides the jitted step as fused aux
    # (no extra dispatch), refs are collected during the windows without
    # a sync, and the host decode runs AFTER timing. Its per-step host
    # cost is priced into the same 1% overhead gate as the spans.
    # Explicit falsy GRAFT_NUMERICS opts out.
    _num_env = os.environ.get("GRAFT_NUMERICS")
    num_probe = None
    if _num_env is None or _num_env.strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        from pytorch_distributedtraining_tpu.observe.numerics import (
            NumericsProbe,
        )

        num_probe = NumericsProbe()

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        out = model.apply({"params": params}, lr_img)
        return mse_loss(out, hr_img), {}

    state, shardings = create_train_state(
        init_fn=lambda rng: (
            model.init(rng, jnp.zeros((1, PATCH, PATCH, 3)))["params"],
            {},
        ),
        tx=tx,
        mesh=mesh,
        policy=policy,
        # params stay f32 master copies; compute casts to bf16 in-model
    )
    if wire_fmt is not None:
        if loop_impl == "scan":
            # MultiStep scans step._step without the residual auto-init
            # the quantized step's __call__ performs
            raise SystemExit(
                "wire arm composes with the host loop only "
                "(GRAFT_BENCH_LOOP=scan measures dispatch cost, not wire)"
            )
        from pytorch_distributedtraining_tpu.parallel import (
            CompressedGradStep,
        )

        step = CompressedGradStep(
            loss_fn, tx, mesh, policy, donate=True, wire=wire_fmt,
            numerics=num_probe,
        )
    else:
        step = TrainStep(
            loss_fn, tx, mesh, policy,
            precision=Precision(),
            state_shardings=shardings,
            extra_metrics=False,
            donate=True,
            numerics=num_probe,
        )
    # bytes-on-wire accounting for the record: analytic per-step gradient
    # collective traffic in the chosen format vs the f32 wire it replaces
    wire_info = (
        step.wire_cost(state.params) if wire_fmt is not None else None
    )
    if wire_info is not None:
        print(f"# child: wire {json.dumps(wire_info)}", flush=True)

    rng = np.random.default_rng(0)
    # a small pool of DISTINCT samples so the prefetch feed stages real,
    # varying batches (a single repeated host array would let the runtime
    # dedupe the transfer); 4 batches' worth keeps host RAM trivial
    n_distinct = 4 * BATCH
    hr_all = rng.random(
        (n_distinct, 2 * PATCH, 2 * PATCH, 3)
    ).astype(np.float32)
    lr_all = hr_all.reshape(
        n_distinct, PATCH, 2, PATCH, 2, 3
    ).mean(axis=(2, 4)).astype(np.float32)
    hr = hr_all[:BATCH]
    lr_img = lr_all[:BATCH]
    # warmup (and the resident arm) run on a device-resident batch
    batch = (
        jax.device_put(lr_img, jax.devices()[0]),
        jax.device_put(hr, jax.devices()[0]),
    )

    class _CycleSR:
        """Index-cycling (lr, hr) sample source for the prefetch feed."""

        def __init__(self, n: int):
            self.n = n

        def __len__(self) -> int:
            return self.n

        def __getitem__(self, i: int):
            j = i % n_distinct
            return lr_all[j], hr_all[j]

    dl = None
    dspec = None
    if feed_impl == "prefetch":
        from pytorch_distributedtraining_tpu.data import DataLoader
        from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

        dspec = batch_spec(mesh)
        dl = DataLoader(
            _CycleSR(STEPS * BATCH),
            batch_size=BATCH,
            shuffle=False,
            drop_last=True,
            num_workers=2,
            mesh=mesh,
            spec=dspec,
        )

    # unified telemetry (observe/trace.py): ON by default in the bench
    # child — the record's mfu/goodput_fraction/time_breakdown fields come
    # from these spans. Explicit falsy GRAFT_TELEMETRY opts out (and the
    # bench-telemetry graftcheck rule then WARNs the number is
    # unattributable). Span cost is guarded below: >1% of the steady-state
    # step refuses to publish (exit 9).
    from pytorch_distributedtraining_tpu.observe import trace as telemetry

    _tel_env = os.environ.get("GRAFT_TELEMETRY")
    if _tel_env is None or _tel_env.strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        telemetry.enable()

    # anomaly-triggered capture (observe/capture.py): armed by default so
    # the bench prices the armed-but-idle poll cost inside the same 1%
    # overhead gate as the spans (an instrument a training loop can't
    # afford to keep armed must not claim it's free here). Fires a
    # bounded jax.profiler capture on straggler / SLO-burn / numerics /
    # regression signals. GRAFT_CAPTURE=0 opts out; any other non-flag
    # value names the capture dir (default: under the run dir).
    capture_prof = None
    _cap_env = os.environ.get("GRAFT_CAPTURE")
    if (_cap_env if _cap_env is not None else "1").strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        from pytorch_distributedtraining_tpu.observe.capture import (
            OnDemandProfiler,
        )

        _cap_dir = None
        if _cap_env and _cap_env.strip().lower() not in ("1", "true", "on",
                                                         "yes"):
            _cap_dir = _cap_env.strip()
        capture_prof = OnDemandProfiler(trace_dir=_cap_dir).arm()

    def _sync(x):
        # the post-dispatch wait IS the device compute tail of a timed
        # window — billed productive (cat "step") alongside the dispatch
        # spans, so the ledger's wall-clock decomposition closes
        with telemetry.span("device.sync", "step"):
            jax.block_until_ready(x)

    print("# child: compiling + warmup", flush=True)
    trace_dir = os.environ.get("GRAFT_BENCH_TRACE")
    with mesh:
        for _ in range(WARMUP):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        # compile + warmup cost, reported separately from the steady-state
        # rate (the timed windows below exclude it by construction)
        time_to_first_step = time.perf_counter() - t_child_start
        print(
            f"# child: time-to-first-step {time_to_first_step:.1f}s",
            flush=True,
        )
        if trace_dir:
            # op-level profile of a few steady-state steps (xplane into
            # trace_dir) for MFU analysis; timed loop runs untraced after
            print(f"# child: tracing 3 steps -> {trace_dir}", flush=True)
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    state, metrics = step(state, batch)
                jax.block_until_ready(metrics["loss"])
        # fixed-shape window starts here: any compile-cache entry that
        # appears between this snapshot and the end of the timed windows
        # is a mid-measurement retrace (graftcheck's recompile-drift rule
        # gates on the pair below)
        cache_entries_warm = cache_entry_count(cache_path)
        print("# child: warmup done, timing", flush=True)
        # goodput-ledger bracket: every timed window (plus, on the scan
        # arm, the scan compile) lands inside [t_meas0, t_meas1]
        t_meas0 = time.perf_counter()
        # Best-of-N sustained windows of STEPS steps each; every window is
        # logged for transparency.
        rates: list[float] = []
        # device refs to each step's fused numerics aux (tiny per-leaf
        # vectors) — an append per step, no host sync; decoded after the
        # windows. The deep-scan arm (k>32) drops metrics by design and
        # records no aux.
        num_aux: list = []
        actual_steps = STEPS  # scan mode may round up to k*ceil(STEPS/k)
        if loop_impl == "scan":
            # k steps per dispatch (default: the whole window in one call).
            # Small k amortizes the per-dispatch host cost by k while
            # keeping the program and the stacked batch size bounded.
            k = max(1, min(scan_k_raw, STEPS)) if scan_k_raw > 0 else STEPS
            # ceil: a window never runs FEWER than STEPS steps, so every
            # K value still measures (at least) the committed sustained
            # methodology; the rate math below uses the true k*n_calls
            n_calls = -(-STEPS // k)
            actual_steps = k * n_calls
            if k * n_calls != STEPS:
                print(
                    f"# child: scan k={k} does not divide STEPS={STEPS}; "
                    f"windows run {k * n_calls} steps",
                    flush=True,
                )
            if k <= 32:
                # the public-API path: a real [k, B, ...] stack, so the
                # scan body reads a distinct batch per step like real
                # training (not a loop-invariant constant XLA could hoist)
                from pytorch_distributedtraining_tpu.parallel import (
                    MultiStep,
                )

                multi_api = MultiStep(step, k=k)
                if dl is not None:
                    # stage the window's k distinct batches through the
                    # device prefetcher, then stack on device — the same
                    # staged-feed path MultiStep.feed uses in training
                    from pytorch_distributedtraining_tpu.data import (
                        stack_windows,
                    )

                    pf = dl.device_iter(mesh, dspec, depth=min(k, 8))
                    stacked = next(stack_windows(pf, k))
                    pf.close()
                else:
                    stacked = jax.tree.map(
                        lambda x: jax.device_put(
                            np.broadcast_to(
                                np.asarray(x)[None], (k,) + x.shape
                            )
                        ),
                        batch,
                    )

                def multi_step(s):
                    s2, m = multi_api(s, stacked)
                    if num_probe is not None and "numerics" in m:
                        num_aux.append(m["numerics"])  # k-stacked
                    return s2, m["loss"]

            else:
                # deep windows (default k=STEPS=200) stay on a closure-
                # constant batch: a materialized 200-deep stack would be
                # ~900 MB of HBM + upload, distorting the dispatch-cost
                # diagnostic this arm exists for — it measures per-call
                # overhead, not input-pipeline fidelity
                from functools import partial

                import jax.lax as lax

                @partial(jax.jit, donate_argnums=0)
                def multi_step(s):
                    def body(s, _):
                        s2, m = step._step(s, batch, jnp.float32(1.0))
                        return s2, m["loss"]

                    return lax.scan(body, s, None, length=k)

            t_c = time.perf_counter()
            state, losses = multi_step(state)  # compile + warmup
            jax.block_until_ready(losses)
            print(
                f"# child: scan(k={k}) compile+first-run "
                f"{time.perf_counter() - t_c:.1f}s",
                flush=True,
            )
            # window 1 vs 2 doubles as the replay split: a slow first
            # replay with fast repeats = per-call constant (program
            # upload / remote dispatch), not per-step cost
            for w in range(windows):
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    with telemetry.span("step.dispatch", "step", k=k):
                        state, losses = multi_step(state)
                    if capture_prof is not None:
                        capture_prof.note_step()
                _sync(losses)
                dt = time.perf_counter() - t0
                rates.append(BATCH * k * n_calls / dt)
                print(
                    f"# child: scan window {w + 1}/{windows}: "
                    f"{rates[-1]:.1f} img/s "
                    f"({n_calls} calls x {k} steps, {dt:.2f}s)",
                    flush=True,
                )
        elif dl is not None:
            # prefetch feed: each window is one loader epoch of STEPS
            # distinct staged batches; the prefetcher's queue-wait tally
            # gives the transfer-vs-compute overlap fraction per window
            overlap_fracs: list = []
            for w in range(windows):
                it = dl.device_iter(mesh, dspec, depth=prefetch_depth)
                t0 = time.perf_counter()
                n_steps = 0
                for b in it:
                    # dispatch is billed productive: async backends return
                    # in µs (the sync span carries the window), but when the
                    # dispatch queue throttles, the wait is real step time
                    with telemetry.span("step.dispatch", "step"):
                        state, metrics = step(state, b)
                    if num_probe is not None and "numerics" in metrics:
                        num_aux.append(metrics["numerics"])
                    if capture_prof is not None:
                        capture_prof.note_step()
                    n_steps += 1
                _sync(metrics["loss"])
                dt = time.perf_counter() - t0
                rates.append(BATCH * n_steps / dt)
                overlap_fracs.append(it.overlap_fraction(dt))
                frac = overlap_fracs[-1]
                print(
                    f"# child: window {w + 1}/{windows}: "
                    f"{rates[-1]:.1f} img/s ({dt:.2f}s, "
                    f"{n_steps} steps, overlap="
                    + (f"{frac:.3f}" if frac is not None else "n/a")
                    + (", degraded" if it.degraded else "")
                    + ")",
                    flush=True,
                )
        else:
            for w in range(windows):
                t0 = time.perf_counter()
                for _ in range(STEPS):
                    state, metrics = step(state, batch)
                    if num_probe is not None and "numerics" in metrics:
                        num_aux.append(metrics["numerics"])
                    if capture_prof is not None:
                        capture_prof.note_step()
                _sync(metrics["loss"])
                dt = time.perf_counter() - t0
                rates.append(BATCH * STEPS / dt)
                print(
                    f"# child: window {w + 1}/{windows}: "
                    f"{rates[-1]:.1f} img/s ({dt:.2f}s)",
                    flush=True,
                )

    t_meas1 = time.perf_counter()
    # untimed verification fetch: the loss chains through every timed
    # step, so a real finite host value proves the windows executed; the
    # roofline guard bounds a residual lie.
    final_loss = float(
        jnp.ravel(losses)[-1] if loop_impl == "scan" else metrics["loss"]
    )
    if not np.isfinite(final_loss):
        print(f"non-finite loss after timing: {final_loss}", flush=True)
        sys.exit(6)

    img_per_sec = max(rates)
    # Roofline guard: SwinIR-S x2 at 64x64 trains at ~21
    # GFLOPs/image (fwd+bwd, observe.goodput.swinir_train_flops); no
    # v5e-class chip exceeds ~1 PFLOP/s effective bf16. A rate above
    # peak/model-FLOPs is an instrument failure
    # (e.g. async dispatch not actually synced), never a measurement —
    # refuse to publish it.
    roofline_img_s = 1000e12 / 21e9
    if img_per_sec > roofline_img_s:
        # no "# " prefix: _informative_tail must pick THIS line (not
        # stderr chatter) as the cause in the parent's error record
        print(
            f"ROOFLINE VIOLATION: {img_per_sec:.0f} img/s exceeds the "
            f"{roofline_img_s:.0f} img/s compute bound "
            f"(1 PFLOP/s / 21 GFLOP per image) — timing loop is broken, "
            f"refusing to publish",
            flush=True,
        )
        sys.exit(5)
    # windows/window_rates make the methodology auditable from the record
    # itself (ADVICE r4 #1): best-of-N is distinguishable from a
    # single-window number, and the spread is the variance envelope.
    # overlap fraction from the BEST window (the one whose rate is
    # published); None on the resident/scan arms, which have no input
    # pipeline during the timed region
    overlap_fraction = None
    if loop_impl == "host" and dl is not None:
        best = rates.index(img_per_sec)
        f = overlap_fracs[best]
        overlap_fraction = None if f is None else round(f, 4)
    # Numerics decode (untimed): walk the aux refs the windows collected,
    # name any non-finite offender, feed the divergence watchdog, and
    # summarize update health. The per-observe host cost measured here is
    # what a training loop would pay each step — it folds into the same
    # 1% telemetry-overhead gate below (priced, not assumed free).
    step_time_best = BATCH / img_per_sec  # best window, per step
    numerics_block = None
    numerics_overhead_fraction = None
    if num_probe is not None and num_aux:
        from pytorch_distributedtraining_tpu.observe import (
            numerics as obs_num,
        )

        num_watchdog = obs_num.watchdog_from_env()
        gnorms: list[float] = []
        nonfinite_steps = 0
        first_verdict = None
        t_n0 = time.perf_counter()
        for i, aux in enumerate(num_aux):
            s = num_probe.observe(aux, step=i, watchdog=num_watchdog)
            gnorms.append(s["grad_norm"])
            nonfinite_steps += bool(s["nonfinite"])
            if first_verdict is None and s.get("verdict"):
                first_verdict = s["verdict"]
        per_observe_s = (time.perf_counter() - t_n0) / len(num_aux)
        observes_per_step = len(num_aux) / max(
            1, len(rates) * actual_steps
        )
        numerics_overhead_fraction = round(
            per_observe_s * observes_per_step
            / max(step_time_best, 1e-9),
            6,
        )
        g = np.asarray(gnorms, dtype=np.float64)
        finite_g = g[np.isfinite(g)]
        numerics_block = {
            "steps_observed": len(num_aux),
            "nonfinite_steps": nonfinite_steps,
            "blame": obs_num.runtime_stats["last_nonfinite"],
            "grad_norm_p50": (
                round(float(np.percentile(finite_g, 50)), 6)
                if finite_g.size else None
            ),
            "grad_norm_p95": (
                round(float(np.percentile(finite_g, 95)), 6)
                if finite_g.size else None
            ),
            "grad_norm_max": (
                round(float(finite_g.max()), 6) if finite_g.size else None
            ),
            # pre-clip norms: the fraction of steps the clip engaged
            "clip_fraction": (
                round(float((finite_g > clip_norm).mean()), 4)
                if finite_g.size else None
            ),
            "watchdog_verdict": (
                {
                    k: first_verdict[k]
                    for k in ("kind", "step", "action", "detail")
                    if k in first_verdict
                }
                if first_verdict else None
            ),
            "per_observe_us": round(per_observe_s * 1e6, 1),
            "overhead_fraction": numerics_overhead_fraction,
        }
        for k in (
            "fp8_amax_saturation", "fp8_underflow_frac",
            "wire_residual_norm", "wire_residual_max",
        ):
            if k in obs_num.rolling_gauges:
                numerics_block[k] = round(
                    float(obs_num.rolling_gauges[k]), 6
                )
        print(
            "# child: numerics " + json.dumps(numerics_block), flush=True
        )
    # Goodput/MFU ledger (untimed): classify the measurement interval's
    # wall clock from the spans recorded during the windows, and report
    # utilization against the analytic per-image train FLOPs (is a slow
    # window compile or input-wait?).
    mfu_val = None
    goodput_fraction = None
    time_breakdown = None
    telemetry_overhead_fraction = None
    fleet_summary = None
    flops_per_step = None  # also feeds the mfu_flops calibration below
    if telemetry.enabled():
        from pytorch_distributedtraining_tpu.observe.goodput import (
            GoodputLedger,
            mfu as _mfu,
            model_train_flops,
        )

        ledger = GoodputLedger.from_records(
            telemetry.records(), t_meas0, t_meas1
        )
        gf = ledger.goodput_fraction()
        goodput_fraction = None if gf is None else round(gf, 4)
        time_breakdown = ledger.time_breakdown()
        dev0 = jax.devices()[0]
        try:
            flops_per_step = model_train_flops(model, BATCH, (PATCH, PATCH))
            m = _mfu(
                flops_per_step,
                step_time_best,
                n_devices=1,  # the timed mesh is a single device
                platform=dev0.platform,
                device_kind=getattr(dev0, "device_kind", ""),
            )
            mfu_val = None if m is None else round(m, 6)
        except Exception as e:  # noqa: BLE001 — accounting, not the metric
            print(f"# child: mfu unavailable: {e}", flush=True)
        # overhead guard: measure raw span cost AFTER the windows (the
        # probe spans fall outside the ledger bracket) and scale by the
        # spans-per-step the windows actually recorded
        n_window_spans = sum(
            1 for r in telemetry.records()
            if not r.get("instant") and t_meas0 <= r["t0"] <= t_meas1
        )
        probe_n = 2000
        t_p = time.perf_counter()
        for _ in range(probe_n):
            with telemetry.span("overhead.probe", "other"):
                pass
        per_span_s = (time.perf_counter() - t_p) / probe_n
        spans_per_step = n_window_spans / max(1, len(rates) * actual_steps)
        # armed-but-idle capture cost: note_step() per step is one poll
        # over the anomaly sources' module dicts — measure it raw and
        # charge it to the same budget (an armed profiler that can't
        # stay under 1% has no business being armed in training loops)
        per_poll_s = 0.0
        if capture_prof is not None:
            t_cp = time.perf_counter()
            for _ in range(probe_n):
                capture_prof.poll()
            per_poll_s = (time.perf_counter() - t_cp) / probe_n
        # the numerics decode is instrumentation a training loop pays per
        # step too — it shares the 1% budget with the spans
        telemetry_overhead_fraction = round(
            (per_span_s * spans_per_step + per_poll_s)
            / max(step_time_best, 1e-9)
            + (numerics_overhead_fraction or 0.0),
            6,
        )
        print(
            "# child: telemetry "
            + json.dumps({
                "mfu": mfu_val,
                "goodput_fraction": goodput_fraction,
                "time_breakdown": time_breakdown,
                "overhead_fraction": telemetry_overhead_fraction,
                "spans_per_step": round(spans_per_step, 3),
                "capture_poll_us": round(per_poll_s * 1e6, 2),
            }),
            flush=True,
        )
        # same counters through the sink layer (rank-0 JSONL under the
        # run dir), so harvest tooling reads them without parsing stdout
        try:
            from pytorch_distributedtraining_tpu.observe.sink import (
                JSONLSink,
            )

            _sink = JSONLSink()
            _sink.log({
                "bench_img_per_sec": round(img_per_sec, 2),
                "mfu": mfu_val,
                "goodput_fraction": goodput_fraction,
                **{
                    f"time_{k}_s": v
                    for k, v in (time_breakdown or {}).items()
                },
            })
            _sink.finish()
        except Exception as e:  # noqa: BLE001 — logging must not kill a run
            print(f"# child: telemetry sink unavailable: {e}", flush=True)
        if (os.environ.get("GRAFT_TRACE") or "").strip():
            try:
                print(
                    "# child: telemetry trace written: "
                    + telemetry.export_chrome_trace(),
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001
                print(f"# child: trace export failed: {e}", flush=True)
        # fleet-plane step-time histogram (observe/fleet.py): built
        # post-hoc from the already-recorded span buffer, so it costs the
        # hot path nothing and the 1% overhead gate below is unaffected
        try:
            from pytorch_distributedtraining_tpu.observe.fleet import (
                fleet_summary_from_records,
            )

            fleet_summary = fleet_summary_from_records(telemetry.records())
        except Exception as e:  # noqa: BLE001 — accounting, not the metric
            print(f"# child: fleet summary unavailable: {e}", flush=True)
        if telemetry_overhead_fraction > 0.01:
            # no "# " prefix: _informative_tail must pick THIS line as
            # the cause in the parent's error record
            print(
                f"TELEMETRY OVERHEAD: instrumentation cost "
                f"{telemetry_overhead_fraction:.2%} of the steady-state "
                f"step ({per_span_s * 1e6:.1f} us/span x "
                f"{spans_per_step:.2f} spans/step"
                + (
                    f" + numerics {numerics_overhead_fraction:.2%}"
                    if numerics_overhead_fraction else ""
                )
                + f" vs {step_time_best * 1e3:.3f} ms/step) exceeds the "
                "1% budget — the instrument is distorting the "
                "measurement, refusing to publish",
                flush=True,
            )
            sys.exit(9)
    # graftcheck (untimed; must run BEFORE the accounting passes below —
    # memory_analysis/pipeline probe legitimately add cache entries, so
    # the recompile-drift window closes here): trace+HLO rules over the
    # timed step, plus the cache-entry pair bracketing the fixed-shape
    # windows. Error-severity findings refuse to publish (exit 7): a
    # record whose timing includes recompiles, or whose step hides a
    # host round-trip, is not a benchmark result. GRAFT_BENCH_ANALYZE=0
    # opts out; analyzer *crashes* (not findings) degrade to
    # static_findings=None rather than killing the run.
    static_findings = None
    if os.environ.get("GRAFT_BENCH_ANALYZE", "1").strip().lower() not in (
        "0", "false", "off", "no"
    ):
        try:
            entries_after_windows = cache_entry_count(cache_path)
            from pytorch_distributedtraining_tpu.analyze import analyze_step

            report = analyze_step(
                step,
                state,
                batch,
                cache_entries_before=cache_entries_warm,
                cache_entries_after=entries_after_windows,
                cache_window=(
                    f"{len(rates)} timed windows x {actual_steps} "
                    "fixed-shape steps"
                ),
            )
            for line in report.render().splitlines():
                print(f"# child: {line}", flush=True)
            static_findings = report.counts()
            if not report.ok:
                # no "# " prefix: _informative_tail must pick THIS line
                # as the cause in the parent's error record
                print(
                    "STATIC ANALYSIS ERRORS: "
                    + "; ".join(
                        f"{f.rule}: {f.message}" for f in report.errors
                    )[:400]
                    + " — refusing to publish",
                    flush=True,
                )
                sys.exit(7)
        except SystemExit:
            raise
        except Exception as e:  # noqa: BLE001 — analyzer crash != finding
            print(f"# child: graftcheck unavailable: {e}", flush=True)
    # graftcheck source plane (untimed, no XLA work): the whole-repo AST
    # lint — host-divergent collectives, knob-registry drift, fault-site
    # drift, stdlib-only contracts. Same publication contract as the
    # artifact planes: ERROR findings exit 7 (a benched binary whose
    # source carries a pod-deadlock hazard or a drifted knob table is
    # not a publishable configuration), same GRAFT_BENCH_ANALYZE opt-out,
    # and analyzer crashes degrade to source_findings=None.
    source_findings = None
    if os.environ.get("GRAFT_BENCH_ANALYZE", "1").strip().lower() not in (
        "0", "false", "off", "no"
    ):
        try:
            from pytorch_distributedtraining_tpu.analyze.source_rules import (
                source_report,
            )

            src_report = source_report()
            for line in src_report.render().splitlines():
                print(f"# child: source: {line}", flush=True)
            source_findings = src_report.counts()
            if not src_report.ok:
                print(
                    "SOURCE ANALYSIS ERRORS: "
                    + "; ".join(
                        f"{f.rule}: {f.message}" for f in src_report.errors
                    )[:400]
                    + " — refusing to publish",
                    flush=True,
                )
                sys.exit(7)
        except SystemExit:
            raise
        except Exception as e:  # noqa: BLE001 — analyzer crash != finding
            print(f"# child: source plane unavailable: {e}", flush=True)
    # Convergence A/B gate (untimed; runs AFTER graftcheck so its extra
    # compiles land outside the recompile-drift window): a short fp32
    # TrainStep run vs the quantized step, both from identical init
    # params over the same batch sequence. A quantized loss that drifts
    # past tolerance means the wire format is eating the model, and the
    # throughput number must not publish (exit 8 — deterministic, the
    # parent emits an error record, never a headline value).
    # GRAFT_WIRE_GATE=0 skips; _STEPS / _TOL resize the probe.
    wire_gate = None
    if wire_fmt is not None and os.environ.get(
        "GRAFT_WIRE_GATE", "1"
    ).strip().lower() not in ("0", "false", "off", "no"):
        gate_steps = max(2, int_env("GRAFT_WIRE_GATE_STEPS", "12"))
        try:
            gate_tol = float(os.environ.get("GRAFT_WIRE_GATE_TOL", "0.05"))
        except ValueError:
            raise SystemExit("GRAFT_WIRE_GATE_TOL must be a float")
        print(
            f"# child: convergence gate: {gate_steps} steps fp32 vs "
            f"{wire_fmt.name}, tol {gate_tol}",
            flush=True,
        )
        # same init rng as the timed run -> identical starting params
        ref_state, _ = create_train_state(
            init_fn=lambda rng: (
                model.init(rng, jnp.zeros((1, PATCH, PATCH, 3)))["params"],
                {},
            ),
            tx=tx, mesh=mesh, policy=policy,
        )
        q_state, _ = create_train_state(
            init_fn=lambda rng: (
                model.init(rng, jnp.zeros((1, PATCH, PATCH, 3)))["params"],
                {},
            ),
            tx=tx, mesh=mesh, policy=policy,
        )
        ref_step = TrainStep(
            loss_fn, tx, mesh, policy,
            precision=Precision(), extra_metrics=False, donate=False,
        )
        gate_batches = [
            (
                jax.device_put(lr_all[j * BATCH:(j + 1) * BATCH]),
                jax.device_put(hr_all[j * BATCH:(j + 1) * BATCH]),
            )
            for j in range(n_distinct // BATCH)
        ]
        with mesh:
            for i in range(gate_steps):
                b = gate_batches[i % len(gate_batches)]
                ref_state, m_ref = ref_step(ref_state, b)
                q_state, m_q = step(q_state, b)
            ref_loss = float(m_ref["loss"])
            q_loss = float(m_q["loss"])
        rel_delta = abs(q_loss - ref_loss) / max(abs(ref_loss), 1e-12)
        wire_gate = {
            "steps": gate_steps,
            "fp32_loss": round(ref_loss, 6),
            "quantized_loss": round(q_loss, 6),
            "rel_delta": round(rel_delta, 6),
            "tol": gate_tol,
        }
        print(f"# child: wire gate {json.dumps(wire_gate)}", flush=True)
        if not np.isfinite(q_loss) or rel_delta > gate_tol:
            # no "# " prefix: _informative_tail must pick THIS line as
            # the cause in the parent's error record
            print(
                f"CONVERGENCE GATE: quantized wire {wire_fmt.name} loss "
                f"{q_loss:.6f} vs fp32 {ref_loss:.6f} after {gate_steps} "
                f"steps (rel delta {rel_delta:.4f} > tol {gate_tol}) — "
                "refusing to publish",
                flush=True,
            )
            sys.exit(8)
    # HBM accounting (untimed, after the windows): XLA's memory plan for
    # the compiled step — the persistent compile cache makes this AOT
    # lower+compile a cheap deserialize, not a second cold compile. None
    # when the backend has no memory analysis.
    peak_hbm_bytes = None
    try:
        mem = step.memory_analysis(state, batch)
        # live HBM high-water/in-use into observe.memory's module stats
        # (the crash flight record picks them up via sys.modules)
        from pytorch_distributedtraining_tpu.observe.memory import (
            record_hbm_stats,
        )

        record_hbm_stats(
            projected_peak_bytes=(
                mem.peak_bytes if mem is not None else None
            )
        )
        if mem is not None:
            peak_hbm_bytes = mem.peak_bytes
            print(
                f"# child: projected peak HBM {peak_hbm_bytes / 1e6:.1f} MB "
                f"(args {mem.argument_bytes / 1e6:.1f} + out "
                f"{mem.output_bytes / 1e6:.1f} + temp "
                f"{mem.temp_bytes / 1e6:.1f} - alias "
                f"{mem.alias_bytes / 1e6:.1f})",
                flush=True,
            )
    except Exception as e:  # noqa: BLE001 — accounting must not kill a run
        print(f"# child: memory analysis unavailable: {e}", flush=True)
    # pipeline provenance (untimed): pp>1 resolves the schedule table for
    # its analytic bubble fraction and — when the backend has the devices —
    # compiles a small stacked-trunk PipelineStep for the XLA memory plan
    # (pp_peak_residency_bytes; the measured GPipe-vs-1F1B A/B lives in
    # benchmarks/pipeline_bench.py)
    bubble_fraction = None
    pp_peak_residency_bytes = None
    if pp_impl > 1:
        try:
            from pytorch_distributedtraining_tpu.parallel.pipeline import (
                build_schedule,
            )

            pp_n_micro = pp_micro_impl or 2 * pp_impl
            pp_v = 2 if pp_schedule_impl == "interleaved" else 1
            sched = build_schedule(
                pp_schedule_impl, pp_impl, pp_n_micro, v=pp_v
            )
            bubble_fraction = round(sched.bubble_fraction, 4)
            if jax.device_count() >= pp_impl:
                pp_peak_residency_bytes = _pipeline_probe_peak(
                    pp_impl, pp_schedule_impl, pp_n_micro
                )
                print(
                    f"# child: pipeline probe pp={pp_impl} "
                    f"{pp_schedule_impl} bubble={bubble_fraction} peak="
                    f"{pp_peak_residency_bytes}",
                    flush=True,
                )
        except Exception as e:  # noqa: BLE001 — provenance, not the metric
            print(f"# child: pipeline probe unavailable: {e}", flush=True)
    # Op-cost attribution + cost-model calibration (untimed, after every
    # gate that polices the timed windows): parse a short steady-state
    # profiler trace into per-class cost tables and per-axis collective
    # bandwidth, then score the analytic models (MFU FLOPs, the
    # hops-model wire bytes, the pipeline bubble) against what was
    # measured (observe/opcost.py). The per-class table is what
    # benchmarks/trace_diff.py diffs when the regression sentry fires.
    # GRAFT_OPCOST=0 opts out.
    opcost_block = None
    calibration_block = None
    _opc_env = os.environ.get("GRAFT_OPCOST")
    if (_opc_env if _opc_env is not None else "1").strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        try:
            from pytorch_distributedtraining_tpu.observe import (
                opcost as opcost_mod,
                profiling as _prof,
            )

            opcost_trace_dir = trace_dir
            opcost_steps = 3  # the GRAFT_BENCH_TRACE pre-window trace
            if not opcost_trace_dir:
                # no pre-window trace: capture 2 steps now into the run
                # dir (the guarded trace no-ops if an anomaly capture is
                # still in flight; ingest then finds nothing and skips)
                opcost_trace_dir = os.path.join(
                    telemetry.run_dir(), "opcost_trace"
                )
                opcost_steps = 2
                with mesh, _prof.trace(opcost_trace_dir):
                    for _ in range(opcost_steps):
                        state, _opc_metrics = step(state, batch)
                    jax.block_until_ready(_opc_metrics["loss"])
            hlo_text = None
            try:
                hlo_text = step.compiled_text(state, batch)
            except Exception as e:  # noqa: BLE001 — join is optional
                print(f"# child: opcost hlo unavailable: {e}", flush=True)
            ingest = opcost_mod.ingest_trace(
                opcost_trace_dir,
                hlo_text=hlo_text,
                mesh_axes=dict(mesh.shape),
                steps=opcost_steps,
            )
            if ingest is None:
                print("# child: opcost trace empty", flush=True)
            else:
                tbl = ingest["table"]
                nsteps = max(1, opcost_steps)
                per_class_s = {
                    cls: round(row["seconds"] / nsteps, 9)
                    for cls, row in tbl["classes"].items()
                }
                bw = ingest["bandwidth"] or {}
                opcost_block = {
                    "trace_steps": nsteps,
                    "total_s": round(tbl["total_s"] / nsteps, 9),
                    "per_class_s": per_class_s,
                    "collectives": {
                        r["op"]: round(r["s"] / nsteps, 9)
                        for r in tbl["collectives"]
                    },
                    "axis_bytes_per_s": {
                        ax: (
                            round(row["bytes_per_s"], 1)
                            if row.get("bytes_per_s")
                            else None
                        )
                        for ax, row in bw.items()
                    } or None,
                }
                print(
                    "# child: opcost " + json.dumps(opcost_block),
                    flush=True,
                )
                models = {}
                if flops_per_step:
                    from pytorch_distributedtraining_tpu.observe.goodput \
                        import peak_flops
                    dev0 = jax.devices()[0]
                    pf = peak_flops(
                        dev0.platform, getattr(dev0, "device_kind", "")
                    )
                    if pf and per_class_s.get("compute"):
                        models["mfu_flops"] = {
                            "analytic": flops_per_step / pf,
                            "measured": per_class_s["compute"],
                            "unit": "s",
                        }
                # wire model: hops-convention analytic bytes (wire_cost /
                # comm_cost walk the params) vs what XLA actually emitted
                # (the HLO wire-inventory join behind the bandwidth rows)
                measured_wire_bytes = (
                    sum(row.get("bytes", 0) for row in bw.values()) / nsteps
                )
                analytic_wire = None
                if wire_info is not None:
                    analytic_wire = wire_info.get("wire_bytes")
                else:
                    try:
                        analytic_wire = step.comm_cost(
                            state.params
                        )["fp32_bytes"]
                    except Exception:  # noqa: BLE001 — optional model
                        analytic_wire = None
                if analytic_wire and measured_wire_bytes:
                    models["wire"] = {
                        "analytic": float(analytic_wire),
                        "measured": float(measured_wire_bytes),
                        "unit": "bytes",
                    }
                if bubble_fraction and opcost_block["total_s"]:
                    # measured bubble: the device-idle share of the best
                    # window's step — 1 - busy/wall (an approximation:
                    # the trace's op seconds are the busy side)
                    busy = min(opcost_block["total_s"], step_time_best)
                    models["bubble"] = {
                        "analytic": float(bubble_fraction),
                        "measured": max(
                            0.0, 1.0 - busy / max(step_time_best, 1e-9)
                        ),
                        "unit": "fraction",
                    }
                prev_cal = (_read_last_good() or {}).get("calibration")
                calibration_block = (
                    opcost_mod.calibrate(models, previous=prev_cal) or None
                )
                if calibration_block:
                    cal_path = opcost_mod.write_calibration(
                        os.path.join(
                            telemetry.run_dir(), "calibration.json"
                        ),
                        calibration_block,
                        meta={
                            "metric": METRIC,
                            "value": round(img_per_sec, 2),
                            # measured per-axis collective bandwidth —
                            # parallel/hierarchy.py (bucket sizing) and
                            # the planner's --axis-bw auto-load read
                            # this back instead of analytic constants
                            "axis_bandwidth": {
                                ax: round(row["bytes_per_s"], 1)
                                for ax, row in bw.items()
                                if row.get("bytes_per_s")
                            } or None,
                        },
                    )
                    print(
                        f"# child: calibration -> {cal_path} "
                        + json.dumps(calibration_block),
                        flush=True,
                    )
        except Exception as e:  # noqa: BLE001 — accounting, not the metric
            print(f"# child: opcost unavailable: {e}", flush=True)
    cache_entries_now = cache_entry_count(cache_path)
    compile_cache = {
        "enabled": cache_path is not None,
        "dir": cache_path,
        "entries_before": cache_entries_before,
        "new_entries": max(0, cache_entries_now - cache_entries_before),
        # hit = the warm path: entries existed and the compile added none
        "hit": bool(
            cache_path
            and cache_entries_before > 0
            and cache_entries_now <= cache_entries_before
        ),
    }
    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": round(img_per_sec, 2),
                "unit": UNIT,
                "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
                "windows": len(rates),
                "window_rates": [round(r, 1) for r in rates],
                "steps_per_window": actual_steps,
                "batch": BATCH,
                "final_loss": round(final_loss, 6),
                "time_to_first_step_s": round(time_to_first_step, 2),
                "feed": feed_impl,
                "prefetch_depth": (
                    prefetch_depth if feed_impl == "prefetch" else None
                ),
                "overlap_fraction": overlap_fraction,
                "mfu": mfu_val,
                "goodput_fraction": goodput_fraction,
                "time_breakdown": time_breakdown,
                "telemetry_overhead_fraction": telemetry_overhead_fraction,
                "numerics": numerics_block,
                "fleet": fleet_summary,
                "opcost": opcost_block,
                "calibration": calibration_block,
                "capture": (
                    capture_prof.summary()
                    if capture_prof is not None
                    else None
                ),
                "compile_cache": compile_cache,
                "static_findings": static_findings,
                "source_findings": source_findings,
                "peak_hbm_bytes": peak_hbm_bytes,
                "remat": remat_impl,
                "scan_layers": scan_layers,
                "wire_format": (
                    wire_info["wire_format"] if wire_info else None
                ),
                "wire_bytes": (
                    wire_info["wire_bytes"] if wire_info else None
                ),
                "wire_fp32_bytes": (
                    wire_info["fp32_bytes"] if wire_info else None
                ),
                "wire_gate": wire_gate,
                "pp": pp_impl,
                "pp_schedule": pp_schedule_impl if pp_impl > 1 else None,
                "bubble_fraction": bubble_fraction,
                "pp_peak_residency_bytes": pp_peak_residency_bytes,
            }
        )
    )


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 — 'never silence' contract
        # Parent-side bugs / fork failures must still yield the record.
        # Child processes re-raise normally (the parent reads their rc).
        if os.environ.get("_GRAFT_BENCH_CHILD") or os.environ.get(
            "_GRAFT_BENCH_PROBE"
        ):
            raise
        _emit_error(f"unexpected parent error: {type(e).__name__}: {e}")
