"""Runtime-plane rules over measured process state (not jaxpr/HLO).

The runtime plane reads facts only the live process knows: env knobs,
loaded modules, harness state. First resident: the bench-telemetry rule —
a benchmark run whose step is being timed without the unified telemetry
layer (observe/trace.py) publishes a throughput number with no goodput/
MFU decomposition behind it — exactly when run-to-run noise gets mistaken
for a regression.
"""

from __future__ import annotations

import os
import sys

from .findings import Finding, Severity
from .registry import rule


@rule(
    "bench-telemetry",
    "runtime",
    "bench step timed without the unified telemetry layer enabled",
)
def bench_telemetry(ctx):
    if not os.environ.get("GRAFT_BENCH"):
        return
    # sys.modules lookup, not an import: this module must stay importable
    # from jax-free tooling, and an un-imported tracer IS the finding
    tr = sys.modules.get("pytorch_distributedtraining_tpu.observe.trace")
    if tr is not None and tr.enabled():
        return
    yield Finding(
        "bench-telemetry",
        Severity.WARN,
        "runtime:telemetry",
        "bench run is timing the step without telemetry: the published "
        "record will carry no goodput/MFU breakdown, so a slow window "
        "cannot be attributed (compile vs input-wait vs outage). Unset "
        "GRAFT_TELEMETRY=0 or accept an unattributable number",
        evidence=(
            "observe.trace "
            + ("loaded but disabled" if tr is not None else "never imported")
        ),
    )


def _ckpt_stats():
    """checkpoint_sharded.runtime_stats via sys.modules — never imported
    (the checkpoint layer pulls in jax; this plane must stay jax-free)."""
    ck = sys.modules.get("pytorch_distributedtraining_tpu.checkpoint_sharded")
    return getattr(ck, "runtime_stats", None)


@rule(
    "ckpt-commits-silent",
    "runtime",
    "checkpoint saves initiated but no commit marker ever observed",
)
def ckpt_commits_silent(ctx):
    stats = _ckpt_stats()
    if stats is None or stats.get("save_every") is None:
        return
    # only process 0 runs the portable commit, so on ranks > 0
    # commits_observed is structurally 0 in a perfectly healthy run —
    # evaluate the rule where the commit actually happens
    if stats.get("process_index") not in (None, 0):
        return
    if stats.get("saves_initiated", 0) > 0 and not stats.get(
        "commits_observed", 0
    ):
        err = stats.get("last_write_error")
        yield Finding(
            "ckpt-commits-silent",
            Severity.WARN,
            "runtime:checkpoint",
            "checkpoint saves were initiated but NO commit marker landed: "
            "the async writer is silently dead (or every write is torn), "
            "so a preemption right now would resume from nothing. Check "
            "disk space / the writer's last error and call "
            "CheckpointManager.wait() to force the drain",
            evidence=(
                f"saves_initiated={stats.get('saves_initiated')} "
                f"commits_observed=0"
                + (f" last_write_error={err!r}" if err else "")
            ),
        )


@rule(
    "ckpt-manifest-mismatch",
    "runtime",
    "resume template's leaf shapes disagree with the checkpoint manifest",
)
def ckpt_manifest_mismatch(ctx):
    stats = _ckpt_stats()
    if not stats:
        return
    mismatches = stats.get("manifest_mismatches") or []
    if not mismatches:
        return
    yield Finding(
        "ckpt-manifest-mismatch",
        Severity.ERROR,
        "runtime:checkpoint",
        f"{len(mismatches)} template leaf(s) disagree with the checkpoint "
        "manifest (shape/dtype): the restore is loading a DIFFERENT model "
        "than was saved — a resumed run would train from silently corrupt "
        "state. Fix the template (model config / scan layout / precision) "
        "to match the manifest, or point at the right checkpoint",
        evidence="; ".join(str(m) for m in mismatches[:3]),
    )


@rule(
    "elastic-flap",
    "runtime",
    "membership epochs advancing faster than the grow hysteresis allows",
)
def elastic_flap(ctx):
    # sys.modules, never imported: membership is stdlib-only but lives in
    # the runtime package whose __init__ pulls jax — this plane must stay
    # importable from jax-free tooling
    ms = sys.modules.get(
        "pytorch_distributedtraining_tpu.runtime.membership"
    )
    stats = getattr(ms, "runtime_stats", None)
    if not stats:
        return
    window_s = stats.get("hysteresis_window_s")
    limit = stats.get("flap_limit")
    advances = stats.get("epoch_advances") or []
    if window_s is None or not limit or len(advances) <= limit:
        return
    # count epoch bumps inside any sliding hysteresis window: more than
    # `limit` world transitions within one window means the gate is being
    # overridden faster than it can damp — a flapping host is thrashing
    # the run through save/relaunch cycles instead of being quarantined
    window = max(float(window_s), 1.0)
    worst = 0
    lo = 0
    for hi in range(len(advances)):
        while advances[hi] - advances[lo] > window:
            lo += 1
        worst = max(worst, hi - lo + 1)
    if worst <= limit:
        return
    yield Finding(
        "elastic-flap",
        Severity.ERROR,
        "runtime:membership",
        f"membership epochs advanced {worst} times within one "
        f"{window:.0f}s hysteresis window (flap limit {limit}): a host "
        "is flapping — joining, being grown onto, and dying — and every "
        "cycle costs a forced save + relaunch + reshard. Raise "
        "GRAFT_GROW_PROBES / GRAFT_GROW_MIN_INTERVAL_S so admission "
        "needs a longer healthy streak, or quarantine the host "
        "(its failures may be misclassified as external)",
        evidence=(
            f"epoch_advances={len(advances)} worst_window={worst} "
            f"window_s={window:.0f} flap_limit={limit}"
        ),
    )


@rule(
    "serve-recompile-under-load",
    "runtime",
    "serving engine compiled new programs during its steady-state window",
)
def serve_recompile_under_load(ctx):
    # sys.modules, never imported: the engine pulls in jax and this plane
    # must stay importable from jax-free tooling
    eng = sys.modules.get("pytorch_distributedtraining_tpu.serve.engine")
    stats = getattr(eng, "runtime_stats", None)
    if not stats or not stats.get("steady_windows"):
        return
    grew = stats.get("steady_recompiles", 0)
    if grew <= 0:
        return
    yield Finding(
        "serve-recompile-under-load",
        Severity.ERROR,
        "runtime:serve",
        f"the serving engine compiled {grew} new program(s) AFTER marking "
        "steady state: some request shape escaped the warmed bucket set, "
        "so tail latency is paying trace+compile instead of a dispatch — "
        "exactly the p99 cliff continuous batching exists to remove. Add "
        "the offending shape to GRAFT_SERVE_BUCKETS (or cap request "
        "lengths) so warmup covers every dispatchable shape",
        evidence=(
            f"jit_entries_at_steady={stats.get('jit_entries_at_steady')} "
            f"jit_entries_now={stats.get('jit_entries_now')} "
            f"steady_recompiles={grew}"
        ),
    )


@rule(
    "serve-spec-regress",
    "runtime",
    "speculative decode regressing: low accept rate or steady-set growth",
)
def serve_spec_regress(ctx):
    # sys.modules, never imported: the engine pulls in jax and this plane
    # must stay importable from jax-free tooling
    eng = sys.modules.get("pytorch_distributedtraining_tpu.serve.engine")
    stats = getattr(eng, "runtime_stats", None)
    if not stats or not stats.get("spec_enabled"):
        return
    grew = stats.get("steady_recompiles", 0)
    if stats.get("steady_windows") and grew > 0:
        yield Finding(
            "serve-spec-regress",
            Severity.ERROR,
            "runtime:serve",
            f"speculative decode grew the steady compiled set by {grew} "
            "program(s): the fast path's contract is exactly ONE extra "
            "program (the [n_slots, k] verify step), warmed before "
            "mark_steady — anything beyond that means a spec shape "
            "escaped warmup and the latency win is being paid back as "
            "trace+compile on the serving path. Pin GRAFT_SERVE_SPEC_K "
            "so warmup and steady state agree on the draft depth",
            evidence=(
                f"spec_k={stats.get('spec_k')} "
                f"jit_entries_at_steady={stats.get('jit_entries_at_steady')} "
                f"jit_entries_now={stats.get('jit_entries_now')} "
                f"steady_recompiles={grew}"
            ),
        )
    if not stats.get("spec_ticks"):
        return
    raw = (os.environ.get("GRAFT_SPEC_ACCEPT_FLOOR") or "").strip()
    try:
        floor = float(raw) if raw else 0.0
    except ValueError:
        floor = 0.0
    rate = float(stats.get("spec_accept_rate", 1.0))
    if floor > 0.0 and rate < floor:
        yield Finding(
            "serve-spec-regress",
            Severity.WARN,
            "runtime:serve",
            f"speculative accept rate {rate:.3f} is below the provisioned "
            f"floor {floor:.3f}: each decode tick is verifying spec_k "
            "positions but banking barely more than the one guaranteed "
            "greedy token, so the verify pass's extra FLOPs/HBM traffic "
            "are overhead, not speedup. Lower GRAFT_SERVE_SPEC_K (shorter "
            "drafts fail cheaper) or disable the fast path for this "
            "workload — prompt-lookup drafting only pays off on "
            "repetitive continuations",
            evidence=(
                f"spec_k={stats.get('spec_k')} "
                f"spec_ticks={stats.get('spec_ticks')} "
                f"spec_proposed={stats.get('spec_proposed')} "
                f"spec_accepted={stats.get('spec_accepted')} "
                f"spec_accept_rate={rate:.4f} floor={floor}"
            ),
        )


@rule(
    "serve-slo-burn",
    "runtime",
    "serving error budget burning faster than provisioned",
)
def serve_slo_burn(ctx):
    # sys.modules, never imported: observe.slo is stdlib-only but its
    # package __init__ pulls jax — the serving engine's SLOTracker
    # populates runtime_stats before this plane runs
    slo = sys.modules.get("pytorch_distributedtraining_tpu.observe.slo")
    stats = getattr(slo, "runtime_stats", None)
    if not stats or not stats.get("requests"):
        return
    remaining = stats.get("budget_remaining")
    peak = stats.get("burn_rate_peak") or 0.0
    evidence = (
        f"objective={stats.get('objective')!r} "
        f"requests={stats.get('requests')} "
        f"violations={stats.get('violations')} "
        f"burn_rate_peak={peak:.3g} "
        f"budget_remaining={remaining}"
    )
    if remaining is not None and remaining <= 0:
        yield Finding(
            "serve-slo-burn",
            Severity.ERROR,
            "runtime:serve",
            "the serving error budget is EXHAUSTED: the run's all-time "
            "violation rate exceeds the budgeted miss fraction, so the "
            "latency/TTFT objective is already broken for this window — "
            "shed load (tighten admission), add slots/pages, or loosen "
            "GRAFT_SERVE_SLO_LATENCY_MS if the objective was aspirational",
            evidence=evidence,
        )
        return
    if peak > 1.0:
        yield Finding(
            "serve-slo-burn",
            Severity.WARN,
            "runtime:serve",
            f"serving SLO burn rate peaked at {peak:.2f}x the provisioned "
            "error budget: violations are arriving faster than budgeted, "
            "and at this pace the budget exhausts before the window does. "
            "Check the tail attribution (queue_wait => admission-bound, "
            "prefill padding => re-bucket, stall => slow readers) before "
            "the WARN becomes the exhausted-budget ERROR",
            evidence=evidence,
        )


@rule(
    "router-hang",
    "runtime",
    "a routed request is still open past the fleet router's deadline",
)
def router_hang(ctx):
    # sys.modules, never imported: serve.router is stdlib-only but its
    # package __init__ pulls jax — a live router populates runtime_stats
    rt = sys.modules.get("pytorch_distributedtraining_tpu.serve.router")
    stats = getattr(rt, "runtime_stats", None)
    if not stats:
        return
    deadline = stats.get("deadline_s")
    inflight = stats.get("inflight") or {}
    if deadline is None or not inflight:
        return
    import time as _time

    now = _time.monotonic()
    stuck = sorted(
        (rid, now - t0) for rid, t0 in inflight.items()
        if now - t0 > float(deadline)
    )
    if not stuck:
        return
    worst_rid, worst_age = max(stuck, key=lambda kv: kv[1])
    yield Finding(
        "router-hang",
        Severity.ERROR,
        "runtime:serve",
        f"{len(stuck)} routed request(s) are still open PAST the "
        f"{float(deadline):.0f}s dispatch deadline with no terminal "
        f"phase in the ledger (worst: rid={worst_rid} open "
        f"{worst_age:.1f}s): the router's never-hang contract is broken "
        "— a dispatch is blocked on a replica that neither answered nor "
        "died visibly. Check the replica's heartbeat (TTL expiry should "
        "have failed it over) and the transport's timeout wiring",
        evidence=(
            f"deadline_s={deadline} stuck={len(stuck)} "
            f"worst_rid={worst_rid} worst_age_s={worst_age:.3f} "
            f"inflight={len(inflight)}"
        ),
    )


@rule(
    "serve-replica-flap",
    "runtime",
    "a serve replica cycling register/deregister inside one hysteresis "
    "window",
)
def serve_replica_flap(ctx):
    # same elastic-flap machinery, applied per replica: membership's
    # runtime_stats records every replica register/deregister with a
    # monotonic stamp
    ms = sys.modules.get(
        "pytorch_distributedtraining_tpu.runtime.membership"
    )
    stats = getattr(ms, "runtime_stats", None)
    if not stats:
        return
    events = stats.get("replica_events") or []
    if not events:
        return
    window = max(float(stats.get("hysteresis_window_s") or 30.0), 1.0)
    try:
        limit = int(os.environ.get("GRAFT_FLAP_MAX", "3") or 3)
    except ValueError:
        limit = 3
    per_replica: dict = {}
    for t, rid, kind in events:
        per_replica.setdefault(str(rid), []).append(float(t))
    for rid, times in sorted(per_replica.items()):
        times.sort()
        # a register/deregister PAIR is one cycle; count lifecycle
        # events in the worst sliding window and halve
        worst = 0
        lo = 0
        for hi in range(len(times)):
            while times[hi] - times[lo] > window:
                lo += 1
            worst = max(worst, hi - lo + 1)
        cycles = worst // 2
        if cycles <= limit:
            continue
        yield Finding(
            "serve-replica-flap",
            Severity.WARN,
            "runtime:serve",
            f"replica {rid!r} cycled register/deregister {cycles} times "
            f"inside one {window:.0f}s hysteresis window (flap limit "
            f"{limit}): the fleet is churning a replica faster than the "
            "scale gate can damp — every cycle re-warms an engine and "
            "migrates or replays its residents. Raise GRAFT_FLAP_MAX "
            "only if the churn is intentional; otherwise widen the "
            "GrowGate (GRAFT_GROW_PROBES / GRAFT_GROW_MIN_INTERVAL_S) "
            "or fix the replica's crash loop",
            evidence=(
                f"replica={rid} events={len(times)} worst_window={worst} "
                f"cycles={cycles} window_s={window:.0f} "
                f"flap_limit={limit}"
            ),
        )


def _numerics_stats():
    """observe.numerics.runtime_stats via sys.modules — never imported
    (stdlib-only module, but importing it here would defeat the
    'a live probe IS the signal' contract: stats only exist when the
    training process actually ran the numerics plane)."""
    nm = sys.modules.get(
        "pytorch_distributedtraining_tpu.observe.numerics"
    )
    return getattr(nm, "runtime_stats", None)


@rule(
    "numerics-nonfinite",
    "runtime",
    "the numerics probe observed non-finite gradients, with blame",
)
def numerics_nonfinite(ctx):
    stats = _numerics_stats()
    if not stats or not stats.get("nonfinite_steps_total"):
        return
    blame = stats.get("last_nonfinite") or {}
    where = blame.get("leaf", "<unknown leaf>")
    layer = blame.get("layer")
    if layer is not None and layer >= 0:
        where += f" (layer {layer})"
    yield Finding(
        "numerics-nonfinite",
        Severity.ERROR,
        "runtime:numerics",
        f"{stats['nonfinite_steps_total']} step(s) produced non-finite "
        f"gradients; first offender of the latest: {where} at step "
        f"{blame.get('step')}. Every poisoned step trains on garbage — "
        "roll back to the last committed checkpoint "
        "(GRAFT_NUMERICS_ACTION=rollback), or bisect the leaf (lr too "
        "hot, fp8 overflow, quantized wire) before resuming",
        evidence=(
            f"nonfinite_steps_total={stats['nonfinite_steps_total']} "
            f"last_nonfinite={blame!r} "
            f"grad_norm_last={stats.get('grad_norm_last')}"
        ),
    )


@rule(
    "numerics-divergence",
    "runtime",
    "the numerics watchdog tripped on a confirmed divergence",
)
def numerics_divergence(ctx):
    stats = _numerics_stats()
    if not stats:
        return
    for v in stats.get("verdicts") or []:
        yield Finding(
            "numerics-divergence",
            Severity.WARN,
            "runtime:numerics",
            f"watchdog tripped: {v.get('kind')} at step {v.get('step')} "
            f"(action={v.get('action')}) — {v.get('detail')}. A trip "
            "that rolled back cleanly is survivable but the trajectory "
            "lost the rolled-back window; repeated trips mean the run "
            "is unstable (lower the lr, widen the clip, or degrade the "
            "quantized wire)",
            evidence=(
                f"kind={v.get('kind')} step={v.get('step')} "
                f"action={v.get('action')}"
                + (f" z={v.get('z')}" if v.get("z") is not None else "")
            ),
        )


def _opcost_stats():
    """observe.opcost.runtime_stats via sys.modules — never imported
    (stdlib-only module, same 'a live probe IS the signal' contract:
    bandwidth/calibration stats only exist when something in this
    process actually ingested a profiler trace)."""
    oc = sys.modules.get(
        "pytorch_distributedtraining_tpu.observe.opcost"
    )
    return getattr(oc, "runtime_stats", None)


@rule(
    "comm-bandwidth-degraded",
    "runtime",
    "a mesh axis's measured collective bandwidth fell below its best",
)
def comm_bandwidth_degraded(ctx):
    stats = _opcost_stats()
    if not stats:
        return
    try:
        frac = float(os.environ.get("GRAFT_BW_DEGRADED_FRAC", "0.5") or 0.5)
    except ValueError:
        frac = 0.5
    for axis, bw in (stats.get("axis_bandwidth") or {}).items():
        best = (stats.get("axis_bandwidth_best") or {}).get(axis)
        if not best or bw >= frac * best:
            continue
        yield Finding(
            "comm-bandwidth-degraded",
            Severity.WARN,
            "runtime:opcost",
            f"measured collective bandwidth on mesh axis {axis!r} is "
            f"{bw / 1e9:.2f} GB/s — {bw / best:.0%} of the "
            f"{best / 1e9:.2f} GB/s this process has seen on the same "
            "axis. The links did not change; the traffic pattern or the "
            "neighborhood did (congested DCN hop, a straggling peer "
            "serializing the ring, or a layout change routing gradient "
            "bytes over the slow axis). Check the per-axis gauges on the "
            "fleet endpoint before trusting new step-time numbers",
            evidence=(
                f"axis={axis} bytes_per_s={bw:.3e} best={best:.3e} "
                f"threshold_frac={frac}"
            ),
        )


@rule(
    "calibration-drift",
    "runtime",
    "an analytic cost model drifted from its measured calibration",
)
def calibration_drift(ctx):
    stats = _opcost_stats()
    if not stats:
        return
    try:
        tol = float(
            os.environ.get("GRAFT_CALIB_DRIFT_TOL", "0.5") or 0.5
        )
    except ValueError:
        tol = 0.5
    for name, row in (stats.get("calibration") or {}).items():
        drift = row.get("drift")
        if drift is None or abs(drift) <= tol:
            continue
        yield Finding(
            "calibration-drift",
            Severity.ERROR,
            "runtime:opcost",
            f"cost model {name!r} drifted {drift:+.0%} from its previous "
            f"measured/analytic ratio ({row.get('ratio')} vs the last "
            "calibration.json): every plan built on this model — wire "
            "byte budgets, bubble-fraction schedules, MFU targets — is "
            "now reasoning about a machine that no longer exists. "
            "Re-measure (refresh calibration.json from a clean capture) "
            "or find what changed under the model (compiler version, "
            "mesh layout, dtype legalization)",
            evidence=(
                f"model={name} ratio={row.get('ratio')} "
                f"drift={drift:+.4f} tol={tol} "
                f"analytic={row.get('analytic')} "
                f"measured={row.get('measured')} unit={row.get('unit')!r}"
            ),
        )


def _plan_stats():
    """analyze.plan's gauges via sys.modules — same no-import contract
    as every other runtime source (plan.py is stdlib-only, but going
    through its package spelling keeps this plane import-free)."""
    mod = sys.modules.get("pytorch_distributedtraining_tpu.analyze.plan")
    return getattr(mod, "runtime_stats", None) if mod else None


@rule(
    "plan-stale",
    "runtime",
    "calibration drifted past tolerance after the active plan was ranked",
)
def plan_stale(ctx):
    stats = _plan_stats()
    if not stats or not stats.get("stale") or not stats.get("active_plan"):
        return
    plan = stats["active_plan"]
    yield Finding(
        "plan-stale",
        Severity.WARN,
        "runtime:plan",
        f"the active GRAFT_PLAN (rank {plan.get('rank')}, "
        f"{plan.get('policy')} on {plan.get('topology')}) was ranked "
        "with calibration ratios that have since drifted past tolerance "
        f"({stats.get('stale_reason')}). The plan still runs, but its "
        "ordering argument is gone — the runner-up may now be faster. "
        "Re-run the planner (python -m "
        "pytorch_distributedtraining_tpu.analyze.plan) against the fresh "
        "calibration.json; it re-ranks automatically",
        evidence=(
            f"rank={plan.get('rank')} key={plan.get('policy')}/"
            f"remat={plan.get('remat')}/pp={plan.get('pp')} "
            f"stale_reason={stats.get('stale_reason')!r} "
            f"applied_at={stats.get('applied_at')}"
        ),
    )


@rule(
    "plan-infeasible",
    "runtime",
    "the applied GRAFT_PLAN fails its own memory/static prune here",
)
def plan_infeasible(ctx):
    stats = _plan_stats()
    if not stats or not stats.get("active_plan"):
        return
    reason = stats.get("infeasible")
    if not reason:
        return
    plan = stats["active_plan"]
    yield Finding(
        "plan-infeasible",
        Severity.ERROR,
        "runtime:plan",
        f"the applied GRAFT_PLAN does not survive its own prune on this "
        f"topology: {reason}. The plan was ranked for "
        f"{plan.get('topology')!r} ({plan.get('dp')}x{plan.get('fsdp')}"
        f"x{plan.get('pp')} devices) — applying it here either OOMs or "
        "silently trains a different layout than the one the ranking "
        "argued for. Re-plan for THIS topology instead of reusing the "
        "artifact",
        evidence=(
            f"reason={reason!r} plan_devices={plan.get('dp', 1)}*"
            f"{plan.get('fsdp', 1)}*{plan.get('pp', 1)} "
            f"peak_bytes={plan.get('peak_bytes')} "
            f"feasible={plan.get('feasible')}"
        ),
    )


@rule(
    "bench-regression",
    "runtime",
    "a fresh bench record regressed against the BENCH_* trajectory",
)
def bench_regression(ctx):
    # sys.modules, never imported: observe.fleet is stdlib-only but its
    # package __init__ pulls jax — the sentry (benchmarks/regress.py)
    # populates runtime_stats before this plane runs
    fl = sys.modules.get("pytorch_distributedtraining_tpu.observe.fleet")
    stats = getattr(fl, "runtime_stats", None)
    if not stats:
        return
    for v in stats.get("verdicts") or []:
        status = v.get("status")
        if status not in ("drift", "regression"):
            continue
        sev = Severity.ERROR if status == "regression" else Severity.WARN
        yield Finding(
            "bench-regression",
            sev,
            "runtime:bench",
            (
                f"bench metric {v.get('metric')!r} {status}: "
                f"{v.get('detail', 'worse than the trajectory baseline')}. "
                "Outage/fallback records are already excluded from the "
                "baseline, so this is a genuine same-code slowdown — "
                "bisect the change, or re-measure before refreshing "
                "BENCH_LAST_GOOD.json (the sentry will not refresh it "
                "over a regression)"
            ),
            evidence=(
                f"value={v.get('value')} "
                f"baseline_median={v.get('baseline_median')} "
                f"n_history={v.get('n_history')} "
                f"worse_frac={v.get('worse_frac')} "
                f"noise_frac={v.get('noise_frac')}"
            ),
        )
