"""Latency-SLO serving bench: continuous vs static batching, same trace.

Open-loop load generator (seeded Poisson arrivals, mixed prompt lengths
and token budgets) driven through the ``serve/`` engine twice:

- **continuous** — the engine under test: paged KV cache, chunked prefill
  interleaved with batched decode, requests admitted the tick a slot
  frees;
- **static** — the gang baseline: a batch only admits into an EMPTY
  engine (what a fixed-batch ``generate()`` loop does), so a straggler
  request holds every finished slot hostage.

Both arms warm up their whole compiled set first and then assert the
steady-state window compiled **nothing** — the graftcheck runtime rule
``serve-recompile-under-load`` is run in-process and its verdict is part
of the published record (a p99 that secretly paid a compile is not a
p99). A fault-chaos sub-run exercises the two serving fault sites:
``serve.admit``/raise must shed exactly the planned request without
killing the engine, ``serve.client``/sleep is a slow reader whose stall
the engine accounts.

Each arm also carries its request-lifecycle accounting
(``observe/slo.py``): a per-phase latency breakdown (queue_wait /
prefill / decode / stall / deliver / other, summing to wall latency), a
p99 **tail attribution** (which phase owns the tail, and how much of it
is bucket/batch padding vs genuine compute — asserted non-empty), and
the SLO tracker's burn rate. The lifecycle bookkeeping's own cost is
measured in-process and published as ``telemetry_overhead_fraction``,
gated at a 1% publication bar (exit 9 over it). The continuous arm's lifecycles are exported as a
``graft-serve`` Chrome-trace lane for ``trace_summary.py``.

Two decode fast-path arms ride the same trace (docs/SERVING.md):

- **spec** — self-speculative decoding (``spec_k`` drafts per tick, one
  batched verify). Greedy decode is deterministic, so the arm's tokens
  must be **identical** per request to the continuous arm's
  (``spec_token_identical``) — a speedup that changes tokens is a bug,
  not a speedup — and its realized ``accept_rate`` is published next to
  the ``decode_tokens_per_sec_spec`` headline.
- **kvq** — block-scaled quantized paged KV residency
  (``GRAFT_SERVE_KV_WIRE``, default int8_block for the bench): the
  engine's ``kv_bytes_per_slot`` pricing must show >= 1.8x resident
  slots per HBM byte vs dense, gated by per-request token agreement
  with the dense continuous arm (``kv_gate_green``).

One JSON line:
    {"metric": "serve_slo", "continuous": {p50/p99 latency + TTFT,
     tokens/sec, occupancy, steady_recompiles, phase_breakdown_s,
     tail_attribution, slo}, "static": {...}, "spec": {...,
     spec_k, accept_rate, decode_tokens_per_sec}, "kvq": {...,
     kv_wire, kv_bytes_per_slot, slots_per_hbm_gain},
     "spec_k": ..., "accept_rate": ..., "kv_wire": ...,
     "kv_bytes_per_slot": ..., "decode_tokens_per_sec_spec": ...,
     "spec_token_identical": bool, "kv_gate_green": bool,
     "slo_burn_rate": ..., "telemetry_overhead_fraction": ...,
     "continuous_beats_static": bool, "graftcheck_clean": bool, ...}

Env: GRAFT_BENCH_PLATFORM=cpu -> tiny-model CPU self-test;
GRAFT_SERVE_BENCH_REQUESTS / GRAFT_SERVE_BENCH_GAP_MS resize the trace;
GRAFT_SERVE_SPEC_K / GRAFT_SERVE_KV_WIRE pick the fast-path arms' knobs
(bench defaults 4 / int8_block when unset — the vanilla arms always run
with the fast path off, so the A/B stays honest); the engine's other
GRAFT_SERVE_* / GRAFT_SERVE_SLO_* knobs apply to every arm.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

CPU_SELF_TEST = os.environ.get("GRAFT_BENCH_PLATFORM") == "cpu"
N_REQUESTS = max(4, int(
    os.environ.get("GRAFT_SERVE_BENCH_REQUESTS", "24" if CPU_SELF_TEST else "64")
))
GAP_MS = float(os.environ.get("GRAFT_SERVE_BENCH_GAP_MS", "2.0"))


def build_trace(rng, n, *, mean_gap_s, prompt_lens, max_new_lo, max_new_hi):
    """Seeded open-loop arrival trace: Poisson gaps, mixed shapes."""
    from pytorch_distributedtraining_tpu.serve.scheduler import Request

    t = 0.0
    out = []
    for rid in range(n):
        t += float(rng.exponential(mean_gap_s))
        plen = int(rng.choice(prompt_lens))
        out.append(Request(
            rid,
            rng.integers(0, 64, size=plen).astype("int32"),
            int(rng.integers(max_new_lo, max_new_hi + 1)),
            arrival_s=t,
        ))
    return out


def _pct(vals, q):
    import numpy as np

    return float(np.percentile(np.asarray(vals, float), q)) if vals else None


def _arm(cfg, params, trace, admission, knobs, realtime):
    """One engine arm over a (copied) trace; returns (summary, engine)."""
    from pytorch_distributedtraining_tpu.observe import slo as slo_mod
    from pytorch_distributedtraining_tpu.serve.engine import ServeEngine
    from pytorch_distributedtraining_tpu.serve.scheduler import Request

    eng = ServeEngine(cfg, params, admission=admission, **knobs)
    eng.warmup()
    eng.mark_steady()
    # fresh Request objects: scheduler state must not leak across arms
    reqs = [
        Request(r.rid, r.prompt.copy(), r.max_new_tokens, r.arrival_s)
        for r in trace
    ]
    t0 = time.perf_counter()
    records = eng.run(reqs, realtime=realtime)
    wall = time.perf_counter() - t0
    lat = [r["latency_s"] for r in records]
    ttft = [r["ttft_s"] for r in records if r["ttft_s"] is not None]
    new_tokens = sum(r["new_tokens"] for r in records)
    m = eng.metrics()
    completed = eng.ledger.completed
    phase_sum: dict = {}
    for r in completed:
        for phase, secs in r["phases"].items():
            phase_sum[phase] = phase_sum.get(phase, 0.0) + secs
    return {
        "admission": admission,
        "delivered": len(records),
        "new_tokens": new_tokens,
        "wall_s": round(wall, 4),
        "throughput_tok_s": round(new_tokens / wall, 2) if wall else None,
        "p50_latency_s": _pct(lat, 50),
        "p99_latency_s": _pct(lat, 99),
        "p50_ttft_s": _pct(ttft, 50),
        "p99_ttft_s": _pct(ttft, 99),
        "mean_slot_occupancy": round(m["mean_slot_occupancy"], 4),
        "ticks": m["ticks"],
        "steady_recompiles": m["steady_recompiles"],
        "compiled_programs": m["compiled_programs"],
        # request-lifecycle accounting (observe/slo.py): where the
        # latency went, phase-by-phase, and who owns the tail
        "phase_breakdown_s": {
            k: round(v, 6) for k, v in sorted(
                phase_sum.items(), key=lambda kv: -kv[1]
            )
        },
        "phase_p50_s": slo_mod.phase_quantiles(completed, 50),
        "phase_p99_s": slo_mod.phase_quantiles(completed, 99),
        "tail_attribution": slo_mod.tail_attribution(completed),
        "slo": m["slo"],
        # decode fast-path accounting (zeros/None when the path is off)
        "decode_tokens_per_sec": round(m["decode_tokens_per_sec"], 2),
        "spec_k": m["spec"]["spec_k"],
        "accept_rate": round(m["spec"]["accept_rate"], 4),
        "kv_wire": m["kv"]["kv_wire"],
        "kv_bytes_per_slot": m["kv"]["kv_bytes_per_slot"],
        "slots_per_hbm_gain": round(m["kv"]["slots_per_hbm_gain"], 4),
    }, eng


def _tokens_by_rid(eng) -> dict:
    return {r["rid"]: list(r["tokens"]) for r in eng.delivered}


def _token_agreement(a: dict, b: dict) -> float:
    """Fraction of requests whose full token sequences agree."""
    rids = set(a) & set(b)
    if not rids:
        return 0.0
    return sum(1 for r in rids if a[r] == b[r]) / len(rids)


def _ledger_overhead_fraction(eng, wall_s: float) -> float:
    """Measured cost of the lifecycle bookkeeping, as a fraction of the
    arm's wall time. A
    scratch ledger absorbs 2000 interval closes to price one op, then
    the arm's actual op count (intervals recorded + per-tick gauge
    stores) converts it to seconds."""
    from pytorch_distributedtraining_tpu.observe.slo import RequestLedger

    probe = RequestLedger()
    probe.begin("probe")
    n = 2000
    t0 = time.perf_counter()
    t = t0
    for _ in range(n):
        t2 = time.perf_counter()
        probe.add_phase(
            "probe", "decode", t, t2,
            active_slots=1, share=1.0, padding_fraction=0.0,
        )
        t = t2
    per_op = (time.perf_counter() - t0) / n
    # the per-tick rolling-gauge store is a 4-key dict update, priced at
    # its own (much cheaper) rate rather than the add_phase rate
    g: dict = {}
    t0 = time.perf_counter()
    for i in range(n):
        g.update({
            "serve_queue_depth": float(i), "serve_slot_occupancy": 0.5,
            "serve_kv_pages_free": 1.0, "serve_slo_burn_rate": 0.0,
        })
    per_gauge = (time.perf_counter() - t0) / n
    n_intervals = sum(len(r["intervals"]) for r in eng.ledger.completed)
    cost = per_op * n_intervals + per_gauge * eng._tick
    return cost / wall_s if wall_s else 0.0


def _chaos(cfg, params, knobs):
    """Fault-site drill: shed one request at admission, stall one reader."""
    import numpy as np

    from pytorch_distributedtraining_tpu.resilience.faults import (
        FaultPlan, install_plan,
    )
    from pytorch_distributedtraining_tpu.serve.engine import ServeEngine
    from pytorch_distributedtraining_tpu.serve.scheduler import Request

    rng = np.random.default_rng(7)
    reqs = [
        Request(i, rng.integers(0, 64, size=6).astype("int32"), 3,
                arrival_s=0.0)
        for i in range(4)
    ]
    install_plan(FaultPlan.from_json([
        {"site": "serve.admit", "action": "raise", "at": 2, "times": 1},
        {"site": "serve.client", "action": "sleep", "arg": 0.02,
         "at": 1, "times": 1},
    ]))
    try:
        eng = ServeEngine(cfg, params, **knobs)
        delivered = eng.run(reqs, realtime=False)
        m = eng.metrics()
    finally:
        install_plan(None)
    # lifecycle completeness under fault: every submitted request's
    # record closed (shed requests terminally), stall billed as stall
    completed = eng.ledger.completed
    outcomes = sorted(r["outcome"] for r in completed)
    return {
        "submitted": len(reqs),
        "delivered": len(delivered),
        "dropped_at_admit": m["dropped_at_admit"],
        "slow_reader_stall_s": round(m["slow_reader_stall_s"], 4),
        "engine_survived": True,
        "lifecycles_closed": (
            len(completed) == len(reqs) and not eng.ledger._open
        ),
        "lifecycle_outcomes": outcomes,
        "stall_billed_s": round(sum(
            r["phases"].get("stall", 0.0) for r in completed
        ), 4),
    }


def run_serve_bench(*, realtime: bool = True) -> dict:
    """In-process bench body (importable — the fast test path)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.analyze.registry import (
        AnalysisContext, run_rules,
    )
    from pytorch_distributedtraining_tpu.models.gpt2 import GPT2, GPT2Config
    from pytorch_distributedtraining_tpu.observe import trace as telemetry
    from pytorch_distributedtraining_tpu.observe.goodput import GoodputLedger
    from pytorch_distributedtraining_tpu.serve import serve_knobs_from_env

    telemetry.enable()
    if CPU_SELF_TEST:
        # n_embd=64 keeps the model tiny while making the quantized-KV
        # residency ratio representative: at head_dim*n_head < 64 the
        # per-position f32 scale dominates and the >=1.8x gain bar is
        # unreachable regardless of format quality
        cfg = GPT2Config(
            vocab_size=64, n_positions=96, n_embd=64, n_layer=2, n_head=2,
        )
    else:  # GPT-2 125M, bf16 — the BASELINE ladder's transformer
        cfg = GPT2Config(dtype=jnp.bfloat16)
    train_model = GPT2(cfg, decode=False)
    params = train_model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    knobs = serve_knobs_from_env()
    if CPU_SELF_TEST:
        knobs.update(n_slots=3, page_size=8, max_len=48,
                     prefill_chunk=16, prefill_buckets=(8, 16))
    # fast-path knobs go ONLY to their own arms: the vanilla arms run
    # with spec/quantization off so the A/B comparison stays honest
    spec_k = knobs.pop("spec_k", 0) or 4
    kv_wire = knobs.pop("kv_wire", None) or "int8_block"
    rng = np.random.default_rng(0)
    trace_reqs = build_trace(
        rng, N_REQUESTS,
        mean_gap_s=GAP_MS / 1e3,
        prompt_lens=(4, 7, 12, 20),
        max_new_lo=4, max_new_hi=10,
    )

    t_bench0 = time.perf_counter()
    # throwaway mini-arm: absorb process-wide one-time costs (dtype
    # conversion jits, first host<->device transfers) that would
    # otherwise all be billed to whichever measured arm runs first
    _arm(cfg, params, trace_reqs[:3], "continuous", knobs, False)
    continuous, c_eng = _arm(
        cfg, params, trace_reqs, "continuous", knobs, realtime
    )
    static, _ = _arm(cfg, params, trace_reqs, "static", knobs, realtime)
    spec, s_eng = _arm(
        cfg, params, trace_reqs, "continuous",
        dict(knobs, spec_k=spec_k), realtime,
    )
    kvq, q_eng = _arm(
        cfg, params, trace_reqs, "continuous",
        dict(knobs, kv_wire=kv_wire), realtime,
    )
    # greedy decode is deterministic: the speculative arm must bank the
    # EXACT tokens the vanilla arm did, request by request
    base_toks = _tokens_by_rid(c_eng)
    spec_token_identical = _token_agreement(base_toks, _tokens_by_rid(s_eng)) == 1.0
    # quantized residency gate: block-scaled rounding may flip an argmax
    # in principle, so the gate is near-unanimous token agreement with
    # the dense arm (the strict paged==dense tolerance matrix lives in
    # tests/test_serve_spec.py)
    kv_agreement = _token_agreement(base_toks, _tokens_by_rid(q_eng))
    kv_gate_green = kv_agreement >= 0.95
    chaos = _chaos(cfg, params, knobs)
    overhead = _ledger_overhead_fraction(c_eng, continuous["wall_s"])
    serve_trace_path = c_eng.export_serve_trace()

    # graftcheck runtime plane over the live process: the recompile rule
    # reads serve.engine.runtime_stats, the burn rule reads
    # observe.slo.runtime_stats; ERROR findings fail the record
    report = run_rules(
        AnalysisContext(platform=jax.default_backend()),
        planes=("runtime",),
    )
    findings = [
        {"rule": f.rule, "severity": f.severity.name, "message": f.message}
        for f in report.findings
    ]
    serve_findings = [
        f for f in findings
        if f["rule"] == "serve-recompile-under-load"
        or (f["rule"] == "serve-slo-burn" and f["severity"] == "ERROR")
        or (f["rule"] == "serve-spec-regress" and f["severity"] == "ERROR")
    ]

    ledger = GoodputLedger.from_tracer(
        t0=t_bench0, t1=time.perf_counter()
    )
    beats = bool(
        continuous["throughput_tok_s"] and static["throughput_tok_s"]
        and continuous["throughput_tok_s"] > static["throughput_tok_s"]
        and continuous["p99_latency_s"] <= static["p99_latency_s"]
    )
    return {
        "metric": "serve_slo",
        "unit": "summary",
        "requests": N_REQUESTS,
        "mean_gap_ms": GAP_MS,
        "continuous": continuous,
        "static": static,
        "spec": spec,
        "kvq": kvq,
        "continuous_beats_static": beats,
        # decode fast-path headlines
        "spec_k": spec["spec_k"],
        "accept_rate": spec["accept_rate"],
        "decode_tokens_per_sec_spec": spec["decode_tokens_per_sec"],
        "decode_tokens_per_sec_vanilla": continuous["decode_tokens_per_sec"],
        "spec_token_identical": spec_token_identical,
        "kv_wire": kvq["kv_wire"],
        "kv_bytes_per_slot": kvq["kv_bytes_per_slot"],
        "slots_per_hbm_gain": kvq["slots_per_hbm_gain"],
        "kv_token_agreement": round(kv_agreement, 4),
        "kv_gate_green": kv_gate_green,
        "steady_recompiles": continuous["steady_recompiles"],
        "steady_recompiles_spec": spec["steady_recompiles"],
        "steady_recompiles_kvq": kvq["steady_recompiles"],
        "slo_burn_rate": continuous["slo"]["burn_rate"],
        "tail_attribution": continuous["tail_attribution"],
        "telemetry_overhead_fraction": round(overhead, 6),
        "serve_trace": serve_trace_path,
        "graftcheck_clean": not serve_findings,
        "graftcheck_findings": findings,
        "chaos": chaos,
        "goodput_fraction": ledger.goodput_fraction(),
        "time_breakdown": ledger.time_breakdown(),
    }


def main() -> None:
    if CPU_SELF_TEST:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    record = run_serve_bench()
    assert record["steady_recompiles"] == 0, (
        "serving engine recompiled during the steady-state window: "
        f"{record['graftcheck_findings']}"
    )
    assert record["steady_recompiles_spec"] == 0, (
        "speculative arm recompiled in steady state — the fast path's "
        "one extra program must be warmed before mark_steady: "
        f"{record['graftcheck_findings']}"
    )
    assert record["steady_recompiles_kvq"] == 0, (
        "quantized-KV arm recompiled in steady state: "
        f"{record['graftcheck_findings']}"
    )
    assert record["graftcheck_clean"], record["graftcheck_findings"]
    # the fast-path claims: spec must be a pure speedup (identical
    # tokens, more of them per decode second) and quantized residency
    # must actually buy slots per HBM byte without breaking tokens
    assert record["spec_token_identical"], (
        "speculative arm diverged from vanilla greedy decode — the "
        "accept rule must make accepted tokens exactly the greedy ones"
    )
    assert (
        record["decode_tokens_per_sec_spec"]
        > record["decode_tokens_per_sec_vanilla"]
    ), (
        f"speculative decode did not beat vanilla: "
        f"{record['decode_tokens_per_sec_spec']} <= "
        f"{record['decode_tokens_per_sec_vanilla']} tok/s "
        f"(accept_rate={record['accept_rate']})"
    )
    assert record["slots_per_hbm_gain"] >= 1.8, (
        f"quantized KV residency gain {record['slots_per_hbm_gain']}x "
        "is below the 1.8x bar"
    )
    assert record["kv_gate_green"], (
        f"quantized-KV token agreement {record['kv_token_agreement']} "
        "below gate — residency format is changing what gets decoded"
    )
    # the tail attribution is the point of the lifecycle plumbing: an
    # empty one means no request completed its phase accounting
    assert record["tail_attribution"].get("dominant_phase"), (
        "p99 tail attribution is empty — lifecycle records missing"
    )
    assert record["slo_burn_rate"] is not None, "SLO tracker saw no requests"
    if record["telemetry_overhead_fraction"] > 0.01:
        print(
            "TELEMETRY OVERHEAD: lifecycle bookkeeping cost "
            f"{record['telemetry_overhead_fraction']:.2%} of the "
            "continuous arm's wall time (gate: 1%) — record withheld",
            flush=True,
        )
        raise SystemExit(9)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
