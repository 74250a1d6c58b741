"""Measurement-calibrated AOT auto-planner: offline config search.

Enumerates candidate configurations over mesh shapes (dp/fsdp/pp
factorizations of a target topology) x ZeRO policy x remat policy x
pp schedule/microbatch x wire format, ranks them by an analytic
step-time model — compute from goodput-style FLOPs tables, comm from
the steps' ``comm_cost``/``wire_cost`` hop conventions over a per-axis
bandwidth, pipeline ``bubble_fraction`` — each term corrected by the
per-model ratios in ``calibration.json`` (observe/opcost.calibrate)
when present, then walks the ranking AOT-probing each candidate on the
CPU backend: graftcheck static findings of error grade disqualify, and
so does a compiled-memory peak over the HBM budget. Only candidates
that PASSED both prunes are emitted as the ranked ``plan.json``::

    python -m pytorch_distributedtraining_tpu.analyze.plan \
        --model gpt2 --topology 2x4 --budget-gb 16 --top-k 3

    GRAFT_PLAN=plan.json python drivers/stoke_ddp.py ...   # apply

Everything before the probe runs jax-free on the host; the probe is
the same AOT ``jit.lower().compile()`` pass graftcheck uses, so a pod
layout is planned and vetted from a laptop. Exit codes: 0 a ranked
plan with >= 1 feasible candidate was emitted, 1 the search found no
feasible candidate, 2 usage/environment problems.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

from .plan import Plan, plan_doc, write_plan
from . import plan as plan_mod

POLICIES = ("ddp", "zero1", "zero2", "zero3")
REMATS = ("none", "full", "dots", "names", "offload")
WIRES = (None, "int8", "int8_block", "fp8_e4m3", "fp8_e5m2")
PP_SCHEDULES = ("gpipe", "1f1b", "interleaved")

DEFAULT_POLICIES = POLICIES
DEFAULT_REMATS = ("none", "full")
DEFAULT_WIRES = (None, "int8_block")
DEFAULT_SCHEDULES = ("gpipe", "1f1b")
DEFAULT_MICRO_FACTORS = (1, 2)  # pp_micro = factor * pp stages
DEFAULT_HIERS = (False, True)   # flat vs two-level grad sync

# fwd-recompute overhead of each remat policy on the compute term
REMAT_COMPUTE = {
    "none": 1.0, "names": 1.08, "dots": 1.12, "offload": 1.25,
    "full": 4.0 / 3.0,
}

# grad-hop payload shrink per wire format (block-scale overhead folded
# in; mirrors parallel/compressed.py's payload+scales accounting)
WIRE_FACTOR = {
    "int8": 0.25, "int8_block": 0.27, "fp8_e4m3": 0.27, "fp8_e5m2": 0.27,
}

# data-axis traffic per policy, in units of per-stage param bytes
# (same hop convention as TrainStep.comm_cost: reduce-scatter moves n,
# all-reduce 2n) plus the post-step param fan-out ZeRO pays:
#   ddp   grad all-reduce 2n
#   zero1 grad all-reduce 2n + updated-param all-gather n
#   zero2 grad reduce-scatter n + updated-param all-gather n
#   zero3 grad reduce-scatter n + fwd/bwd param all-gathers 2n
POLICY_GRAD_HOPS = {"ddp": 2, "zero1": 2, "zero2": 1, "zero3": 1}
POLICY_GATHER_HOPS = {"ddp": 0, "zero1": 1, "zero2": 1, "zero3": 2}

DEFAULT_AXIS_BW = 1.8e10  # bytes/s on the data-parallel hop (ICI-class)
DEFAULT_DCN_BW = 2.5e9  # bytes/s across slices when dp rides DCN (hier)
DEFAULT_PEAK_FLOPS = 100e9  # planning-host stand-in (goodput's cpu entry)

# memory-budget safety margin, same default as observe.memory.tune_batch_size
DEFAULT_SAFETY = 0.9

_TOPOLOGY = re.compile(r"^(\d+)x(\d+)$")


# -- model table ---------------------------------------------------------


def _gpt2_tiny_params(
    vocab: int = 256, n_pos: int = 64, d: int = 32, layers: int = 2,
    mlp_ratio: int = 4,
) -> int:
    """Analytic param count of models.gpt2.GPT2Config.tiny() (host-side
    twin of the real init — the planner never materializes params)."""
    per_layer = (
        4 * d                          # two layernorms
        + 3 * d * d + 3 * d            # qkv
        + d * d + d                    # attention out proj
        + d * mlp_ratio * d + mlp_ratio * d  # mlp in
        + mlp_ratio * d * d + d        # mlp out
    )
    return vocab * d + n_pos * d + layers * per_layer + 2 * d


MODELS: dict = {
    # TinyMLP (analyze/fixtures.py): Dense(8->32) + Dense(32->1)
    "mlp": {
        "param_count": 8 * 32 + 32 + 32 + 1,
        "seq": None,       # tokens per sample (None = 1)
        "default_batch": 16,
    },
    # GPT2Config.tiny(): vocab 256, 64 positions, d=32, 2 layers
    "gpt2": {
        "param_count": _gpt2_tiny_params(),
        "seq": 32,
        "default_batch": 16,
    },
}


def parse_topology(spec: str) -> int:
    """'2x4' -> 8 devices; a bare integer is accepted too."""
    s = str(spec).strip().lower()
    if s.isdigit() and int(s) > 0:
        return int(s)
    m = _TOPOLOGY.match(s)
    if m:
        n = int(m.group(1)) * int(m.group(2))
        if n > 0:
            return n
    raise ValueError(
        f"topology must be 'AxB' (e.g. 2x4) or a positive device "
        f"count, got {spec!r}"
    )


def topology_slices(spec) -> int:
    """Slice count of a topology spec: 'AxB' is A slices of B chips
    (the A dimension is the DCN hop), a bare device count is one slice.
    The cost model uses this to charge any data ring wider than one
    slice its DCN crossing — a flat fsdp=8 on 2x4 is NOT ICI-fast."""
    m = _TOPOLOGY.match(str(spec).strip().lower())
    return int(m.group(1)) if m else 1


def factorizations(n: int):
    """All (dp, fsdp, pp) triples with dp*fsdp*pp == n, dp-major order
    (pure data-parallel first, deepest pipeline last)."""
    out = []
    for pp in range(1, n + 1):
        if n % pp:
            continue
        rest = n // pp
        for fsdp in range(1, rest + 1):
            if rest % fsdp:
                continue
            out.append((rest // fsdp, fsdp, pp))
    out.sort(key=lambda t: (t[2], t[1]))
    return out


# -- enumeration + compatibility prune -----------------------------------


def _compat_prune(p: Plan) -> str | None:
    """Static compatibility rules — the search-space truths that need no
    compiler: returns a prune reason or None."""
    w = p.dp * p.fsdp
    if p.policy != "ddp" and w <= 1:
        return "compat:zero-needs-data-axis"
    if p.policy == "ddp" and p.fsdp > 1 and not p.hier:
        # DDP's twin already lives on the dp axis; the fsdp spelling of
        # the same layout would double-count the candidate. Under hier
        # the two axes are DIFFERENT links (dp=DCN, fsdp=ICI), so the
        # split is a distinct layout, not a respelling.
        return "compat:ddp-uses-dp-axis"
    if p.hier:
        if p.dp <= 1:
            return "compat:hier-needs-slices"  # no DCN axis to tier over
        if p.fsdp <= 1:
            # no within-slice axis to reduce-scatter on first — the
            # "two-level" form would degenerate to the flat ring
            return "compat:hier-needs-ici-axis"
        if p.pp > 1:
            return "compat:hier-pp"  # HierGradStep has no pipeline path
        if p.policy == "zero3":
            return "compat:hier-zero3"  # sharded params need gathers
    if p.pp > 1 and p.policy == "zero3":
        return "compat:pp-zero3"  # PipelineStep rejects sharded params
    if p.wire and p.policy == "zero3":
        return "compat:wire-zero3"  # CompressedGradStep needs full params
    if p.wire and p.pp > 1:
        return "compat:wire-pp"  # the quantized wire has no pipeline path
    if p.batch % w:
        return "compat:batch-divide"
    if p.pp > 1:
        shard_batch = p.batch // w
        if p.pp_micro < 1 or shard_batch % p.pp_micro or p.pp_micro > shard_batch:
            return "compat:microbatch-divide"
        if p.pp_schedule == "interleaved" and p.pp_micro % p.pp:
            return "compat:interleaved-micro"
    return None


def enumerate_candidates(
    model: str,
    topology: str,
    *,
    batch: int | None = None,
    policies=DEFAULT_POLICIES,
    remats=DEFAULT_REMATS,
    wires=DEFAULT_WIRES,
    schedules=DEFAULT_SCHEDULES,
    micro_factors=DEFAULT_MICRO_FACTORS,
    hiers=DEFAULT_HIERS,
) -> list:
    """The full candidate list for a topology, compat prunes stamped.

    Every point of the cross product is returned (pruned ones carry
    their reason) so the truth table is inspectable — nothing is
    silently dropped.
    """
    if model not in MODELS:
        raise ValueError(f"model must be one of {sorted(MODELS)}, got {model!r}")
    n = parse_topology(topology)
    batch = batch or MODELS[model]["default_batch"]
    out = []
    for dp, fsdp, pp in factorizations(n):
        if pp == 1:
            pipeline_combos = [("none", 0, 1)]
        else:
            pipeline_combos = []
            for sched in schedules:
                v = 2 if sched == "interleaved" else 1
                for k in micro_factors:
                    pipeline_combos.append((sched, k * pp, v))
        for policy in policies:
            for remat in remats:
                for wire in wires:
                    for sched, micro, v in pipeline_combos:
                        for hier in hiers:
                            p = Plan(
                                model=model, topology=str(topology),
                                dp=dp, fsdp=fsdp, pp=pp, policy=policy,
                                remat=remat, pp_schedule=sched,
                                pp_micro=micro, pp_v=v, wire=wire,
                                hier=hier, batch=batch,
                            )
                            reason = _compat_prune(p)
                            if reason:
                                p.prune_reason = reason
                                p.feasible = False
                            out.append(p)
    return out


# -- calibrated cost model -----------------------------------------------


def analytic_bubble(schedule: str, stages: int, micro: int, v: int = 1) -> float:
    """Idle fraction of the rank x tick grid — the host-side analytic
    twin of ``PipelineSchedule.bubble_fraction`` (parallel/pipeline.py):
    gpipe/1f1b fill+drain costs (S-1) ticks per phase; interleaving v
    virtual stages divides the bubble by keeping each rank busy v times
    per microbatch."""
    if stages <= 1:
        return 0.0
    m = max(1, micro)
    if schedule == "interleaved":
        return (stages - 1) / (m * max(1, v) + stages - 1)
    return (stages - 1) / (m + stages - 1)


def model_step_flops(model: str, batch: int) -> float:
    """Train-step FLOPs (fwd + bwd = 3x fwd), goodput-style 6*N*tokens."""
    spec = MODELS[model]
    tokens = batch * (spec["seq"] or 1)
    return 6.0 * spec["param_count"] * tokens


def _peak_flops() -> float:
    env = os.environ.get("GRAFT_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            raise SystemExit(f"error: GRAFT_PEAK_FLOPS must be a float, got {env!r}")
    return DEFAULT_PEAK_FLOPS


def _bw_for(axis_bw, axis: str, *, dcn: bool = False) -> float:
    """Resolve one axis' bytes/s from a scalar or a per-axis dict.

    A scalar (the legacy --axis-bw form) applies to every hop. A dict —
    calibration.json's ``meta.axis_bandwidth``, the measured form —
    looks up the axis; a missing axis falls back to the analytic
    constant for its link class (DCN for the dp hop of a hier plan,
    ICI otherwise), so hier ranking never silently treats an
    unmeasured DCN hop as ICI-fast.
    """
    if axis_bw is None:
        return DEFAULT_DCN_BW if dcn else DEFAULT_AXIS_BW
    if isinstance(axis_bw, dict):
        v = axis_bw.get(axis)
        if v:
            return float(v)
        return DEFAULT_DCN_BW if dcn else DEFAULT_AXIS_BW
    return float(axis_bw)


def _cal_ratio(calibration: dict | None, name: str) -> float:
    row = (calibration or {}).get(name) or {}
    ratio = row.get("ratio")
    if ratio is None or not ratio > 0:
        return 1.0
    return float(ratio)


def predict(
    plan: Plan,
    *,
    calibration: dict | None = None,
    axis_bw=DEFAULT_AXIS_BW,
    peak: float = DEFAULT_PEAK_FLOPS,
) -> float:
    """Fill ``plan.predicted`` with the calibrated step-time model and
    return total_s. Terms: compute (FLOPs / peak, x remat recompute,
    x the ``mfu_flops`` ratio), comm (policy hop bytes / axis
    bandwidth, grad hop x the ``wire`` ratio), bubble (analytic
    schedule bubble x the ``bubble`` ratio, divides the busy time).

    ``axis_bw`` is a scalar (one bytes/s for every hop) or a per-axis
    dict (calibration.json's measured ``meta.axis_bandwidth``). Hier
    plans split the comm term by link: the 1/fsdp-scattered grad hop
    at the dp (DCN) bandwidth, the within-slice reduce-scatter /
    all-gather at the fsdp (ICI) bandwidth — so a measured slow DCN
    ranks the two-level form above the flat ring it replaces.
    """
    cal = {
        "mfu_flops": _cal_ratio(calibration, "mfu_flops"),
        "wire": _cal_ratio(calibration, "wire"),
        "bubble": _cal_ratio(calibration, "bubble"),
    }
    flops = model_step_flops(plan.model, plan.batch) * REMAT_COMPUTE.get(
        plan.remat, 1.0
    )
    compute_s = flops / (peak * plan.devices) * cal["mfu_flops"]

    w = plan.dp * plan.fsdp
    stage_param_bytes = MODELS[plan.model]["param_count"] * 4.0 / plan.pp
    comm_bytes = 0.0
    dcn_bytes = 0.0
    comm_s = 0.0
    wire_f = (
        WIRE_FACTOR.get(plan.wire.partition(":")[0], 1.0) if plan.wire else 1.0
    )
    if plan.hier:
        # two-level: reduce-scatter over fsdp (ICI) first, so only a
        # 1/fsdp shard of the gradient crosses the slice boundary; the
        # wire format (when any) narrows ONLY that DCN hop
        frac_dp = (plan.dp - 1) / plan.dp
        frac_fsdp = (plan.fsdp - 1) / plan.fsdp
        dcn_bytes = (
            POLICY_GRAD_HOPS[plan.policy]
            * (stage_param_bytes / plan.fsdp)
            * frac_dp * wire_f * cal["wire"]
        )
        ici_bytes = 2.0 * stage_param_bytes * frac_fsdp  # RS + AG
        gather = POLICY_GATHER_HOPS[plan.policy] * stage_param_bytes * frac_fsdp
        comm_bytes = dcn_bytes + ici_bytes + gather
        comm_s = (
            dcn_bytes / _bw_for(axis_bw, "dp", dcn=True)
            + (ici_bytes + gather) / _bw_for(axis_bw, "fsdp")
        )
    elif w > 1:
        frac = (w - 1) / w
        grad = POLICY_GRAD_HOPS[plan.policy] * stage_param_bytes * frac
        grad *= wire_f
        gather = POLICY_GATHER_HOPS[plan.policy] * stage_param_bytes * frac
        comm_bytes = grad * cal["wire"] + gather
        # a flat ring over a joint data axis moves at its slowest link
        bw = min(
            _bw_for(axis_bw, ax)
            for ax, size in (("dp", plan.dp), ("fsdp", plan.fsdp))
            if size > 1
        )
        slices = topology_slices(plan.topology)
        if slices > 1 and w > plan.devices // slices:
            # wider than one slice: the flat ring drags its FULL payload
            # across the slice boundary — the hier twin's dcn_bytes
            # divides this by fsdp, which is the planner's whole case
            # for the hierarchy — and it moves at the DCN link's pace
            dcn_bytes = comm_bytes
            bw = min(bw, _bw_for(axis_bw, "dp", dcn=True))
        comm_s = comm_bytes / bw

    bubble = analytic_bubble(plan.pp_schedule, plan.pp, plan.pp_micro, plan.pp_v)
    bubble = min(0.95, bubble * cal["bubble"])
    total_s = (compute_s + comm_s) / (1.0 - bubble)
    plan.predicted = {
        "compute_s": compute_s,
        "comm_s": comm_s,
        "comm_bytes": comm_bytes,
        "dcn_bytes": dcn_bytes,
        "bubble_fraction": bubble,
        "total_s": total_s,
    }
    plan.calibration = cal
    return total_s


def rank_candidates(
    candidates,
    *,
    calibration: dict | None = None,
    axis_bw: float | None = None,
    peak: float | None = None,
) -> list:
    """Rank the un-pruned candidates by predicted total step time
    (stable: enumeration order — dp-major, ddp-first — breaks ties, so
    equal-cost layouts prefer the simplest spelling)."""
    if not axis_bw:  # None, 0, or an empty measured dict
        axis_bw = DEFAULT_AXIS_BW
    peak = peak or _peak_flops()
    alive = [p for p in candidates if p.prune_reason is None]
    for p in alive:
        predict(p, calibration=calibration, axis_bw=axis_bw, peak=peak)
    alive.sort(key=lambda p: p.predicted["total_s"])
    return alive


# -- AOT probe: build the real step, memory + static prune ---------------


def synth_batch(plan: Plan, batch: int):
    """Host numpy batch for one candidate (new arrays only — lets the
    batch-size tuner re-probe without rebuilding step/state)."""
    import numpy as np

    rng = np.random.default_rng(0)
    if plan.model == "gpt2":
        seq = MODELS["gpt2"]["seq"]
        if plan.pp > 1:
            # pipeline trunk twin feeds pre-embedded activations
            return {
                "x": rng.normal(size=(batch, seq, 32)).astype(np.float32),
            }
        tok = rng.integers(0, 256, size=(batch, seq + 1), dtype=np.int32)
        return {"x": tok[:, :-1], "y": tok[:, 1:]}
    if plan.pp > 1:
        return {
            "x": rng.normal(size=(batch, 8)).astype(np.float32),
            "y": rng.normal(size=(batch, 1)).astype(np.float32),
        }
    return (
        rng.normal(size=(batch, 8)).astype(np.float32),
        rng.normal(size=(batch, 1)).astype(np.float32),
    )


def build_step(plan: Plan, batch: int | None = None):
    """Materialize one candidate as a concrete (step, state, batch).

    Shared by the planner's AOT probe and the batch-size tuner's pre-built
    closure. Imports jax
    lazily — enumeration and ranking stay host-side.
    """
    import jax
    import jax.numpy as jnp

    from .. import optim
    from ..parallel import (
        DDP,
        ZeRO1,
        ZeRO2,
        ZeRO3,
        CompressedGradStep,
        HierGradStep,
        PipelineStep,
        TrainStep,
        create_train_state,
        pipeline_state_shardings,
        stack_stage_params,
    )
    from ..runtime.mesh import MeshSpec, make_hybrid_mesh, make_mesh

    b = batch or plan.batch
    spec = MeshSpec(dp=plan.dp, fsdp=plan.fsdp, pp=plan.pp)
    if len(jax.devices()) < spec.size:
        raise RuntimeError(
            f"candidate needs {spec.size} devices but the backend has "
            f"{len(jax.devices())}"
        )
    if plan.hier:
        # the dp axis is the DCN hop: build the slice-aware layout so
        # slice_axis(mesh) is registered and the step tiers its sync
        mesh = make_hybrid_mesh(
            MeshSpec(fsdp=plan.fsdp, pp=plan.pp),
            dcn_dp=plan.dp,
            devices=jax.devices()[: spec.size],
        )
    else:
        mesh = make_mesh(spec, devices=jax.devices()[: spec.size])
    pol_kw: dict = {"min_shard_size": 1}
    if plan.remat != "none":
        pol_kw["remat"] = plan.remat
    policy = {
        "ddp": DDP, "zero1": ZeRO1, "zero2": ZeRO2, "zero3": ZeRO3,
    }[plan.policy](**pol_kw)
    tx = optim.adamw(lr=1e-3)
    batch_arrays = synth_batch(plan, b)

    if plan.pp > 1:
        layers = plan.pp * plan.pp_v
        if plan.model == "gpt2":
            from ..models.gpt2 import Block, GPT2Config

            cfg = GPT2Config.tiny()
            blk = Block(cfg)
            width = cfg.n_embd
            x0 = jnp.zeros((1, MODELS["gpt2"]["seq"], width))
            block_fn = lambda p, x: Block(cfg).apply({"params": p}, x)  # noqa: E731
        else:
            width = 8
            x0 = None
            blk = None

            def block_fn(p, x):
                return jnp.tanh(x @ p["w"] + p["b"])

        def init_fn(rng_):
            if blk is not None:
                stacked = stack_stage_params([
                    blk.init(jax.random.fold_in(rng_, i), x0)["params"]
                    for i in range(layers)
                ])
            else:
                k1, k2 = jax.random.split(rng_)
                stacked = {
                    "w": jax.random.normal(k1, (layers, width, width)) * 0.3,
                    "b": jax.random.normal(k2, (layers, width)) * 0.1,
                }
            return {"h": stacked}, {}

        def embed_fn(other, mb, rng_):
            return mb["x"]

        def head_fn(other, y, mb, rng_):
            if plan.model == "gpt2":
                return jnp.mean(y**2)
            return jnp.mean((y @ jnp.ones((width, 1)) - mb["y"]) ** 2)

        state, sh = create_train_state(
            init_fn=init_fn, tx=tx, mesh=mesh, policy=policy
        )
        sh = pipeline_state_shardings(sh, state, mesh, "h")
        state = jax.device_put(state, sh)
        step = PipelineStep(
            block_fn, tx, mesh, policy,
            n_micro=plan.pp_micro, schedule=plan.pp_schedule, v=plan.pp_v,
            stages_key="h", embed_fn=embed_fn, head_fn=head_fn,
            state_shardings=sh, donate=False,
        )
        return step, state, batch_arrays

    if plan.model == "gpt2":
        import optax

        from ..models.gpt2 import GPT2, GPT2Config

        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        init_x = jnp.zeros((1, MODELS["gpt2"]["seq"]), jnp.int32)

        def loss_fn(params, bt, rng_, ms):
            logits = model.apply({"params": params}, bt["x"])
            return (
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, bt["y"]
                ).mean(),
                {},
            )
    else:
        from ..losses import mse_loss
        from .fixtures import TinyMLP

        model = TinyMLP()
        init_x = jnp.zeros((1, 8))

        def loss_fn(params, bt, rng_, ms):
            x, y = bt
            return mse_loss(model.apply({"params": params}, x), y), {}

    state, sh = create_train_state(
        init_fn=lambda r: (model.init(r, init_x)["params"], {}),
        tx=tx, mesh=mesh, policy=policy,
    )
    if plan.wire:
        # on a hybrid mesh CompressedGradStep is already the two-level
        # quantized form: f32 reduce-scatter on ICI, narrow dp hop
        step = CompressedGradStep(
            loss_fn, tx, mesh, policy, donate=False, wire=plan.wire
        )
    elif plan.hier:
        step = HierGradStep(loss_fn, tx, mesh, policy, donate=False)
    else:
        step = TrainStep(
            loss_fn, tx, mesh, policy, state_shardings=sh, donate=False
        )
    return step, state, batch_arrays


def make_aot_probe(batch: int | None = None):
    """The default probe: AOT-build the candidate, run graftcheck, read
    the compiled memory plan. Returns ``(peak_bytes, report, error)``
    — error is a string when the candidate cannot even build."""

    def probe(plan: Plan):
        try:
            step, state, batch_arrays = build_step(plan, batch)
            from .runner import analyze_step

            report = analyze_step(step, state, batch_arrays)
            ms = step.memory_analysis(state, batch_arrays)
            peak = None if ms is None else int(ms.peak_bytes)
            return peak, report, None
        except Exception as e:  # noqa: BLE001 — a bad candidate is a prune
            return None, None, f"{type(e).__name__}: {e}"

    return probe


def make_batch_tuner(budget_bytes, *, safety: float = DEFAULT_SAFETY, max_batch: int = 1024):
    """Batch-size tuner over a pre-built lower/compile closure: one
    ``build_step`` per candidate, then each probe only swaps batch
    arrays (observe.memory.tune_batch_size re-lowers nothing it has in
    its cache)."""
    from ..observe.memory import tune_batch_size

    caches: dict = {}

    def tuner(plan: Plan) -> int:
        step, state, _ = build_step(plan)

        def peak_fn(b: int):
            ms = step.memory_analysis(state, synth_batch(plan, b))
            return None if ms is None else ms.peak_bytes

        return tune_batch_size(
            peak_fn,
            budget_bytes=budget_bytes,
            start=plan.batch,
            max_batch=max_batch,
            safety=safety,
            cache=caches.setdefault(plan.key(), {}),
        )

    return tuner


# -- the search ----------------------------------------------------------


def search(
    model: str,
    topology: str,
    *,
    batch: int | None = None,
    budget_bytes: int | None = None,
    top_k: int = 3,
    probe=None,
    probe_limit: int = 32,
    tuner=None,
    calibration: dict | None = None,
    calibration_path: str | None = None,
    axis_bw=None,
    axis_bw_source: str | None = None,
    peak: float | None = None,
    safety: float = DEFAULT_SAFETY,
    policies=DEFAULT_POLICIES,
    remats=DEFAULT_REMATS,
    wires=DEFAULT_WIRES,
    schedules=DEFAULT_SCHEDULES,
    micro_factors=DEFAULT_MICRO_FACTORS,
    hiers=DEFAULT_HIERS,
) -> dict:
    """Enumerate -> rank -> probe down the ranking until ``top_k``
    candidates survive the memory + static prune. Returns the plan doc.

    ``probe(plan) -> (peak_bytes, report, error)`` defaults to the real
    AOT probe; pass ``probe=False`` to skip probing (rank-only mode —
    the doc's meta says so; nothing in it has passed a prune).
    Candidates past ``probe_limit`` are pruned out loud
    (``probe-budget``), never silently ranked.
    """
    candidates = enumerate_candidates(
        model, topology, batch=batch, policies=policies, remats=remats,
        wires=wires, schedules=schedules, micro_factors=micro_factors,
        hiers=hiers,
    )
    ranked = rank_candidates(
        candidates, calibration=calibration, axis_bw=axis_bw, peak=peak
    )
    pruned = [p for p in candidates if p.prune_reason is not None]
    reranked_from_stale = bool(plan_mod.runtime_stats.get("stale"))

    if probe is None:
        probe = make_aot_probe(batch)

    survivors: list = []
    probes_used = 0
    below_cut = 0
    for p in ranked:
        if len(survivors) >= top_k:
            below_cut += 1
            continue
        if probe is False:
            survivors.append(p)
            continue
        if probes_used >= probe_limit:
            p.feasible = False
            p.prune_reason = f"probe-budget:limit={probe_limit}"
            pruned.append(p)
            continue
        probes_used += 1
        peak_b, report, err = probe(p)
        if err is not None:
            p.feasible = False
            p.prune_reason = f"build:{err}"
            pruned.append(p)
            continue
        if report is not None and report.errors:
            rules = sorted({f.rule for f in report.errors})
            p.feasible = False
            p.prune_reason = "static:" + ",".join(rules)
            pruned.append(p)
            continue
        if peak_b is not None:
            p.peak_bytes = int(peak_b)
            if budget_bytes is not None and peak_b > budget_bytes * safety:
                p.feasible = False
                p.prune_reason = (
                    f"memory:peak={int(peak_b)}B>"
                    f"budget*safety={int(budget_bytes * safety)}B"
                )
                pruned.append(p)
                continue
        if tuner is not None:
            try:
                p.max_batch = int(tuner(p))
            except ValueError as e:
                # observe.memory.NoMemoryBudget — the strict never-guess
                # refusal becomes a prune reason, not a planner crash
                if type(e).__name__ != "NoMemoryBudget":
                    raise
                p.feasible = False
                p.prune_reason = f"no-hbm-budget:{e}"
                pruned.append(p)
                continue
        p.feasible = True
        survivors.append(p)

    meta = {
        "model": model,
        "topology": str(topology),
        "devices": parse_topology(topology),
        "batch": batch or MODELS[model]["default_batch"],
        "budget_bytes": budget_bytes,
        "safety": safety,
        "top_k": top_k,
        "axis_bandwidth": axis_bw if axis_bw else DEFAULT_AXIS_BW,
        "axis_bw_source": axis_bw_source
        or ("given" if axis_bw else "analytic"),
        "peak_flops": peak or _peak_flops(),
        "calibration_path": calibration_path,
        "calibration": {
            name: (row or {}).get("ratio")
            for name, row in (calibration or {}).items()
        },
        "probed": probe is not False,
        "probes_used": probes_used,
        "considered": len(candidates),
        "below_cut_unprobed": below_cut,
        "reranked_from_stale": reranked_from_stale,
        "created": time.time(),
    }
    return plan_doc(survivors, pruned, meta)


# -- CLI -----------------------------------------------------------------


def _load_calibration_doc(path: str) -> dict:
    """Stdlib twin of observe.opcost.load_calibration (that package
    import would pull jax; the planner stays host-side). Returns the
    FULL doc — ``calibration`` ratios plus ``meta`` (which may carry a
    measured ``axis_bandwidth`` table)."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) or not isinstance(doc.get("calibration"), dict):
        raise ValueError(f"{path} is not a calibration.json (no 'calibration' table)")
    return doc


def _load_calibration(path: str) -> dict:
    return _load_calibration_doc(path)["calibration"]


def _csv(spec: str, allowed, what: str):
    out = []
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        val = None if tok in ("off", "none") and what == "wire" else tok
        base = (val or "").partition(":")[0] if what == "wire" else val
        if val is not None and base not in allowed:
            raise SystemExit(
                f"error: unknown {what} {tok!r}; expected one of "
                f"{sorted(x for x in allowed if x)}"
            )
        out.append(val)
    if not out:
        raise SystemExit(f"error: empty {what} list")
    return tuple(out)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m pytorch_distributedtraining_tpu.analyze.plan",
        description=(
            "auto-planner: enumerate mesh x policy x remat x pp x wire "
            "candidates for a topology, prune by AOT memory + graftcheck, "
            "rank by calibrated cost models, emit plan.json"
        ),
    )
    p.add_argument("--model", default="mlp", choices=sorted(MODELS))
    p.add_argument(
        "--topology", required=True,
        help="target topology as AxB (e.g. 2x4) or a device count",
    )
    p.add_argument("--batch", type=int, default=0, help="global batch (0 = model default)")
    p.add_argument(
        "--budget-gb", type=float, default=0.0,
        help="per-device HBM budget in GiB for the memory prune "
        "(default: this host's device_hbm_budget fallback)",
    )
    p.add_argument("--top-k", type=int, default=3, help="ranked survivors to emit")
    p.add_argument("--out", default="plan.json", help="output path (default plan.json)")
    p.add_argument(
        "--calibration", default=os.environ.get("GRAFT_CALIBRATION"),
        help="calibration.json whose per-model ratios correct the cost "
        "terms (default: $GRAFT_CALIBRATION)",
    )
    p.add_argument("--policies", default=",".join(DEFAULT_POLICIES))
    p.add_argument("--remats", default=",".join(DEFAULT_REMATS))
    p.add_argument(
        "--wires", default=",".join(w or "off" for w in DEFAULT_WIRES),
        help="wire formats to consider; 'off' = the f32 wire",
    )
    p.add_argument("--schedules", default=",".join(DEFAULT_SCHEDULES))
    p.add_argument(
        "--micro", default=",".join(str(k) for k in DEFAULT_MICRO_FACTORS),
        help="pp_micro = factor * stages, per factor in this list",
    )
    p.add_argument(
        "--probe-limit", type=int, default=32,
        help="max AOT compiles before remaining candidates prune as "
        "probe-budget (default 32)",
    )
    p.add_argument(
        "--no-probe", action="store_true",
        help="rank-only: skip the AOT memory/static prune (plan.json's "
        "meta records that nothing was vetted)",
    )
    p.add_argument(
        "--tune-batch", action="store_true",
        help="tune_batch_size per survivor over the pre-built compile "
        "closure; strict refusal (no budget) prunes, never raises",
    )
    p.add_argument(
        "--axis-bw", type=float, default=0.0,
        help="bytes/s per data hop (0 = auto: the calibration.json's "
        "measured meta.axis_bandwidth when present, else analytic)",
    )
    p.add_argument("--peak-flops", type=float, default=0.0, help="per-device peak FLOP/s")
    return p


def _ensure_devices(n: int) -> None:
    """Ask the CPU backend for >= n devices; must run before jax init."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        n = parse_topology(args.topology)
        policies = _csv(args.policies, POLICIES, "policy")
        remats = _csv(args.remats, REMATS, "remat")
        wires = _csv(args.wires, set(WIRE_FACTOR), "wire")
        schedules = _csv(args.schedules, PP_SCHEDULES, "schedule")
        micro_factors = tuple(
            int(t) for t in args.micro.split(",") if t.strip()
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except SystemExit as e:
        if isinstance(e.code, str):
            print(e.code, file=sys.stderr)
            return 2
        raise

    calibration = None
    cal_doc = None
    if args.calibration:
        try:
            cal_doc = _load_calibration_doc(args.calibration)
            calibration = cal_doc["calibration"]
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"error: --calibration: {e}", file=sys.stderr)
            return 2

    # per-axis bandwidth precedence: an explicit --axis-bw wins; else the
    # calibration run's MEASURED meta.axis_bandwidth; else the analytic
    # constants. Logged so a plan is never silently ranked on the wrong
    # bandwidth source.
    axis_bw = args.axis_bw or None
    axis_bw_source = "flag:--axis-bw" if axis_bw else None
    if axis_bw is None and cal_doc is not None:
        meta_bw = (cal_doc.get("meta") or {}).get("axis_bandwidth")
        if isinstance(meta_bw, dict):
            measured = {
                str(ax): float(v) for ax, v in meta_bw.items() if v
            }
            if measured:
                axis_bw = measured
                axis_bw_source = f"measured:{args.calibration}"
    if axis_bw_source is None:
        axis_bw_source = "analytic:defaults"
    print(f"axis bandwidth source: {axis_bw_source}")

    budget_bytes = (
        int(args.budget_gb * (1 << 30)) if args.budget_gb > 0 else None
    )
    probe = False if args.no_probe else None
    tuner = None
    if not args.no_probe:
        _ensure_devices(n)
        from ..runtime import force_platform

        force_platform("cpu")  # planning is always an AOT CPU pass
        import jax

        if len(jax.devices()) < n:
            print(
                f"error: topology {args.topology!r} needs {n} devices but "
                f"the CPU backend initialized with {len(jax.devices())} "
                "(jax was already imported before the CLI could request "
                "more)",
                file=sys.stderr,
            )
            return 2
        if budget_bytes is None:
            from ..observe.memory import device_hbm_budget

            budget_bytes = device_hbm_budget()
        if args.tune_batch:
            tuner = make_batch_tuner(budget_bytes)

    if plan_mod.runtime_stats.get("stale"):
        print(
            "active plan is stale "
            f"({plan_mod.runtime_stats.get('stale_reason')}); re-ranking "
            "against the supplied calibration"
        )

    doc = search(
        args.model, args.topology,
        batch=args.batch or None,
        budget_bytes=budget_bytes,
        top_k=args.top_k,
        probe=probe,
        probe_limit=args.probe_limit,
        tuner=tuner,
        calibration=calibration,
        calibration_path=args.calibration,
        axis_bw=axis_bw,
        axis_bw_source=axis_bw_source,
        peak=args.peak_flops or None,
        policies=policies,
        remats=remats,
        wires=wires,
        schedules=schedules,
        micro_factors=micro_factors,
    )
    write_plan(args.out, doc)

    meta = doc["meta"]
    print(
        f"planned {args.model} on {args.topology}: considered "
        f"{meta['considered']} candidates, probed {meta['probes_used']}, "
        f"{len(doc['ranked'])} survived -> {args.out}"
    )
    for row in doc["ranked"]:
        p = Plan.from_dict(row)
        peak_s = f" peak={p.peak_bytes}B" if p.peak_bytes is not None else ""
        tuned = f" max_batch={p.max_batch}" if p.max_batch else ""
        print(
            f"  #{p.rank} {p.describe()} "
            f"total={p.predicted['total_s']:.3e}s{peak_s}{tuned}"
        )
    reasons: dict = {}
    for row in doc["pruned"]:
        key = (row.get("prune_reason") or "?").split(":")[0]
        reasons[key] = reasons.get(key, 0) + 1
    if reasons:
        print(
            "  pruned: "
            + ", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))
        )
    return 0 if doc["ranked"] else 1


if __name__ == "__main__":
    sys.exit(main())
