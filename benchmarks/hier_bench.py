"""Hierarchical-collective microbenchmark: flat vs two-level grad sync.

Runs the SAME tiny-MLP train step through two arms on one hybrid CPU
mesh (2 slices x 4-wide ICI, ``make_hybrid_mesh``):

- ``flat``: TrainStep's topology-blind joint-axis all-reduce — the full
  gradient crosses the slice (DCN) boundary from every device.
- ``hier``: HierGradStep's two-level form — reduce-scatter within-slice,
  all-reduce the 1/ici shard across slices, all-gather back.

Per arm it reports the analytic per-device DCN bytes
(``HierGradStep.dcn_cost`` — the flat arm reads the ``flat_twin``
column) next to measured step time and final loss; the two arms must
land the same loss (same data, same init), which is the equal-loss half
of the acceptance bar — the byte columns are the other half. On CPU the
"DCN" hop is a memcpy, so step-time deltas only bound the bucketing
overhead; the bandwidth win the byte columns promise needs a real
multi-slice pod.

Then the slow-slice drill: a ``comm.dcn`` FaultPlan sleep stretches
every sync from a chosen step on (a degraded DCN link in miniature),
the measured bytes/s stream feeds a :class:`SliceDegradeController`,
the straggler signal names slice 1, and the controller's decision
quarantines that slice's hosts (a real file-backed MembershipStore) and
re-forms the mesh over the survivor via :func:`exclude_slice` — the
drill's ``time_to_degrade_s`` (first degraded sample -> decision) and
post-degrade steps (zero hung ranks) land in the summary record.

Prints one JSON line per arm plus a final summary record
(``metric: "hier"``, headline ``dcn_bytes`` — lower is better) for
the regression sentry.
``GRAFT_HIER_BENCH_STEPS`` / ``_BATCH`` / ``_DIM`` / ``_FAULT_S``
resize the run.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

# an 8-way CPU mesh so the collectives are real (must precede jax import)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

STEPS = int(os.environ.get("GRAFT_HIER_BENCH_STEPS", "20"))
BATCH = int(os.environ.get("GRAFT_HIER_BENCH_BATCH", "32"))
DIM = int(os.environ.get("GRAFT_HIER_BENCH_DIM", "256"))
# injected per-sync DCN stall for the degrade drill (seconds)
FAULT_S = float(os.environ.get("GRAFT_HIER_BENCH_FAULT_S", "0.05"))

N_SLICES = 2
ICI = 4  # devices per slice


def main() -> None:
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.parallel import (
        DDP,
        HierGradStep,
        SliceDegradeController,
        TrainStep,
        create_train_state,
        exclude_slice,
    )
    from pytorch_distributedtraining_tpu.parallel import hierarchy as hier_mod
    from pytorch_distributedtraining_tpu.resilience.faults import (
        FaultPlan,
        install_plan,
    )
    from pytorch_distributedtraining_tpu.runtime.membership import (
        MembershipStore,
    )
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec,
        make_hybrid_mesh,
        slice_axis,
    )

    n_dev = N_SLICES * ICI
    if jax.device_count() < n_dev:
        raise SystemExit(
            f"hier_bench needs {n_dev} devices, have {jax.device_count()}"
        )
    mesh = make_hybrid_mesh(
        MeshSpec(fsdp=ICI), dcn_dp=N_SLICES, devices=jax.devices()[:n_dev]
    )
    assert slice_axis(mesh) == "dp"
    rng = np.random.default_rng(0)
    x_host = rng.normal(size=(BATCH, DIM)).astype(np.float32)
    y_host = rng.normal(size=(BATCH, 1)).astype(np.float32)

    def init_fn(r):
        k1, k2, k3 = jax.random.split(r, 3)
        return {
            "w1": jax.random.normal(k1, (DIM, 2 * DIM)) * 0.05,
            "b1": jnp.zeros((2 * DIM,)),
            "w2": jax.random.normal(k2, (2 * DIM, DIM)) * 0.05,
            "b2": jnp.zeros((DIM,)),
            "out": jax.random.normal(k3, (DIM, 1)) * 0.05,
        }, {}

    def loss_fn(params, batch, rng_, ms):
        xb, yb = batch
        h = jnp.tanh(xb @ params["w1"] + params["b1"])
        h = jnp.tanh(h @ params["w2"] + params["b2"])
        return jnp.mean((h @ params["out"] - yb) ** 2), {}

    tx = optim.adamw(lr=1e-3)
    batch = (jnp.asarray(x_host), jnp.asarray(y_host))

    def run(arm: str) -> dict:
        policy = DDP()
        state, sh = create_train_state(
            init_fn=init_fn, tx=tx, mesh=mesh, policy=policy
        )
        if arm == "flat":
            step = TrainStep(
                loss_fn, tx, mesh, policy, state_shardings=sh,
                extra_metrics=False,
            )
            # the flat twin's DCN accounting rides the hier cost surface
            cost = HierGradStep(loss_fn, tx, mesh, policy).dcn_cost(
                state.params
            )
            dcn_bytes = cost["dcn_bytes_flat_twin"]
        else:
            step = HierGradStep(loss_fn, tx, mesh, policy)
            cost = step.dcn_cost(state.params)
            dcn_bytes = cost["dcn_bytes"]
        with mesh:
            state, metrics = step(state, batch)  # compile
            jax.block_until_ready(metrics["loss"])
            t0 = time.perf_counter()
            for _ in range(STEPS):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
        row = {
            "arm": arm,
            "step_ms": round(1e3 * dt / STEPS, 3),
            "dcn_bytes": int(dcn_bytes),
            "dcn_bytes_flat_twin": int(cost["dcn_bytes_flat_twin"]),
            "ici_size": cost["ici_size"],
            "final_loss": round(float(metrics["loss"]), 6),
        }
        print(json.dumps(row), flush=True)
        return row

    flat_row = run("flat")
    hier_row = run("hier")

    # -- slow-slice degrade drill -----------------------------------------
    # stall every DCN sync from step 3 on; the bytes/s stream collapses,
    # the controller arms, the straggler signal names slice 1, the
    # decision quarantines its hosts and the mesh re-forms over slice 0
    install_plan(FaultPlan.from_json([
        {"site": "comm.dcn", "action": "sleep", "arg": FAULT_S,
         "at": 3, "times": 0},
    ]))
    hosts_by_slice = {
        s: [f"host-s{s}"] for s in range(N_SLICES)
    }
    store = MembershipStore(
        tempfile.mkdtemp(prefix="hier_bench_membership_")
    )
    ctl = SliceDegradeController(
        N_SLICES, store=store, hosts_by_slice=hosts_by_slice,
    )
    policy = DDP()
    state, _sh = create_train_state(
        init_fn=init_fn, tx=tx, mesh=mesh, policy=policy
    )
    step = HierGradStep(loss_fn, tx, mesh, policy)
    dcn_bytes = step.dcn_cost(state.params)["dcn_bytes"]
    decision = None
    drill_steps = 0
    with mesh:
        for i in range(4 * STEPS):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            drill_steps += 1
            sync_s = max(1e-9, time.perf_counter() - t0)
            armed = ctl.note_axis_bandwidth(dcn_bytes / sync_s)
            if armed:
                # the straggler monitor localizes blame: ranks of slice 1
                # report the stretched sync
                ctl.note_straggler(rank=ICI, ranks_per_slice=ICI)
            decision = ctl.decide()
            if decision is not None:
                break
    install_plan(None)
    if decision is None:
        raise SystemExit(
            "degrade drill never converged: the controller saw "
            f"{drill_steps} stalled syncs without a decision"
        )
    survivor = exclude_slice(mesh, decision.excluded_slice)
    # one surviving slice: every link is ICI again, the flat sync is the
    # correct degraded form (HierGradStep refuses single-slice meshes)
    post_state, post_sh = create_train_state(
        init_fn=init_fn, tx=tx, mesh=survivor, policy=policy
    )
    post = TrainStep(
        loss_fn, tx, survivor, policy, state_shardings=post_sh,
        extra_metrics=False,
    )
    with survivor:
        for _ in range(3):
            post_state, post_metrics = post(post_state, batch)
        jax.block_until_ready(post_metrics["loss"])
    drill = {
        "arm": "degrade_drill",
        "steps_to_decision": drill_steps,
        "time_to_degrade_s": decision.time_to_degrade_s,
        "excluded_slice": decision.excluded_slice,
        "reason": decision.reason,
        "quarantined_hosts": list(decision.quarantined_hosts),
        "survivor_devices": int(np.asarray(survivor.devices).size),
        "post_degrade_loss": round(float(post_metrics["loss"]), 6),
    }
    print(json.dumps(drill), flush=True)

    print(json.dumps({
        "summary": "hier_bench",
        "metric": "hier",
        "hier": True,
        "devices": n_dev,
        "slices": N_SLICES,
        "ici_size": ICI,
        "steps": STEPS,
        "dcn_bytes": hier_row["dcn_bytes"],
        "dcn_bytes_flat_twin": flat_row["dcn_bytes"],
        "dcn_reduction": round(
            flat_row["dcn_bytes"] / max(hier_row["dcn_bytes"], 1), 3
        ),
        "equal_loss": abs(
            flat_row["final_loss"] - hier_row["final_loss"]
        ) < 1e-4,
        "flat_step_ms": flat_row["step_ms"],
        "hier_step_ms": hier_row["step_ms"],
        "time_to_degrade_s": decision.time_to_degrade_s,
        "degrade_reason": decision.reason,
        "quarantined_hosts": list(decision.quarantined_hosts),
        "bucket_plan": hier_mod.runtime_stats.get("hier"),
        "platform": jax.devices()[0].platform,
    }), flush=True)


if __name__ == "__main__":
    main()
