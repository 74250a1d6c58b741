"""TPU-native port of the reference's minimal ZeRO-2 driver.

Mirrors `/root/reference/Fairscale-DDP.py` structure-for-structure: process
bootstrap → dataset/split/samplers/loaders → probe batch → Net + MSE →
OSS+ShardedDDP optimizer/model wrap → epoch/iteration loop printing loss
every 25 iterations → teardown. TPU-native differences:

- ``mp.spawn`` over 4 gloo ranks (`:125-133`) becomes one SPMD process
  driving every device on the mesh (multi-host runs launch one process per
  host; `runtime.initialize` is the `init_process_group` twin, `:27`);
- the OSS optimizer + ShardedDDP wrapper (`:86-89`) becomes the ZeRO2
  sharding policy on a compiled TrainStep — same reduce-to-owner +
  sharded-update semantics, zero wrapper classes;
- reference bugs fixed, not ported: ``num_replicas`` hardcoded to 4
  (`:47,53`), sampler ``set_epoch`` never called, computed rank ignored.

Run: ``python drivers/fairscale_ddp.py [--synthetic] [--epochs N]``
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributedtraining_tpu import optim, runtime
from pytorch_distributedtraining_tpu.data import (
    CustomDataset,
    DataLoader,
    DistributedSampler,
    SyntheticSRDataset,
    random_split,
)
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import (
    CompressedGradStep,
    ZeRO2,
    TrainStep,
    create_train_state,
    wire_format,
)
from pytorch_distributedtraining_tpu.runtime.mesh import (
    MeshSpec, batch_spec, make_hybrid_mesh, make_mesh,
)

# reference constants (Fairscale-DDP.py:57,116,118)
BATCH_SIZE = 40
WORLD_SIZE = 4  # informational under SPMD: actual width = device count
EPOCHS = 2

# reference data locations (Fairscale-DDP.py:32-33)
INPUT_PATH = "/opt/hubshare/vectorly-share/shared/Image_Superresolution/Dataset/Flickr2K/Patches/LRPatch_256/"
TARGET_PATH = "/opt/hubshare/vectorly-share/shared/Image_Superresolution/Dataset/Flickr2K/Patches/512/"


def train(rank: int, world_size: int, epochs: int, opt=None):
    # process-group init twin (Fairscale-DDP.py:27): env:// rendezvous
    runtime.initialize()
    # unified telemetry: --trace/$GRAFT_TRACE/$GRAFT_TELEMETRY turn the
    # tracer on here (this driver builds steps directly, no Stoke facade)
    from pytorch_distributedtraining_tpu.observe import trace as telemetry

    telemetry.configure_from_env()
    pp = max(1, int(getattr(opt, "pp", 1)))
    # --hier/$GRAFT_HIER: two-level gradient sync. The mesh gains a slice
    # (dp/DCN) axis of 2; the within-slice axis keeps the ZeRO2 shards.
    hier = getattr(opt, "hier", None)
    if hier is None:
        hier = os.environ.get("GRAFT_HIER", "").strip().lower() not in (
            "", "0", "false", "off", "no"
        )
    if pp > 1:
        # --pp shapes the mesh with a pipeline axis (remaining devices on
        # the sharded-DP axis). ESPCN has no uniform stacked stage trunk,
        # so the TrainStep below replicates over pp — a mesh-shape smoke
        # path; the schedule-driven engine is parallel.PipelineStep.
        import jax as _jax

        fsdp = max(1, _jax.device_count() // pp)
        print(f"--pp={pp} ({getattr(opt, 'pp_schedule', '1f1b')}): mesh "
              f"fsdp={fsdp} x pp={pp}; ESPCN has no stacked stages, pp "
              "ranks replicate (see parallel.PipelineStep)")
        mesh = make_mesh(MeshSpec(fsdp=fsdp, pp=pp))
        if hier:
            print("--hier ignored under --pp (the pipelined mesh has no "
                  "slice axis; hierarchy needs the data devices)")
            hier = False
    else:
        import jax as _jax

        n_dev = _jax.device_count()
        if hier and n_dev >= 4 and n_dev % 2 == 0:
            # two slices of n/2: dp rides DCN, fsdp keeps the ZeRO2
            # shards on the within-slice (ICI) links
            mesh = make_hybrid_mesh(
                MeshSpec(fsdp=n_dev // 2), dcn_dp=2
            )
            print(f"===> Hierarchical sync: 2 slices x fsdp={n_dev // 2} "
                  "(reduce-scatter on ICI, cross-slice all-reduce on DCN)")
        else:
            if hier:
                print(f"--hier needs >= 4 devices in an even split, have "
                      f"{n_dev}; flat sync")
                hier = False
            mesh = make_mesh(MeshSpec.zero())

    print("===> Loading datasets")
    input_path = getattr(opt, "input_dir", INPUT_PATH)
    target_path = getattr(opt, "target_dir", TARGET_PATH)
    print("--Input Directory--", input_path)

    if getattr(opt, "synthetic", False) or not os.path.isdir(input_path):
        if not getattr(opt, "synthetic", False):
            print("(dataset dirs absent -> synthetic SR data)")
        full_dataset = SyntheticSRDataset(
            n=getattr(opt, "synthetic_n", 512), lr_size=32, scale=2
        )
    else:
        full_dataset = CustomDataset(input_path, target_path)

    train_size = int(0.99 * len(full_dataset))
    test_size = len(full_dataset) - train_size
    train_dataset, val_dataset = random_split(full_dataset, [train_size, test_size])

    # fixed: num_replicas from the runtime, not hardcoded 4 (:47,53)
    train_sampler = DistributedSampler(
        train_dataset,
        num_replicas=runtime.process_count(),
        rank=runtime.process_index(),
    )
    val_sampler = DistributedSampler(
        val_dataset,
        num_replicas=runtime.process_count(),
        rank=runtime.process_index(),
    )

    # device_prefetch keeps 2 sharded batches staged on the mesh ahead of
    # the hot loop so H2D transfer overlaps the running step
    batch_size = getattr(opt, "batch_size", BATCH_SIZE)
    training_dataloader = DataLoader(
        dataset=train_dataset, num_workers=getattr(opt, "workers", 16),
        batch_size=batch_size, drop_last=True, shuffle=False,
        pin_memory=True, sampler=train_sampler,
        mesh=mesh, spec=batch_spec(mesh),
        device_prefetch=getattr(opt, "device_prefetch", 2),
    )
    val_dataloader = DataLoader(
        dataset=val_dataset, num_workers=8, batch_size=batch_size,
        shuffle=False, sampler=val_sampler, drop_last=True,
        mesh=mesh, spec=batch_spec(mesh),
        device_prefetch=getattr(opt, "device_prefetch", 2),
    )

    # probe batch (Fairscale-DDP.py:67-71)
    x, y = next(iter(training_dataloader))
    print("Length of Training dataset - ", len(train_dataset))
    print("--Shape--", x.shape, y.shape)

    print("===> Building model")
    model = Net(upscale_factor=2)

    def loss_fn(params, batch, rng, model_state):
        inputs, targets = batch
        return mse_loss(model.apply({"params": params}, inputs), targets), {}

    # OSS(AdamW) + ShardedDDP wrap (:78-89) -> ZeRO2 policy on the engine;
    # --remat/$GRAFT_REMAT picks the activation-checkpoint policy
    remat = getattr(opt, "remat", None)
    if remat is None:
        remat = os.environ.get("GRAFT_REMAT", "none")
    tx = optim.adamw(lr=1e-3, betas=(0.9, 0.99), eps=1e-8, weight_decay=1e-4)
    state, shardings = create_train_state(
        model=model, sample_input=jnp.asarray(np.asarray(x)[:1]),
        tx=tx, mesh=mesh, policy=ZeRO2(remat=remat),
    )
    # --wire/$GRAFT_WIRE: quantized gradient collectives (block-scaled
    # int8/fp8 with error feedback — parallel/compressed.py). ZeRO-2's
    # reduce-to-owner becomes a narrow all-to-all + local dequant-sum;
    # wire_cost prints the analytic bytes saved per step.
    wire_spec = getattr(opt, "wire", None)
    if wire_spec is None:
        wire_spec = os.environ.get("GRAFT_WIRE")
    wire = wire_format(wire_spec)
    # --numerics/$GRAFT_NUMERICS: fuse the numerics probe into the jitted
    # step and run the host-side divergence watchdog over its aux
    from pytorch_distributedtraining_tpu.observe import numerics as obs_num

    probe = obs_num.probe_from_env()
    watchdog = obs_num.watchdog_from_env() if probe is not None else None
    # --capture/$GRAFT_CAPTURE: arm the anomaly-triggered profiler on this
    # driver's raw-step loop; with --opcost/$GRAFT_OPCOST a landed capture
    # is parsed into the per-axis bandwidth gauges the fleet endpoint
    # publishes (observe/capture.py + observe/opcost.py)
    capture_prof = None
    _cap_env = os.environ.get("GRAFT_CAPTURE", "")
    if _cap_env.strip().lower() not in ("", "0", "false", "off", "no"):
        from pytorch_distributedtraining_tpu.observe.capture import (
            OnDemandProfiler,
        )

        _cap_dir = (
            _cap_env.strip()
            if _cap_env.strip().lower() not in ("1", "true", "on", "yes")
            else None
        )
        _on_capture = None
        if os.environ.get("GRAFT_OPCOST", "").strip().lower() not in (
            "", "0", "false", "off", "no"
        ):
            from pytorch_distributedtraining_tpu.observe import (
                opcost as opcost_mod,
            )

            def _on_capture(cap_dir, source):
                opcost_mod.ingest_trace(cap_dir, mesh_axes=dict(mesh.shape))

        capture_prof = OnDemandProfiler(
            trace_dir=_cap_dir, on_capture=_on_capture
        ).arm()
    if wire is not None and pp == 1:
        # MeshSpec.zero() puts every device on the sharded-DP axis, so
        # the quantized hop is the fsdp axis there; on the --hier hybrid
        # mesh the quantized hop is the dp (DCN) crossing — the only
        # link narrow enough to care
        step = CompressedGradStep(
            loss_fn, tx, mesh, ZeRO2(remat=remat),
            axis_name="dp" if hier else "fsdp", wire=wire, numerics=probe,
        )
        cost = step.wire_cost(state.params)
        print(f"===> Quantized wire {cost['wire_format']}: "
              f"{cost['wire_bytes']} bytes/step on the gradient hop vs "
              f"{cost['fp32_bytes']} fp32 "
              f"({cost['wire_fraction_quantized']:.1%} of gradient "
              "elements quantized)")
    elif hier:
        from pytorch_distributedtraining_tpu.parallel import HierGradStep

        step = HierGradStep(
            loss_fn, tx, mesh, ZeRO2(remat=remat), numerics=probe,
        )
        cost = step.dcn_cost(state.params)
        print(f"===> Two-level sync: {cost['dcn_bytes']} bytes/step on "
              f"the DCN hop vs {cost['dcn_bytes_flat_twin']} flat "
              f"(1/{cost['ici_size']} of the gradient crosses slices)")
    else:
        if wire is not None:
            print("--wire ignored under --pp (the pipelined mesh's "
                  "collectives re-home activations, not gradients)")
        step = TrainStep(
            loss_fn, tx, mesh, ZeRO2(remat=remat), state_shardings=shardings,
            numerics=probe,
        )

    # --analyze/$GRAFT_ANALYZE: graftcheck the step before the first
    # device step (AOT — the jit cache keeps the lowering, so the
    # training loop below pays no extra compile)
    analyze = getattr(opt, "analyze", None) or os.environ.get("GRAFT_ANALYZE")
    if analyze and analyze != "off":
        from pytorch_distributedtraining_tpu.analyze import analyze_step

        report = analyze_step(step, state, (x, y))
        print(report.render())
        if analyze == "error" and not report.ok:
            print("===> graftcheck: error-severity findings; aborting "
                  "before the first step")
            raise SystemExit(2)

    # --ckpt: periodic (optionally async) checkpointing with elastic,
    # reshard-capable auto-resume — a checkpoint written on a different
    # mesh shape (or world size) restores onto THIS mesh via the portable
    # manifest (checkpoint_sharded.restore_latest → reshard path)
    mgr = None
    start_step = 0
    if getattr(opt, "ckpt", None):
        from pytorch_distributedtraining_tpu.checkpoint_sharded import (
            CheckpointManager,
        )

        mgr = CheckpointManager(
            opt.ckpt,
            save_every=getattr(opt, "save_every", 100),
            keep=3,
            async_save=getattr(opt, "ckpt_async", False),
        )
        resumed = mgr.restore_latest(jax.tree.map(lambda a: a, state))
        if resumed is not None:
            start_step, state = resumed
            mode = os.environ.get("GRAFT_RECOVERY_MODE", "")
            print(f"===> Resumed from checkpoint @ step {start_step}"
                  + (f" (recovery_mode={mode})" if mode else ""))

    # a resume COMPLETES the original --epochs schedule: epochs and
    # iterations the checkpoint already covers are skipped, not re-trained
    # (one optimizer step per iteration, so step count maps onto the
    # epoch/iteration grid directly)
    steps_per_epoch = len(training_dataloader)
    start_epoch = start_step // steps_per_epoch if steps_per_epoch else 0
    skip_iters = start_step % steps_per_epoch if steps_per_epoch else 0
    if start_epoch >= epochs:
        print(f"===> Checkpoint step {start_step} already covers the "
              f"{epochs}-epoch schedule; nothing left to train")

    loss = None
    dispatched = 0
    try:
        for e in range(start_epoch, epochs):
            for iteration, batch in enumerate(training_dataloader, 1):
                if e == start_epoch and iteration <= skip_iters:
                    continue
                state, metrics = step(state, batch)
                dispatched += 1
                if dispatched == 2:  # the first steady step: where start-up went
                    print("===> " + telemetry.describe_startup(
                        telemetry.startup_report()
                    ))
                loss = metrics["loss"]
                if capture_prof is not None:
                    capture_prof.note_step()
                step_clean = True
                if probe is not None and "numerics" in metrics:
                    summary = probe.observe(
                        metrics["numerics"], step=int(state.step),
                        loss=metrics.get("loss"), watchdog=watchdog,
                    )
                    # a non-finite step poisoned the post-update params:
                    # checkpointing it would make the rollback target
                    # itself divergent once the watchdog's patience runs
                    # out a step or two later
                    step_clean = not summary.get("nonfinite")
                    verdict = summary.get("verdict")
                    if verdict is not None:
                        # rollback restores the last committed checkpoint
                        # and resumes the schedule from there; degrade
                        # flips $GRAFT_WIRE to fp32 for later rebuilds;
                        # halt raises NumericsDivergence out of the loop
                        rolled = watchdog.apply_action(
                            verdict, manager=mgr, template=state,
                        )
                        if rolled is not None:
                            rolled_step, state = rolled
                            print("===> numerics watchdog "
                                  f"{verdict['kind']} @ step "
                                  f"{verdict['step']}: rolled back to "
                                  f"committed step {rolled_step}")
                if mgr is not None and step_clean:
                    mgr.maybe_save(int(state.step), state)
                if iteration % 25 == 0:
                    print(loss)
            print("For Epoch {}, loss: {:.2f}".format(e, float(loss)))
    finally:
        if mgr is not None:
            mgr.close()

    if telemetry.enabled():
        trace_path = telemetry.export_chrome_trace()
        print(f"===> telemetry trace written: {trace_path} "
              "(load in Perfetto / chrome://tracing)")

    runtime.shutdown()
    return float(loss) if loss is not None else None


def main(argv=None):
    parser = argparse.ArgumentParser(description="ZeRO-2 SR training (TPU)")
    parser.add_argument("--epochs", type=int, default=EPOCHS)
    parser.add_argument("--batch-size", type=int, default=BATCH_SIZE)
    parser.add_argument("--input-dir", type=str, default=INPUT_PATH)
    parser.add_argument("--target-dir", type=str, default=TARGET_PATH)
    parser.add_argument("--workers", type=int, default=16)
    parser.add_argument("--device-prefetch", type=int, default=2,
                        help="batches staged on the mesh ahead of the step "
                             "(0 = synchronous placement)")
    parser.add_argument("--synthetic", action="store_true",
                        help="train on synthetic SR data (no dataset needed)")
    parser.add_argument("--synthetic-n", type=int, default=512)
    parser.add_argument("--remat", type=str, default=None,
                        help="activation remat policy for the step: "
                             "none/full/dots/names/offload "
                             "(default: $GRAFT_REMAT or none)")
    parser.add_argument("--pp", type=int,
                        default=int(os.environ.get("GRAFT_PP", "1")),
                        help="pipeline-parallel mesh axis size (env twin "
                             "$GRAFT_PP). ESPCN has no uniform stacked "
                             "stage trunk, so pp>1 only shapes the mesh "
                             "here (pp ranks replicate); the schedule-"
                             "driven engine is parallel.PipelineStep")
    parser.add_argument("--pp-schedule", type=str,
                        default=os.environ.get("GRAFT_PP_SCHEDULE", "1f1b"),
                        choices=["gpipe", "1f1b", "interleaved"],
                        help="pipeline schedule (env twin "
                             "$GRAFT_PP_SCHEDULE)")
    parser.add_argument("--wire", type=str, default=None,
                        help="quantized gradient wire format: int8/"
                             "int8_block/fp8_e4m3/fp8_e5m2, optional "
                             ":BLOCK suffix (env twin $GRAFT_WIRE; "
                             "default: f32 collectives)")
    parser.add_argument("--hier", action="store_true", default=None,
                        help="two-level gradient sync: split the data "
                             "devices into 2 slices (dp rides DCN via "
                             "make_hybrid_mesh) and reduce-scatter within "
                             "the slice before the cross-slice hop (env "
                             "twin $GRAFT_HIER; composes with --wire — "
                             "the quantized hop becomes the DCN axis)")
    parser.add_argument("--plan", type=str,
                        default=os.environ.get("GRAFT_PLAN"),
                        help="auto-planner plan.json (path or inline JSON): "
                             "threads the top-ranked plan's remat/wire/hier "
                             "through their env twins when not set "
                             "explicitly; this driver's engine is fixed "
                             "ZeRO2, so a plan asking for another "
                             "policy/mesh logs the conflict and keeps the "
                             "engine (env twin $GRAFT_PLAN)")
    parser.add_argument("--analyze", type=str, nargs="?", const="error",
                        default=os.environ.get("GRAFT_ANALYZE"),
                        choices=["warn", "error", "off"],
                        help="run graftcheck static analysis on the step "
                             "before training: warn prints the report, "
                             "error additionally aborts on error-severity "
                             "findings (bare --analyze = error; env twin "
                             "$GRAFT_ANALYZE)")
    parser.add_argument("--ckpt", type=str, default=None,
                        help="checkpoint root dir: save every --save-every "
                             "steps and auto-resume (reshard-capable: a "
                             "checkpoint from a different mesh/world "
                             "restores onto this one)")
    parser.add_argument("--ckpt-async", action="store_true",
                        help="snapshot to host on the step path, serialize "
                             "in a background writer (commit-marker "
                             "protocol; see docs/RESILIENCE.md)")
    parser.add_argument("--save-every", type=int, default=100,
                        help="checkpoint cadence in steps (with --ckpt)")
    parser.add_argument("--trace", type=str, nargs="?", const="",
                        default=os.environ.get("GRAFT_TRACE"),
                        help="enable unified telemetry (step spans, goodput "
                             "ledger, crash flight recorder) and export a "
                             "Chrome trace-event JSON at exit — bare "
                             "--trace writes under the run dir, --trace DIR "
                             "writes there (env twin $GRAFT_TRACE; "
                             "$GRAFT_TELEMETRY=0 force-disables)")
    parser.add_argument("--numerics", type=str, nargs="?", const="halt",
                        default=None,
                        choices=[None, "halt", "rollback", "degrade"],
                        help="enable the numerics observability plane: fused "
                             "on-device probes (non-finite blame, grad/param "
                             "norms, fp8/wire health) plus the divergence "
                             "watchdog. The value is the watchdog action — "
                             "rollback pairs with --ckpt to restore the last "
                             "committed step (bare --numerics = halt; env "
                             "twins $GRAFT_NUMERICS / $GRAFT_NUMERICS_ACTION)")
    parser.add_argument("--opcost", action="store_true",
                        default=bool(os.environ.get("GRAFT_OPCOST")),
                        help="enable the op-cost attribution plane: a landed "
                             "profiler capture is parsed into per-class cost "
                             "tables + per-axis collective bandwidth gauges "
                             "(env twin $GRAFT_OPCOST)")
    parser.add_argument("--capture", type=str, nargs="?", const="1",
                        default=os.environ.get("GRAFT_CAPTURE"),
                        help="arm the anomaly-triggered profiler capture on "
                             "the training loop (bounded jax.profiler trace "
                             "on straggler/SLO/numerics/regression signals) "
                             "— bare --capture writes under the run dir, "
                             "--capture DIR writes there (env twin "
                             "$GRAFT_CAPTURE)")
    opt = parser.parse_args(argv)

    if opt.trace is not None:
        os.environ.setdefault("GRAFT_TELEMETRY", "1")
        if opt.trace:
            os.environ["GRAFT_TRACE"] = opt.trace

    if opt.numerics:
        os.environ["GRAFT_NUMERICS"] = "1"
        os.environ["GRAFT_NUMERICS_ACTION"] = opt.numerics

    if opt.plan:
        # this driver hand-builds its ZeRO2 engine, so only the plan's
        # step-level knobs (remat/wire) can apply — thread them through
        # the env twins the train() path already resolves, and say out
        # loud which plan fields the fixed engine overrides
        from pytorch_distributedtraining_tpu.analyze.plan import load_plan

        plan = load_plan(opt.plan)
        want = plan.config_fields()
        if opt.remat is None and not os.environ.get("GRAFT_REMAT"):
            if want["remat"]:
                os.environ["GRAFT_REMAT"] = str(want["remat"])
        elif str(want["remat"] or "none") != str(
            opt.remat or os.environ.get("GRAFT_REMAT") or "none"
        ):
            print(f"===> plan conflict: explicit remat wins over the "
                  f"plan's {want['remat']!r}")
        if opt.wire is None and not os.environ.get("GRAFT_WIRE"):
            if want["wire"]:
                os.environ["GRAFT_WIRE"] = want["wire"]
        elif (opt.wire or os.environ.get("GRAFT_WIRE")) != want["wire"]:
            print(f"===> plan conflict: explicit wire wins over the "
                  f"plan's {want['wire']!r}")
        if opt.hier is None and not os.environ.get("GRAFT_HIER"):
            if want.get("hier"):
                os.environ["GRAFT_HIER"] = "1"
        elif bool(
            opt.hier
            or os.environ.get("GRAFT_HIER", "").strip().lower()
            not in ("", "0", "false", "off", "no")
        ) != bool(want.get("hier")):
            print(f"===> plan conflict: explicit hier wins over the "
                  f"plan's {bool(want.get('hier'))!r}")
        if plan.policy != "zero2" or plan.pp > 1 or (
            # dp=2 + hier IS this driver's hybrid mesh (2 slices); any
            # other dp asks for a mesh the fixed engine won't build
            plan.dp > 1 and not (want.get("hier") and plan.dp == 2)
        ):
            print(f"===> plan conflict: this driver's fixed ZeRO2 mesh "
                  f"overrides the plan's {plan.describe()!r}")

    if opt.opcost:
        os.environ["GRAFT_OPCOST"] = "1"
    if opt.capture and opt.capture.strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        os.environ["GRAFT_CAPTURE"] = opt.capture

    # GRAFT_PLATFORM=cpu selects the backend after jax is imported
    runtime.force_platform_from_env()
    runtime.enable_compile_cache()

    # env rendezvous exactly like the reference __main__ (:122-123); under
    # SPMD the single controller drives all devices, no mp.spawn fork
    os.environ.setdefault("MASTER_ADDR", "127.0.0.1")
    os.environ.setdefault("MASTER_PORT", str(runtime.find_free_port()))
    return train(0, WORLD_SIZE, opt.epochs, opt)


if __name__ == "__main__":
    main()
