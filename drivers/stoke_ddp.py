"""TPU-native port of the reference's full-featured Stoke driver.

Mirrors `/root/reference/Stoke-DDP.py` function-for-function with the CLI
preserved flag-for-flag (`:156-173`): ``train_log``/``val_log`` (`:47-58`),
``train`` (`:61-98`), ``validate`` (`:101-134`), ``save_checkpoint``
(`:137-147`), ``main`` (`:150-342`). The launch lines become::

    python drivers/stoke_ddp.py --projectName "Stoke-4K-2X-DDP" \
        --batchSize 18 --nEpochs 2 --lr 1e-3 --weight_decay 1e-4 --grad_clip 0.1

(one SPMD process drives all devices; no torch.distributed.launch fork).

Reference bugs fixed, not ported (SURVEY §2.1): ``scheduler2.step`` missing
call parens (`:84` — dead code; here stepped on val loss each epoch),
``wandb.init()`` re-called per log (`:49,56` — idempotent shim tolerates
it), un-detached loss logged (`:93`), sampler ``set_epoch`` never called.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributedtraining_tpu import metrics, runtime
from pytorch_distributedtraining_tpu.data import (
    CustomDataset,
    DistributedSampler,
    SyntheticSRDataset,
    random_split,
)
from pytorch_distributedtraining_tpu.losses import feat_loss
from pytorch_distributedtraining_tpu.models import SwinIR
from pytorch_distributedtraining_tpu.observe import trace as telemetry
from pytorch_distributedtraining_tpu.observe import wandb
from pytorch_distributedtraining_tpu.optim import OneCycleLR, ReduceLROnPlateau
from pytorch_distributedtraining_tpu.stoke import (
    AMPConfig,
    ClipGradNormConfig,
    DDPConfig,
    DistributedOptions,
    FairscaleOSSConfig,
    Stoke,
    StokeOptimizer,
)

try:
    from tqdm import tqdm
except ImportError:  # pragma: no cover
    tqdm = lambda x, **k: x  # noqa: E731


def train_log(loss, example_ct, epoch):
    wandb.init()  # tolerated (reference pattern :49); no-op once running
    wandb.log({"epoch": epoch, "train_loss": float(loss)})
    print(f"Loss after " + str(example_ct).zfill(5) + f" examples: {float(loss):.3f}")


def val_log(loss, avg_mae, avg_psnr, example_ct, epoch):
    wandb.init()
    wandb.log({
        "epoch": epoch, "val_loss": float(loss),
        "PSNR": float(avg_psnr), "MAE": float(avg_mae),
    })
    print(
        f"-----VALIDATION Loss after " + str(example_ct).zfill(5)
        + f" examples: {float(loss):.3f}--------"
    )


def _maybe_analyze(stoke_model: Stoke, inputs, targets):
    """--analyze/$GRAFT_ANALYZE: graftcheck the fused-step program on the
    first batch. ``warn`` prints the report; ``error`` aborts on
    error-severity findings before any device step runs."""
    mode = getattr(opt, "analyze", None) if "opt" in globals() else None
    mode = mode or os.environ.get("GRAFT_ANALYZE")
    if not mode or mode == "off":
        return
    report = stoke_model.static_analyze(inputs, targets)
    print(report.render())
    if mode == "error" and not report.ok:
        print("===> graftcheck: error-severity findings; aborting before "
              "the first step")
        raise SystemExit(2)


def train(train_dataloader, stoke_model: Stoke, scheduler1, scheduler2, epoch: int):
    example_ct = 0
    batch_ct = 0
    sum_loss = 0.0

    stoke_model.print_on_devices(f"Starting Epoch {epoch + 1}")
    stoke_model.model_access.train()

    for idx, (inputs, targets) in enumerate(train_dataloader):
        if epoch == 0 and idx == 0:
            # graftcheck before the first device step. This driver trains
            # on the eager loss/backward/step surface, which never builds
            # the fused TrainStep on its own — analyze it explicitly so
            # --analyze means the same thing on every driver.
            _maybe_analyze(stoke_model, inputs, targets)
        if epoch == 0 and idx == stoke_model.grad_accum_steps:
            # the first optimizer step is out: where start-up went
            stoke_model.print_on_devices(telemetry.describe_startup(
                telemetry.startup_report(until=time.perf_counter())
            ))
        outputs = stoke_model.model(inputs)
        train_loss = stoke_model.loss(outputs, targets)

        stoke_model.print_ema_loss(prepend_msg=f"Step {idx+1} -- EMA Loss")

        stoke_model.backward(loss=train_loss)
        stoke_model.step()
        scheduler1.step()
        # scheduler2 (plateau) steps on the validation metric in main();
        # the reference's per-batch `scheduler2.step` (:84) was dead code

        # device scalar: accumulation stays async; float() only at logs
        sum_loss += stoke_model.detach_and_sync_loss(loss=train_loss)

        example_ct += len(inputs)
        batch_ct += 1

        if ((batch_ct + 1) % 50) == 0:
            train_log(stoke_model.detach_and_sync_loss(train_loss), example_ct, epoch)

    if batch_ct == 0:
        # a silent zero-batch epoch leaves the model uninitialized and
        # surfaces later as a confusing validate() failure — name the
        # actual cause (global batch = per-device x n_devices > split size)
        raise ValueError(
            "train dataloader yielded no batches: the dataset split is "
            "smaller than one global batch "
            f"(len(dataset)={len(getattr(train_dataloader, 'dataset', []))}, "
            f"global batch={getattr(train_dataloader, 'batch_size', '?')}); "
            "lower --batchSize or provide more data"
        )
    avg_loss = sum_loss / max(1, len(train_dataloader))
    return float(avg_loss)  # one host sync per epoch, at the boundary


def validate(val_dataloader, stoke_model: Stoke, epoch):
    stoke_model.model_access.eval()

    # one compiled fwd+metrics program per batch under the training layout
    # (facade EvalStep); totals accumulate as device scalars, so the whole
    # epoch costs ONE host sync at the bottom — the reference's loop
    # (`Stoke-DDP.py:114-121`) host-synced 3x per batch
    eval_step = stoke_model.eval_step({"mae": metrics.mae, "psnr": metrics.psnr})

    totals, example_ct, batches = None, 0, 0
    for inputs, targets in val_dataloader:
        example_ct += len(inputs)
        m = eval_step(inputs, targets)
        totals = m if totals is None else jax.tree.map(jnp.add, totals, m)
        batches += 1

    n = max(1, batches)
    host = {} if totals is None else jax.device_get(totals)  # the one sync
    val_avg_loss = float(host.get("loss", 0.0)) / n
    avg_mae = float(host.get("mae", 0.0)) / n
    avg_psnr = float(host.get("psnr", 0.0)) / n

    val_log(val_avg_loss, avg_mae, avg_psnr, example_ct, epoch)
    stoke_model.print_on_devices(
        msg=f"Current Average Validation Loss: {val_avg_loss}, PSNR : {avg_psnr}"
    )
    return val_avg_loss


def save_checkpoint(stoke_model, epoch, train_loss, val_loss,
                    portable_dir=None):
    os.makedirs("checkpoint/", exist_ok=True)
    path, tag = stoke_model.save(
        path="checkpoint/",
        name="model_{}_{:.2f}_{:.2f}".format(epoch, train_loss, val_loss),
    )
    print("Checkpoint saved after epoch {}".format(epoch))
    if portable_dir:
        # topology-independent twin: restores onto a different mesh/world
        # via Stoke.load_resharded (elastic resume, docs/RESILIENCE.md)
        p = stoke_model.save_portable(
            os.path.join(portable_dir, "epoch_{:04d}".format(epoch))
        )
        print("Portable (reshardable) checkpoint saved: {}".format(p))
    return path, tag


def build_parser():
    # flag-for-flag with Stoke-DDP.py:156-173
    parser = argparse.ArgumentParser(description="PyTorch-W&B-Training")
    parser.add_argument("--projectName", default="Stoke-4K-2X-DDP", type=str, help="Project Name for W&B")
    parser.add_argument("--batchSize", type=int, default=18, help="Training batch size")
    parser.add_argument("--nEpochs", type=int, default=10, help="Number of epochs to train for")
    parser.add_argument("--start-epoch", default=1, type=int, help="Manual epoch number (useful on restarts)")
    parser.add_argument("--lr", type=float, default=0.001, help="Learning Rate. Default=0.1")
    parser.add_argument("--weight_decay", "--wd", default=1e-4, type=float, help="Weight decay, Default: 1e-4")
    parser.add_argument("--grad_clip", type=float, default=0.1, help="Clipping Gradients. Default=0.1")
    parser.add_argument("--local_rank", default=-1, type=int, help="rank (default: 0)")
    parser.add_argument("--threads", type=int, default=16, help="Number of threads for data loader to use, Default: 4")
    parser.add_argument("--inputDir", type=str, default="/opt/hubshare/vectorly-share/shared/Image_Superresolution/Dataset/Flickr2K/Patches/LRPatch_128/", help="Training Dataset Path")
    parser.add_argument("--targetDir", type=str, default="/opt/hubshare/vectorly-share/shared/Image_Superresolution/Dataset/Flickr2K/Patches/HR_256/", help="Training Dataset Path")
    # TPU-port extras (additive; reference flags above unchanged)
    parser.add_argument("--synthetic", action="store_true", help="use synthetic SR data")
    parser.add_argument("--synthetic-n", type=int, default=256)
    parser.add_argument("--pretrained", type=str, default=None,
                        help="checkpoint to load (nested 'params' key supported)")
    parser.add_argument("--portable-ckpt", type=str, default=None,
                        help="also write a topology-independent (portable) "
                             "checkpoint per epoch under DIR, and auto-"
                             "resume from the latest committed one — "
                             "reshards onto this run's mesh even if saved "
                             "on a different mesh/world size")
    parser.add_argument("--fp16", type=str, default=None, choices=[None, "amp", "bf16"],
                        help="precision: amp (fp16+scaler) or bf16")
    parser.add_argument("--scan-layers", action="store_true",
                        default=os.environ.get("GRAFT_SCAN_LAYERS", "").strip().lower()
                        in ("1", "true", "on", "yes"),
                        help="nn.scan the RSTB layer stacks (one compiled "
                             "W-MSA/SW-MSA pair per RSTB; cold-compile lever)")
    parser.add_argument("--remat", type=str, default=None,
                        help="activation remat policy per Swin layer/pair: "
                             "none/full/dots/names/offload "
                             "(default: $GRAFT_REMAT or none)")
    parser.add_argument("--pp", type=int,
                        default=int(os.environ.get("GRAFT_PP", "1")),
                        help="pipeline-parallel mesh axis size (env twin "
                             "$GRAFT_PP). SwinIR has no uniform stacked "
                             "stage trunk, so on this driver pp>1 only "
                             "shapes the mesh (pp ranks replicate); the "
                             "schedule-driven engine is parallel."
                             "PipelineStep (see docs/PARALLELISM.md)")
    parser.add_argument("--pp-schedule", type=str,
                        default=os.environ.get("GRAFT_PP_SCHEDULE", "1f1b"),
                        choices=["gpipe", "1f1b", "interleaved"],
                        help="pipeline schedule for pipelined steps (env "
                             "twin $GRAFT_PP_SCHEDULE)")
    parser.add_argument("--wire", type=str,
                        default=os.environ.get("GRAFT_WIRE"),
                        help="quantized gradient wire for the fused step: "
                             "int8/int8_block/fp8_e4m3/fp8_e5m2, optional "
                             ":BLOCK suffix (env twin $GRAFT_WIRE). Note "
                             "this driver's grad_accum_steps=2 + amp fall "
                             "back to the f32 wire with a warning — use "
                             "--fp16 bf16 off and accum 1 paths to engage")
    parser.add_argument("--fp8", type=str, default=os.environ.get("GRAFT_FP8"),
                        choices=[None, "e4m3", "e5m2"],
                        help="fp8 matmul mode for models with an fp8 "
                             "config field (GPT-2/ViT; env twin $GRAFT_FP8"
                             "). SwinIR has no fp8 tagging — the facade "
                             "warns and keeps the model dtype")
    parser.add_argument("--plan", type=str,
                        default=os.environ.get("GRAFT_PLAN"),
                        help="apply an auto-planner plan.json (path or "
                             "inline JSON): its top-ranked configuration "
                             "fills every mesh/policy/remat/pp/wire knob "
                             "still at its default; explicit flags above "
                             "win with a logged conflict (env twin "
                             "$GRAFT_PLAN; see docs/PLANNER.md)")
    parser.add_argument("--analyze", type=str, nargs="?", const="error",
                        default=os.environ.get("GRAFT_ANALYZE"),
                        choices=["warn", "error", "off"],
                        help="run graftcheck static analysis at first "
                             "compile of the fused step: warn prints the "
                             "report, error additionally aborts on "
                             "error-severity findings (bare --analyze = "
                             "error; env twin $GRAFT_ANALYZE)")
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
                             "watchdog. The value is the watchdog action "
                             "(bare --numerics = halt; env twins "
                             "$GRAFT_NUMERICS / $GRAFT_NUMERICS_ACTION)")
    parser.add_argument("--opcost", action="store_true",
                        default=bool(os.environ.get("GRAFT_OPCOST")),
                        help="enable the op-cost attribution plane: after a "
                             "profiler capture lands, parse it into per-class "
                             "cost tables and per-axis collective bandwidth "
                             "gauges (env twin $GRAFT_OPCOST)")
    parser.add_argument("--capture", type=str, nargs="?", const="1",
                        default=os.environ.get("GRAFT_CAPTURE"),
                        help="arm the anomaly-triggered profiler capture: a "
                             "bounded jax.profiler trace fires on straggler/"
                             "SLO-burn/numerics/regression signals — bare "
                             "--capture writes under the run dir, --capture "
                             "DIR writes there (env twin $GRAFT_CAPTURE; "
                             "composes with --opcost for the bandwidth "
                             "ingest)")
    return parser


def main(argv=None):
    # (the reference's `os.environ['LOCAL_RANK'] = str(os.getenv(...))` :153
    # poisons an unset var with the string "None" — dropped, the LOCAL_RANK
    # read below handles both unset and "None"; its PYTHONWARNINGS
    # semaphore_tracker silencer :154 is dropped too — no multiprocessing
    # workers exist in this port, and the var is only read at startup)

    global opt
    opt = build_parser().parse_args(argv)
    epochs = opt.nEpochs

    # GRAFT_PLATFORM=cpu selects the backend after jax is imported
    runtime.force_platform_from_env()
    runtime.enable_compile_cache()

    amp_config = AMPConfig(init_scale=2.0**14)
    local_rank = os.getenv("LOCAL_RANK")
    ddp_config = DDPConfig(
        local_rank=int(local_rank) if local_rank not in (None, "None") else None,
        convert_to_sync_batch_norm=True,
    )
    oss_config = FairscaleOSSConfig(broadcast_fp16=True)

    print("===> Building model")
    # --remat/--scan-layers thread the ISSUE-3 knobs ($GRAFT_REMAT /
    # $GRAFT_SCAN_LAYERS are the env twins; the facade also applies the
    # env fallbacks, so the explicit flags here just make them CLI-visible)
    from pytorch_distributedtraining_tpu.parallel.remat import resolve_remat

    remat = resolve_remat(
        opt.remat if opt.remat is not None
        else os.environ.get("GRAFT_REMAT", "none")
    )
    model = SwinIR(
        upscale=2, in_chans=3, img_size=64, window_size=8,
        img_range=1.0, depths=[6, 6, 6, 6], embed_dim=60,
        num_heads=[6, 6, 6, 6], mlp_ratio=2,
        upsampler="pixelshuffledirect", resi_connection="1conv",
        remat=remat, scan_layers=opt.scan_layers,
    )
    if opt.scan_layers or remat != "none":
        print(f"===> scan_layers={opt.scan_layers} remat={remat}")

    loss = feat_loss

    # --pp/--pp-schedule thread the pipeline knobs through their env twins
    # (the facade reads $GRAFT_PP/$GRAFT_PP_SCHEDULE when sizing the mesh)
    if opt.pp > 1:
        os.environ["GRAFT_PP"] = str(opt.pp)
        os.environ["GRAFT_PP_SCHEDULE"] = opt.pp_schedule
        print(f"===> pp={opt.pp} schedule={opt.pp_schedule} "
              "(mesh axis only on this driver; see --help)")

    # --plan threads the auto-planner artifact through its env twin: the
    # facade loads it and fills every knob not explicitly set here
    if opt.plan:
        os.environ["GRAFT_PLAN"] = opt.plan
        print(f"===> auto-planner plan={opt.plan}")

    # --analyze threads graftcheck through its env twin: the facade runs
    # the analyzer once at first compile of the fused step
    if opt.analyze:
        os.environ["GRAFT_ANALYZE"] = opt.analyze
        print(f"===> graftcheck analyze={opt.analyze}")

    # --wire/--fp8 thread the low-precision knobs through their env twins
    # (the facade validates spellings and warn-falls-back when the fused
    # step cannot compose — e.g. this driver's grad_accum_steps=2)
    if opt.wire:
        os.environ["GRAFT_WIRE"] = opt.wire
        print(f"===> quantized gradient wire={opt.wire}")
    if opt.fp8:
        os.environ["GRAFT_FP8"] = opt.fp8
        print(f"===> fp8 matmul mode={opt.fp8}")

    # --numerics threads the numerics plane through its env twins: the
    # facade builds the probe + watchdog at construction; the value picked
    # here is the watchdog action policy
    if opt.numerics:
        os.environ["GRAFT_NUMERICS"] = "1"
        os.environ["GRAFT_NUMERICS_ACTION"] = opt.numerics
        print(f"===> numerics plane on, watchdog action={opt.numerics}")

    # --opcost/--capture thread the op-cost attribution plane through the
    # env twins: the facade arms an OnDemandProfiler at construction and
    # the post-capture hook feeds the per-axis bandwidth gauges
    if opt.opcost:
        os.environ["GRAFT_OPCOST"] = "1"
        print("===> op-cost attribution on")
    if opt.capture and opt.capture.strip().lower() not in (
        "", "0", "false", "off", "no"
    ):
        os.environ["GRAFT_CAPTURE"] = opt.capture
        print(f"===> anomaly capture armed "
              f"(dir: {opt.capture if opt.capture != '1' else 'run dir'})")

    # --trace threads telemetry through its env twins: the facade enables
    # the tracer at construction; export happens after the epoch loop
    if opt.trace is not None:
        os.environ.setdefault("GRAFT_TELEMETRY", "1")
        if opt.trace:
            os.environ["GRAFT_TRACE"] = opt.trace
        print(f"===> telemetry on (trace dir: {opt.trace or 'run dir'})")

    optimizer = StokeOptimizer(
        optimizer="AdamW",
        optimizer_kwargs={
            "lr": opt.lr,
            "betas": (0.9, 0.99),
            "eps": 1e-8,
            "weight_decay": opt.weight_decay,
        },
    )

    stoke_model = Stoke(
        model=model,
        verbose=True,
        optimizer=optimizer,
        loss=loss,
        batch_size_per_device=opt.batchSize,
        gpu=True,
        fp16=opt.fp16,
        distributed=DistributedOptions.ddp.value,
        fairscale_oss=True,
        fairscale_sddp=True,
        grad_accum_steps=2,
        configs=[amp_config, ddp_config, oss_config],
        grad_clip=ClipGradNormConfig(max_norm=opt.grad_clip, norm_type=2.0),
    )

    print("===> Loading datasets")
    input_path = opt.inputDir
    target_path = opt.targetDir
    print("--Input Directory--", input_path)

    if opt.synthetic or not os.path.isdir(input_path):
        if not opt.synthetic:
            print("(dataset dirs absent -> synthetic SR data)")
        full_dataset = SyntheticSRDataset(n=opt.synthetic_n, lr_size=32, scale=2)
    else:
        full_dataset = CustomDataset(input_path, target_path)

    # pretrained load with nested-'params' fallback (Stoke-DDP.py:209-213)
    if opt.pretrained:
        stoke_model.init(np.zeros((1, 32, 32, 3), np.float32))
        stoke_model.load_model_state(opt.pretrained, strict=True, param_key="params")

    train_size = int(0.9 * len(full_dataset))
    test_size = len(full_dataset) - train_size
    train_dataset, val_dataset = random_split(full_dataset, [train_size, test_size])

    # the reference shards per-GPU (num_replicas=world_size :272-283); under
    # SPMD one process feeds all local devices, so sharding is per-process
    # (None -> jax.process_count()/process_index())
    train_sampler = DistributedSampler(
        dataset=train_dataset, num_replicas=None, rank=None,
    )
    val_sampler = DistributedSampler(val_dataset, num_replicas=None, rank=None)

    train_dataloader = stoke_model.DataLoader(
        dataset=train_dataset,
        sampler=train_sampler,
        num_workers=opt.threads,
        multiprocessing_context="spawn",
        # one spawn per run, not per epoch: worker startup is ~1 s each
        persistent_workers=True,
        # stage 2 sharded batches onto the mesh ahead of the running step
        # (H2D overlaps compute; $GRAFT_DEVICE_PREFETCH overrides)
        device_prefetch=None,
    )
    val_dataloader = stoke_model.DataLoader(
        dataset=val_dataset,
        sampler=val_sampler,
        multiprocessing_context="spawn",
        # reference hardcodes 8 (`Stoke-DDP.py:297`); capped by --threads so
        # an explicit --threads 0 (no workers) applies to validation too —
        # spawn is a real process pool here, not a no-op
        num_workers=min(8, opt.threads),
        persistent_workers=True,
        drop_last=False,  # a small val split must not become zero batches
        device_prefetch=None,
    )

    scheduler1 = OneCycleLR(
        stoke_model.optimizer, max_lr=0.01, pct_start=0.9,
        steps_per_epoch=max(1, len(train_dataloader)), epochs=epochs,
    )
    # factor mode (no handle): the plateau cut feeds scheduler1.lr_scale so
    # OneCycle's per-batch writes don't clobber it — a bare torch pairing
    # (reference :300-306) makes plateau cuts last one batch at most.
    # min_factor twins the reference's min_lr=5e-5 floor (:305) relative to
    # the base lr: cumulative cuts never push lr below 5e-5 — and never
    # above the base either (torch's min_lr floors, it never raises).
    scheduler2 = ReduceLROnPlateau(
        mode="min", factor=0.2, patience=2, verbose=True,
        min_factor=min(1.0, 5e-5 / max(opt.lr, 1e-12)),
    )

    config = dict(
        epochs=opt.nEpochs,
        batch_size=opt.batchSize,
        learning_rate=opt.lr,
        dataset="DemoVal",
        architecture="4K-2X-DDP",
    )

    # the reference's retry-forever loop (:316-322) lives inside the sink
    # now (bounded retries + offline fallback); init cannot raise here
    wandb.init(project=opt.projectName, config=config, reinit=True)
    config = wandb.config

    # elastic resume: latest COMMITTED portable checkpoint (torn .tmp dirs
    # and marker-less dirs are never candidates), resharded onto this mesh
    if opt.portable_ckpt and os.path.isdir(opt.portable_ckpt):
        from pytorch_distributedtraining_tpu.checkpoint_sharded import (
            is_committed_dir,
        )

        cands = sorted(
            os.path.join(opt.portable_ckpt, d)
            for d in os.listdir(opt.portable_ckpt)
        )
        latest = next(
            (p for p in reversed(cands) if is_committed_dir(p)), None
        )
        if latest is not None:
            stoke_model.init(np.zeros((1, 32, 32, 3), np.float32))
            stoke_model.load_resharded(latest)
            print("===> Resumed portable checkpoint {} (resharded onto "
                  "this mesh)".format(latest))

    print("===> Training")
    train_loss = val_loss = float("nan")
    for epoch in tqdm(range(epochs), leave=True):
        train_loss = train(train_dataloader, stoke_model, scheduler1, scheduler2, epoch)
        val_loss = validate(val_dataloader, stoke_model, epoch)
        scheduler1.lr_scale = scheduler2.step(val_loss)  # fixed: :84 never fired
        save_checkpoint(stoke_model, epoch, train_loss, val_loss,
                        portable_dir=opt.portable_ckpt)

        print("--------Train Loss after Epoch {} - {} --------".format(epoch, train_loss))
        print("--------Val Loss after Epoch {} - {} --------".format(epoch, val_loss))

    wandb.finish()
    trace_path = stoke_model.export_trace()
    if trace_path:
        print(f"===> telemetry trace written: {trace_path} "
              "(load in Perfetto / chrome://tracing)")
    train_dataloader.shutdown_workers()
    val_dataloader.shutdown_workers()
    return train_loss, val_loss


if __name__ == "__main__":
    main()
