"""The reference-shaped loop as a user runs it: the driver's own
``drivers.stoke_ddp.train(loader, stoke, sched1, sched2, epoch)`` over
``stoke.DataLoader`` on synthetic SR patches, timed. The benchmark hands
``train()`` a loader that stops yielding at the deadline and carries the
input-wait span; the driver's prints go to a file.
"""

from __future__ import annotations

import contextlib
import math
import multiprocessing
import os
import time

from chipbench import loop, stoke_common

# one execution per optimizer step: the split path's update program, or the
# fused eager window should the driver's loop come to take it
STEP_MODULES = ("jit_apply_updates", "jit_eager_step")
# two optimizer steps run every program of the loop: grad, first and later
# accumulation, update, the loss monitor. (The driver's float() of a loss
# every 50th batch compiles nothing; a compile inside the window would make
# the run incorrect, so a wrong guess here cannot pass.) Each microbatch is
# 0.2 s of every run's set-up.
WARM_BATCHES = 4


class DeadlineLoader:
    """The driver's loader, ending at a deadline instead of at the epoch's
    end. ``limit`` bounds the batches instead where there is no deadline
    (warm-up)."""

    def __init__(self, loader, env, *, seconds=None, limit=None, on_first=None):
        self.loader, self.env = loader, env
        self.seconds, self.limit, self.on_first = seconds, limit, on_first
        self.stamps = []
        self.t_open = None

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        spans, tracer = self.env.spans, self.env.tracer
        source = iter(self.loader)
        self.t_open = time.perf_counter()
        deadline = math.inf
        if self.seconds is not None:
            deadline = self.t_open + self.seconds
            tracer.arm(self.t_open, self.seconds)
        try:
            while len(self.stamps) < (self.limit or math.inf):
                now = time.perf_counter()
                if now >= deadline:
                    return
                if self.seconds is not None:
                    tracer.poll(now)
                with spans.span("input_wait"):
                    batch = next(source, None)
                if batch is None:
                    return
                if self.on_first is not None and not self.stamps:
                    self.on_first(batch)
                self.stamps.append(time.perf_counter())
                yield batch
        finally:
            close = getattr(source, "close", None)
            if close is not None:
                close()  # the device prefetcher's feeder thread

plan = stoke_common.plan


class Job:
    def __init__(self, env):
        self.env = env
        self.losses = []  # device scalars, as detach_and_sync_loss gives them

    def setup(self) -> dict:
        import jax

        from drivers import stoke_ddp
        from pytorch_distributedtraining_tpu.data import (
            DistributedSampler, SyntheticSRDataset,
        )

        env, job = self.env, self.env.cell.workload["job_params"]
        self.driver = stoke_ddp
        self.stoke, module, loss = stoke_common.build_stoke(env, self.losses)
        dataset = SyntheticSRDataset(
            n=job["dataset_n"], lr_size=job["lr_size"],
            scale=env.cell.config["upscale"], seed=env.seed,
        )
        self.loader = self.stoke.DataLoader(
            dataset=dataset,
            batch_size=job["batch_size_per_device"] * len(env.devices),
            sampler=DistributedSampler(
                dataset=dataset, num_replicas=None, rank=None
            ),
            num_workers=job["loader_workers"],
            multiprocessing_context="spawn", persistent_workers=True,
            device_prefetch=None,
        )
        self.sched1 = stoke_ddp.OneCycleLR(
            self.stoke.optimizer, max_lr=job["max_lr"], pct_start=0.9,
            steps_per_epoch=max(1, len(self.loader)), epochs=1,
        )
        self.sched2 = stoke_ddp.ReduceLROnPlateau(
            mode="min", factor=0.2, patience=2, verbose=True,
            min_factor=min(1.0, 5e-5 / job["lr"]),
        )
        self.images_per_batch = self.loader.batch_size
        reference = {}

        def on_first(batch):
            self.stoke.init(batch[0])
            reference["loss"] = env.family.reference_loss(
                module, loss, self.stoke.state.params, *batch
            )

        self.log = open(os.path.join(env.out_dir, "driver.log"), "w")
        warm = DeadlineLoader(
            self.loader, env, limit=WARM_BATCHES, on_first=on_first
        )
        self._train(warm, epoch=0)
        jax.block_until_ready(self.stoke.state)
        step0 = float(self.losses[0])
        del self.losses[:]
        env.counters["flops_per_step"] = (
            env.family.train_flops_per_image(env.cell.config, job["lr_size"])
            * self.images_per_batch * job["grad_accum_steps"]
        )
        return {"step0": {"loss": step0}, "reference": reference}

    def _train(self, loader, epoch: int) -> float:
        with contextlib.redirect_stdout(self.log):
            return self.driver.train(
                loader, self.stoke, self.sched1, self.sched2, epoch
            )

    def run(self, seconds: float) -> loop.Window:
        import jax

        timed = DeadlineLoader(self.loader, self.env, seconds=seconds)
        self._train(timed, epoch=1)
        with self.env.spans.span("fence"):
            jax.block_until_ready(self.stoke.state)
        t_close = time.perf_counter()
        self.env.tracer.stop()
        losses = [float(x) for x in self.losses]
        attempted = len(timed.stamps)
        failed = sum(not math.isfinite(x) for x in losses)
        accum = self.env.cell.workload["job_params"]["grad_accum_steps"]
        return loop.Window(
            seconds=t_close - timed.t_open,
            units=(attempted - failed) * self.images_per_batch,
            steps=(attempted - failed) // accum, batches=attempted,
            attempted=attempted, failed=failed, losses=losses,
            tenths=loop.tenth_rates(
                timed.stamps, timed.t_open, t_close, self.images_per_batch
            ),
            # the host's own pace, the first microbatch of each optimizer
            # step to the next: the driver's loop reads no loss per batch,
            # so the full dispatch queue holds it to the device's pace
            step_units=self.images_per_batch * accum,
            marks=timed.stamps[::accum],
        )

    def check(self, setup: dict, window: loop.Window) -> list:
        problems = stoke_common.check_reference(
            self.env, setup["step0"]["loss"], setup["reference"]["loss"]
        )
        # every accumulation window got its update, by whichever program
        accum = self.env.cell.workload["job_params"]["grad_accum_steps"]
        updates = sum(
            self.env.calls.get(n, 0) for n in ("_jit_apply", "_jit_eager_step")
        )
        if updates != window.batches // accum:
            problems.append(
                f"{updates} updates for {window.batches} microbatches"
            )
        return problems

    def close(self) -> None:
        self.log.close()
        self.loader.shutdown_workers()
        for child in multiprocessing.active_children():
            child.join(timeout=30)
