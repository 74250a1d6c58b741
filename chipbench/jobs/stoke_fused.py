"""``stoke.fused_step(inputs, targets)`` on batches already on the device:
the facade's one-program path, past the eager surface and the loader.

The facade hands ``TrainStep`` its ``grad_accum_steps``, and ``TrainStep``
splits ONE call's batch into that many microbatches inside the program
(``parallel/step.py _split_microbatches``; it does not accumulate over
calls). So a call is given the images of one optimizer step of the
stoke-loop cell: ``grad_accum_steps`` x ``batch_size_per_device``.
"""

from __future__ import annotations

from chipbench import loop, stoke_common

STEP_MODULES = ("jit__step",)  # the TrainStep behind fused_step
RESIDENT_BATCHES = 8  # distinct batches kept on the device, taken in turn
WARM_STEPS = 2

plan = stoke_common.plan


class Job:
    def __init__(self, env):
        self.env = env

    def setup(self) -> dict:
        import jax
        import numpy as np

        from pytorch_distributedtraining_tpu.data import SyntheticSRDataset

        env, job = self.env, self.env.cell.workload["job_params"]
        self.stoke, module, loss = stoke_common.build_stoke(env, [])
        per_call = (
            job["grad_accum_steps"] * job["batch_size_per_device"]
            * len(env.devices)
        )
        self.images_per_call = per_call
        data = SyntheticSRDataset(
            n=RESIDENT_BATCHES * per_call, lr_size=job["lr_size"],
            scale=env.cell.config["upscale"], seed=env.seed,
        )
        self.batches = []
        for b in range(RESIDENT_BATCHES):
            pairs = [data[b * per_call + i] for i in range(per_call)]
            self.batches.append(tuple(
                self.stoke._shard_batch(np.stack(part))
                for part in zip(*pairs)
            ))
        first = self.batches[0]
        self.stoke.init(first[0])
        reference = env.family.reference_loss(
            module, loss, self.stoke.state.params, *first
        )
        metrics = self.stoke.fused_step(*first)
        step0 = float(metrics["loss"])
        for i in range(1, WARM_STEPS):
            self.stoke.fused_step(*self.batches[i])
        jax.block_until_ready(self.stoke.state)
        env.counters["flops_per_step"] = (
            env.family.train_flops_per_image(env.cell.config, job["lr_size"])
            * per_call
        )
        return {"step0": {"loss": step0}, "reference": {"loss": reference}}

    def run(self, seconds: float) -> loop.Window:
        import jax

        spans = self.env.spans

        def dispatch(i):
            with spans.span("facade.fused_step"):
                metrics = self.stoke.fused_step(
                    *self.batches[i % RESIDENT_BATCHES]
                )
            return metrics["loss"]

        return loop.run_steps(
            dispatch, lambda: jax.block_until_ready(self.stoke.state),
            seconds, self.images_per_call, self.env.tracer, spans,
        )

    def check(self, setup: dict, window: loop.Window) -> list:
        problems = stoke_common.check_reference(
            self.env, setup["step0"]["loss"], setup["reference"]["loss"]
        )
        calls = self.env.calls.get(stoke_common.FUSED_PROGRAM, 0)
        if calls != window.batches:
            problems.append(f"{calls} programs for {window.batches} batches")
        return problems

    def close(self) -> None:
        pass
