"""One ``TrainStep`` per call, fed by the repo's device prefetcher: the job
of both GPT-2 cells. The configuration's family gives model, loss, data,
FLOPs and reference; mesh, policy, optimizer and batch are the cell's."""

from __future__ import annotations

from chipbench import loop

STEP_MODULES = ("jit__step",)  # TrainStep._step, as the trace names it
WARM_STEPS = 2


def assemble(cell, family, devices):
    """``(task, mesh, policy, tx)`` of a cell on ``devices``."""
    from pytorch_distributedtraining_tpu import optim, parallel
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    job = cell.workload["job_params"]
    return (
        family.task(cell.config, job),
        make_mesh(MeshSpec(**job["mesh"]), devices=devices),
        getattr(parallel, job["policy"])(),
        optim.adamw(**job["optimizer"]),
    )


def make_step(cell, task, mesh, policy, tx, shardings):
    from pytorch_distributedtraining_tpu.parallel import TrainStep
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    return TrainStep(
        task.loss_fn, tx, mesh, policy, state_shardings=shardings,
        precision=Precision.from_name(cell.workload["job_params"]["precision"]),
    )


def plan(cell, family, devices) -> dict:
    """The step compiled for described ``devices`` (chipbench/plan.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from chipbench.plan import abstract_state, compile_plan
    from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

    task, mesh, policy, tx = assemble(cell, family, devices)
    state, shardings = abstract_state(task.init_fn, tx, mesh, policy)
    step = make_step(cell, task, mesh, policy, tx, shardings)
    job = cell.workload["job_params"]
    tokens = jax.ShapeDtypeStruct(
        (job["batch"], job["seq"]), jnp.int32,
        sharding=NamedSharding(mesh, batch_spec(mesh)),
    )
    with mesh:
        return {"train_step": compile_plan(
            step._jitted, state, (tokens, tokens), jnp.float32(1.0)
        )}


class Job:
    def __init__(self, env):
        self.env = env

    def setup(self) -> dict:
        import jax

        from pytorch_distributedtraining_tpu import parallel
        from pytorch_distributedtraining_tpu.data.prefetch import (
            DevicePrefetcher,
        )
        from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

        env = self.env
        task, self.mesh, policy, tx = assemble(
            env.cell, env.family, env.devices
        )
        self.task = task
        self.state, shardings = parallel.create_train_state(
            init_fn=task.init_fn, tx=tx, mesh=self.mesh, policy=policy,
            rng=jax.random.PRNGKey(env.seed),
        )
        self.step = make_step(env.cell, task, self.mesh, policy, tx, shardings)
        self.source = DevicePrefetcher(
            task.batches(env.seed), self.mesh, batch_spec(self.mesh), depth=2
        )
        first = next(self.source)
        reference = task.reference(self.state.params, first)  # before step 0
        with self.mesh:
            self.state, metrics = self.step(self.state, first)
            step0 = {k: float(metrics[k]) for k in ("loss", "grad_norm")}
            for _ in range(WARM_STEPS - 1):
                self.state, metrics = self.step(self.state, next(self.source))
            jax.block_until_ready(self.state)
        env.counters["flops_per_step"] = task.flops_per_step
        return {"step0": step0, "reference": reference}

    def run(self, seconds: float) -> loop.Window:
        import jax

        spans = self.env.spans

        def dispatch(i):
            with spans.span("input_wait"):
                batch = next(self.source)
            with spans.span("step_call"):
                self.state, metrics = self.step(self.state, batch)
            return metrics["loss"]

        with self.mesh:
            return loop.run_steps(
                dispatch, lambda: jax.block_until_ready(self.state), seconds,
                self.task.units_per_step, self.env.tracer, spans,
            )

    def check(self, setup: dict, window: loop.Window) -> list:
        """Reasons why the run is not correct; empty when it is."""
        tol = self.env.cell.workload["tolerance"]
        step0, ref = setup["step0"], setup["reference"]
        problems = []
        if abs(step0["loss"] - ref["loss"]) > tol["loss_abs"]:
            problems.append(f"step-0 loss {step0['loss']} vs {ref['loss']}")
        rel = abs(step0["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"]
        if rel > tol["grad_norm_rel"]:
            problems.append(
                f"step-0 grad_norm {step0['grad_norm']} vs {ref['grad_norm']}"
            )
        return problems

    def close(self) -> None:
        self.source.close()
