"""One ``TrainStep`` per call, fed by the repo's device prefetcher: the job
of both GPT-2 cells. The configuration's family gives model, loss, data,
FLOPs and reference; mesh, policy, optimizer and batch are the cell's."""

from __future__ import annotations

from chipbench import first_steps, loop

STEP_MODULES = ("jit__step",)  # TrainStep._step, as the trace names it
WARM_STEPS = 2


def rate(params: dict):
    """The learning rate of a cell's ``job_params.optimizer``: ``lr``, or
    with ``warmup_steps`` an ``optax.Schedule`` that climbs linearly from 0
    at step 0 to ``lr`` at that step and stays there. The warm-up's steps
    before the window count."""
    import optax

    if not params.get("warmup_steps"):
        return params["lr"]
    return optax.linear_schedule(0.0, params["lr"], params["warmup_steps"])


def optimizer(params: dict):
    """``optim.adamw`` of a cell's ``job_params.optimizer`` at its
    ``rate`` (``optim.adamw`` takes a schedule where it takes a number)."""
    from pytorch_distributedtraining_tpu import optim

    rest = {k: v for k, v in params.items() if k not in ("lr", "warmup_steps")}
    return optim.adamw(lr=rate(params), **rest)


def assemble(cell, family, devices):
    """``(task, mesh, policy, tx)`` of a cell on ``devices``."""
    from pytorch_distributedtraining_tpu import parallel
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    job = cell.workload["job_params"]
    return (
        family.task(cell.config, job),
        make_mesh(MeshSpec(**job["mesh"]), devices=devices),
        getattr(parallel, job["policy"])(),
        optimizer(job["optimizer"]),
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

    @property
    def follow(self) -> int:
        """How many of its first steps the cell's reference follows."""
        return self.env.cell.workload.get("follow_steps", 0)

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
        env.counters["flops_per_step"] = task.flops_per_step
        if self.follow:
            return {"first_steps": self.first_steps()}
        first = next(self.source)
        reference = task.reference(self.state.params, first)  # before step 0
        with self.mesh:
            self.state, metrics = self.step(self.state, first)
            step0 = {k: float(metrics[k]) for k in ("loss", "grad_norm")}
            for _ in range(WARM_STEPS - 1):
                self.state, metrics = self.step(self.state, next(self.source))
            jax.block_until_ready(self.state)
        return {"step0": step0, "reference": reference}

    def first_steps(self) -> dict:
        """The step through its first ``follow_steps`` steps, by the
        window's own call and feed, and what the reference is held against
        once the window has closed (``chipbench/first_steps.py``)."""
        import jax

        env = self.env
        optimizer = env.cell.workload["job_params"]["optimizer"]
        kept = []
        with self.mesh:
            for k in range(max(self.follow, WARM_STEPS)):
                self.state, metrics = self.step(self.state, next(self.source))
                kept.append((metrics["loss"], metrics["grad_norm"]))
                if k == 0:
                    grad_leaf = first_steps.first_gradient_norms(
                        self.state.opt_state, optimizer
                    )
                if k == self.follow - 1:
                    update_leaf = first_steps.change_norms(
                        self.state.params, self.task.init_fn, env.seed
                    )
            jax.block_until_ready(self.state)
        kept, grad_leaf, update_leaf = jax.device_get(
            (kept[:self.follow], grad_leaf, update_leaf)
        )
        return {
            "loss": [float(loss) for loss, _ in kept],
            "grad_norm": float(kept[0][1]),
            "grad_leaf": [float(x) for x in grad_leaf],
            "update_leaf": [float(x) for x in update_leaf],
        }

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

    def reference_after_window(self) -> dict:
        """``follow_steps`` cells: the program's state is freed, then the
        reference takes the first steps from the seed; what it reads of
        them, and of the program's layers at step 0 where the family holds
        those to it (``task.layers``)."""
        import jax

        env, task = self.env, self.task
        self.state = None
        ref = first_steps.follow(
            task, env.cell.workload["job_params"]["optimizer"], env.seed,
            self.follow,
        )
        layers = getattr(task, "layers", None)
        if layers is not None:
            params, _ = jax.jit(task.init_fn)(jax.random.PRNGKey(env.seed))
            ref.update(layers(params, next(task.batches(env.seed))))
        return ref

    def check(self, setup: dict, window: loop.Window) -> list:
        """Every number compared goes beside its limit into
        ``counters["compared"]``, from which ``run.py`` decides and which it
        prints; returned are reasons of any other kind why the run is not
        correct."""
        tol = self.env.cell.workload["tolerance"]
        compared = self.env.counters.setdefault("compared", {})
        if self.follow:
            ref = setup["reference"] = self.reference_after_window()
            read = first_steps.compare(setup["first_steps"], ref)
            setup["worst_leaves"] = read.pop("worst")
        else:
            step0, ref = setup["step0"], setup["reference"]
            read = {
                "loss_abs": abs(step0["loss"] - ref["loss"]),
                "grad_norm_rel": abs(
                    step0["grad_norm"] - ref["grad_norm"]
                ) / ref["grad_norm"],
            }
        compared.update({k: [v, tol[k]] for k, v in read.items()})
        return []

    def close(self) -> None:
        self.source.close()
