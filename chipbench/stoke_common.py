"""What the two Stoke jobs share: the facade built as
``drivers/stoke_ddp.main`` builds it, with the benchmark's counters and
spans around it (a subclass in the benchmark's own files; the program is
not edited)."""

from __future__ import annotations

# the facade's compiled training programs: the fused eager window, or the
# split path's grad program and update program (chip_smoke.FACADE_PROGRAMS)
FACADE_PROGRAMS = ("_jit_eager_step", "_jit_loss_grad", "_jit_apply")
FUSED_PROGRAM = "fused_step_program"  # the TrainStep behind fused_step
FACADE_CALLS = ("model", "loss", "backward", "step")  # and the loss sync, below


def bench_stoke_class(base, env, losses: list):
    """``base`` (the driver's ``Stoke``) with every call of a training
    program counted, every facade call of the loop under a host span, and
    each synced microbatch loss kept as the device scalar it is."""
    calls, spans = env.calls, env.spans

    def counted(name, program):
        def call(*args, **kwargs):
            calls[name] = calls.get(name, 0) + 1
            return program(*args, **kwargs)

        return call

    def spanned(name):
        method = getattr(base, name)

        def call(self, *args, **kwargs):
            with spans.span("facade." + name):
                return method(self, *args, **kwargs)

        return call

    class BenchStoke(base):
        def _build_jits(self):
            super()._build_jits()
            for name in FACADE_PROGRAMS:
                setattr(self, name, counted(name, getattr(self, name)))

        def _build_fused(self):
            if self._fused is None:
                self._fused = counted(FUSED_PROGRAM, super()._build_fused())
            return self._fused

        def detach_and_sync_loss(self, loss):
            with spans.span("facade.detach_and_sync_loss"):
                out = super().detach_and_sync_loss(loss)
            losses.append(out)
            return out

    for name in FACADE_CALLS:
        setattr(BenchStoke, name, spanned(name))
    return BenchStoke


def build_stoke(env, losses: list, instrumented: bool = True):
    """``(stoke, module, loss)`` as the driver's ``main`` builds them: bf16,
    DDP + OSS + SDDP, accumulate by two, clip, ``feat_loss``, AdamW."""
    from drivers import stoke_ddp
    from pytorch_distributedtraining_tpu.losses import feat_loss
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    job = env.cell.workload["job_params"]
    module = env.family.model(env.cell.config)
    optimizer = stoke_ddp.StokeOptimizer(
        optimizer="AdamW",
        optimizer_kwargs={
            "lr": job["lr"], "betas": tuple(job["betas"]), "eps": 1e-8,
            "weight_decay": job["weight_decay"],
        },
    )
    cls = stoke_ddp.Stoke
    if instrumented:
        cls = bench_stoke_class(cls, env, losses)
    stoke = cls(
        model=module, verbose=True, optimizer=optimizer, loss=feat_loss,
        batch_size_per_device=job["batch_size_per_device"], gpu=True,
        fp16=job["fp16"],
        distributed=stoke_ddp.DistributedOptions.ddp.value,
        fairscale_oss=True, fairscale_sddp=True,
        grad_accum_steps=job["grad_accum_steps"],
        configs=[
            stoke_ddp.AMPConfig(init_scale=2.0**14),
            stoke_ddp.DDPConfig(
                local_rank=None, convert_to_sync_batch_norm=True
            ),
            stoke_ddp.FairscaleOSSConfig(broadcast_fp16=True),
        ],
        grad_clip=stoke_ddp.ClipGradNormConfig(
            max_norm=job["grad_clip"], norm_type=2.0
        ),
        # the facade's own default mesh, over the cell's devices
        mesh=make_mesh(
            MeshSpec.zero(len(env.devices)), devices=env.devices
        ),
        rng_seed=env.seed,
    )
    return stoke, module, feat_loss


def check_reference(env, step0_loss: float, reference: float) -> list:
    tol = env.cell.workload["tolerance"]["loss_abs"]
    if abs(step0_loss - reference) > tol:
        return [f"step-0 loss {step0_loss} vs float32 {reference}"]
    return []


def plan(cell, family, devices) -> dict:
    """The facade's training programs compiled for described ``devices``
    (chipbench/plan.py): the split path's grad and update programs and the
    one program behind ``fused_step``. The facade is given shapes with
    shardings where ``init()`` would place arrays."""
    import types

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from chipbench.plan import abstract_state, compile_plan
    from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

    job = cell.workload["job_params"]
    env = types.SimpleNamespace(cell=cell, family=family, devices=devices, seed=0)
    stoke, module, _ = build_stoke(env, [], instrumented=False)
    size, scale = job["lr_size"], cell.config["upscale"]

    def init_fn(rng):
        variables = dict(module.init(rng, jnp.zeros((1, size, size, 3))))
        return variables.pop("params"), variables

    state, stoke._shardings = abstract_state(
        init_fn, stoke._tx, stoke.mesh, stoke.policy
    )
    stoke._state = state
    stoke._build_jits()
    data = NamedSharding(stoke.mesh, batch_spec(stoke.mesh))

    def images(n, side):
        return jax.ShapeDtypeStruct((n, side, side, 3), jnp.float32, sharding=data)

    micro = job["batch_size_per_device"] * len(devices)
    step = micro * job["grad_accum_steps"]
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=p.sharding),
        state.params,
    )
    with stoke.mesh:
        return {
            "grad_program": compile_plan(
                stoke._jit_loss_grad, state.params, state.model_state,
                images(micro, size), images(micro, size * scale), rng, None,
            ),
            "update_program": compile_plan(
                stoke._jit_apply, state.params, state.opt_state, None, grads,
                jnp.float32(1.0),
            ),
            "fused_step_program": compile_plan(
                stoke._build_fused()._jitted, state,
                (images(step, size), images(step, size * scale)),
                jnp.float32(1.0),
            ),
        }
