"""The two readings a tolerance of ``glm-4.7-flash.train-4k`` lies between
(PR 27): what the timed step gives at step 0 against the float32 reference,
and what the reference itself gives when computed one precision lower (fp8
e4m3 matmul operands; a bf16 router), which the cell has to refuse. Same
weights, same batch, the cell's sizes, on the chip:

    chiprun -- python3 benchmarks/glm4_precision_readings.py [seed [reading ...]]

(``reading`` picks among "bf16 operands", "bf16 router", "fp8", "layers": a
float32 pass of 2 x 4,096 tokens compiles for a minute each; all by default.)

One JSON line per reading: loss, gradient norm, and their distance from the
float32 reference (absolute for the loss, relative for the norm, as
``jobs/trainstep.py`` compares them). "layers": the expert layers alone, as
``jobs/trainstep_counted.py`` holds them (``family.expert_layer_distances``:
each layer on the input the program gave it): the program's own distances,
then the reference's layers computed one precision lower on those inputs."""

from __future__ import annotations

import functools
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)


def layer_readings(cell, family, reference, arch, seed):
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.glm4_moe_lite import (
        MOE_PROBE, Glm4MoeLite,
    )
    from pytorch_distributedtraining_tpu.precision import Policy

    job = cell.workload["job_params"]
    task = family.task(cell.config, job)
    params, state = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(next(task.batches(seed))[0])
    bias = state["router_state"]
    model = Glm4MoeLite(
        family.model_config(cell.config, job),
        interpret=job.get("interpret", False),
    )
    cast = Policy.from_name(job["precision"]).cast_to_compute
    probe = jax.jit(lambda p, t: model.apply(
        {"params": cast(p), "router_state": bias}, t, mutable=[MOE_PROBE]
    )[1][MOE_PROBE])(params, tokens)
    operands = jnp.dtype(job["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.expert_layer_distances, reference, arch, operands
    ))

    def lower(how, params, bias, probe):
        """The reference's layers, computed lower, as a probe."""
        router = how.get("router")
        out = {}
        with jax.default_matmul_precision("highest"):
            for name, layer in probe.items():
                x = layer["moe"]["input"].astype(jnp.float32)
                p, b = params[name]["moe"], bias[name]["moe"]["bias"]
                w_r = p["router"].astype(operands).astype(jnp.float32)
                p = {**p, "router": w_r}  # as the policy hands it over
                out[name] = {"moe": {
                    "input": x,
                    "scores": reference.router_scores(x, w_r, router),
                    "picks": reference.route(x, w_r, b, arch, router)[0],
                    "output": reference.expert_layer(x, p, b, arch, **how),
                }}
        return out

    readings = {
        "the program's expert layers": None,
        "bf16 operands": {"operands": jnp.bfloat16},
        "bf16 router": {"router": jnp.bfloat16},
        "fp8 e4m3 operands": {"operands": jnp.float8_e4m3fn},
    }
    for name, how in readings.items():
        layers = probe if how is None else jax.jit(
            functools.partial(lower, how)
        )(params, bias, probe)
        found = distances(params, bias, layers)
        print(json.dumps({
            "reading": f"layers: {name}", "seed": seed,
            **{k: float(v) for k, v in found.items()},
        }), flush=True)


def whole_model_readings(cell, family, reference, arch, seed, wanted):
    import jax
    import jax.numpy as jnp

    from chipbench.jobs import trainstep
    from pytorch_distributedtraining_tpu import parallel

    job = cell.workload["job_params"]
    task, mesh, policy, tx = trainstep.assemble(
        cell, family, jax.devices()[: cell.chips]
    )
    state, shardings = parallel.create_train_state(
        init_fn=task.init_fn, tx=tx, mesh=mesh, policy=policy,
        rng=jax.random.PRNGKey(seed),
    )
    batch = jax.tree.map(jnp.asarray, next(task.batches(seed)))
    bias = state.model_state["router_state"]
    readings = {
        "float32 (the reference)": {},
        "bf16 operands": {"operands": jnp.bfloat16},
        "bf16 router": {"router": jnp.bfloat16},
        "fp8 e4m3 operands": {"operands": jnp.float8_e4m3fn},
    }
    base = None
    for name, how in readings.items():
        if how and wanted and not any(w in name for w in wanted):
            continue
        fn = jax.jit(functools.partial(
            reference.loss_and_grad_norm, arch=arch,
            chunk=job["reference_query_chunk"], **how,
        ))
        loss, gnorm = (float(v) for v in fn(state.params, bias, *batch))
        base = base or (loss, gnorm)
        print(json.dumps({
            "reading": name, "seed": seed, "loss": loss, "grad_norm": gnorm,
            "loss_abs": abs(loss - base[0]),
            "grad_norm_rel": abs(gnorm - base[1]) / base[1],
        }), flush=True)
    step = trainstep.make_step(cell, task, mesh, policy, tx, shardings)
    with mesh:
        state, metrics = step(state, batch)
    loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
    print(json.dumps({
        "reading": "the timed step (bf16 policy, kernels)", "seed": seed,
        "loss": loss, "grad_norm": gnorm, "loss_abs": abs(loss - base[0]),
        "grad_norm_rel": abs(gnorm - base[1]) / base[1],
    }), flush=True)


def main(argv):
    from chipbench import cells
    from chipbench.reference import glm4_moe_lite as reference
    from pytorch_distributedtraining_tpu import runtime

    seed = int(argv[0]) if argv else 2700000031
    wanted = argv[1:]
    runtime.initialize()
    cell = cells.load_cell("glm-4.7-flash.train-4k")
    family = cells.load_module("families", cell.config["family"], cell.roots)
    arch = reference.arch_of(cell.config)
    if wanted != ["layers"]:
        whole_model_readings(cell, family, reference, arch, seed, wanted)
    if not wanted or "layers" in wanted:
        layer_readings(cell, family, reference, arch, seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
