"""The two readings a tolerance of ``smallthinker-21b-a3b.train-16k`` lies
between (PR 32; the pattern is ``glm4_precision_readings.py``): what the
timed step gives at step 0 against the float32 reference, and what the
reference itself gives when computed one precision lower (fp8 e4m3 matmul
operands; a bf16 router), which the cell has to refuse. Same weights, same
batch, the cell's sizes, on the chip:

    chiprun -- python3 benchmarks/smallthinker_precision_readings.py [seed [reading ...]]

(``reading`` picks among "bf16 operands", "bf16 router", "fp8", "layers",
"attention", "kernel": a float32 pass of 16,384 tokens compiles for a minute each; all
by default.)

One JSON line per reading: loss, gradient norm, and their distance from the
float32 reference (absolute for the loss, relative for the norm, as
``jobs/trainstep.py`` compares them). "layers": the expert layers alone, as
``jobs/trainstep_counted.py`` holds them (``family.expert_layer_distances``:
each layer on the inputs the program gave it): the program's own distances,
then the reference's layers computed one precision lower on those inputs.
"attention": the attention cores alone, as ``jobs/
trainstep_attention_checked.py`` holds them (``family.attention_distances``:
each core on the q, k, v the program gave it): the program's own distances,
then the reference's attention computed with bf16 and with fp8 operands, and
under a band that is a key short, a key long, and not there at all.
"kernel": the banded kernel alone at the cell's shapes and blocks on inputs
on which ONE key matters (q, k, v and the output's cotangent drawn normal(0,
1): scores of unit size; the random model's own q and k are nearly alike
for every token, and a band a key off moves its output by less than bf16
rounds it), output and all three gradients against the reference's chunked
attention, per layer kind, beside what the reference reads a key short."""

from __future__ import annotations

import functools
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)

LOWER = {
    "bf16 operands": {"operands": "bfloat16"},
    "bf16 router": {"router": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
}


def program_probe(cell, family, seed):
    """``(params, probe)``: the model's forward pass as the step runs it."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.smallthinker import (
        MOE_PROBE, SmallThinker,
    )
    from pytorch_distributedtraining_tpu.precision import Policy

    job = cell.workload["job_params"]
    task = family.task(cell.config, job)
    params, _ = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(next(task.batches(seed))[0])
    model = SmallThinker(
        family.model_config(cell.config, job),
        interpret=job.get("interpret", False),
    )
    cast = Policy.from_name(job["precision"]).cast_to_compute
    return params, jax.jit(lambda p, t: model.apply(
        {"params": cast(p)}, t, mutable=[MOE_PROBE]
    )[1][MOE_PROBE])(params, tokens)


def layer_readings(cell, family, reference, arch, params, probe, seed):
    import jax
    import jax.numpy as jnp

    operands = jnp.dtype(cell.workload["job_params"]["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.expert_layer_distances, reference, arch, operands
    ))

    def lower(how, params, probe):
        """The reference's layers, computed lower, as a probe."""
        out = {}
        with jax.default_matmul_precision("highest"):
            for name, layer in probe.items():
                x = layer["moe"]["router_input"].astype(jnp.float32)
                u = layer["moe"]["input"].astype(jnp.float32)
                w_r = params[name]["router"]["kernel"].astype(operands).astype(
                    jnp.float32
                )  # as the policy hands it over
                sel, w = reference.route(x, w_r, arch, how.get("router"))
                out[name] = {"moe": {
                    "router_input": x, "input": u, "picks": sel,
                    "scores": reference.router_logits(
                        x, w_r, how.get("router")
                    ),
                    "output": reference.expert_layer(
                        u, params[name]["moe"], sel, w, arch,
                        how.get("operands"),
                    ),
                }}
        return out

    for name, how in {"the program's expert layers": None, **LOWER}.items():
        layers = probe if how is None else jax.jit(functools.partial(
            lower, {k: jnp.dtype(v) for k, v in how.items()}
        ))(params, probe)
        print(json.dumps({
            "reading": f"layers: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(params, layers).items()},
        }), flush=True)


ATTENTION_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
    "a band a key short": {"window": -1},
    "a band a key long": {"window": 1},
    "no band: causal alone": {"window": None},
}


def attention_readings(family, reference, arch, chunk, probe, seed):
    """``probe``: the program's, as ``layer_readings`` took it."""
    import jax
    import jax.numpy as jnp

    distances = jax.jit(functools.partial(
        family.attention_distances, reference, arch, chunk
    ))

    def faulty(how, probe):
        """The reference's cores under a fault, as a probe."""
        out = {}
        with jax.default_matmul_precision("highest"):
            for name, layer in probe.items():
                windowed = arch["windowed"][int(name.rsplit("_", 1)[1])]
                window = arch["window"] if windowed else None
                if windowed and "window" in how:
                    window = how["window"] and window + how["window"]
                operands = how.get("operands")
                out[name] = {"attn": {**layer["attn"], "output": (
                    reference.banded_attention(
                        *(layer["attn"][x].astype(jnp.float32) for x in "qkv"),
                        window, chunk, operands and jnp.dtype(operands),
                    )
                )}}
        return out

    for name, how in {"the program's cores": None, **ATTENTION_FAULTS}.items():
        cores = probe if how is None else jax.jit(
            functools.partial(faulty, how)
        )({k: {"attn": v["attn"]} for k, v in probe.items()})
        print(json.dumps({
            "reading": f"attention: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(cores).items()},
        }), flush=True)


def kernel_readings(cell, family, reference, seed):
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.smallthinker import (
        ATTENTION_BLOCK,
    )
    from pytorch_distributedtraining_tpu.ops.pallas_attn import (
        make_flash_attn_fn,
    )

    job = cell.workload["job_params"]
    cfg = family.model_config(cell.config, job)
    t, chunk = job["seq"], job["reference_query_chunk"]
    block = min(ATTENTION_BLOCK, t)
    kernel = make_flash_attn_fn(
        bq=block, bk=block, interpret=job.get("interpret", False)
    )
    shapes = [(job["batch"], t, heads, cfg.head_dim) for heads in (
        cfg.num_attention_heads, cfg.num_key_value_heads,
        cfg.num_key_value_heads, cfg.num_attention_heads,
    )]
    q, k, v, g = (
        jax.random.normal(key, shape, jnp.float32).astype(cfg.dtype)
        for key, shape in zip(jax.random.split(jax.random.PRNGKey(seed), 4), shapes)
    )

    def outputs(attend, q, k, v):
        """The output and the gradients of sum(out * g) for q, k, v."""
        out, pull = jax.vjp(attend, q, k, v)
        return (out, *pull(g.astype(out.dtype)))

    def plain(window, q, k, v):
        with jax.default_matmul_precision("highest"):
            return reference.banded_attention(q, k, v, window, chunk)

    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)
    )
    in32 = [a.astype(jnp.float32) for a in (q, k, v)]
    for kind, window in (
        ("attention_global", None),
        ("attention_sliding", cfg.sliding_window_size),
    ):
        want = jax.jit(functools.partial(outputs, functools.partial(
            plain, window
        )))(*in32)
        got = jax.jit(functools.partial(outputs, functools.partial(
            kernel, window=window
        )))(q, k, v)
        line = {"reading": f"kernel: {kind}", "seed": seed, "window": window}
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            line[f"{name}_rel"] = rel(a, b)
        if window is not None:
            short = jax.jit(functools.partial(outputs, functools.partial(
                plain, window - 1
            )))(*in32)
            for name, a, b in zip(("out", "dq", "dk", "dv"), short, want):
                line[f"{name}_rel_a_key_short"] = rel(a, b)
        print(json.dumps(line), flush=True)


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
    base = None
    for name, how in {"float32 (the reference)": {}, **LOWER}.items():
        if how and wanted and not any(w in name for w in wanted):
            continue
        fn = jax.jit(functools.partial(
            reference.loss_and_grad_norm, arch=arch,
            chunk=job["reference_query_chunk"],
            **{k: jnp.dtype(v) for k, v in how.items()},
        ))
        loss, gnorm = (float(v) for v in fn(state.params, *batch))
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
    from chipbench.reference import smallthinker as reference
    from pytorch_distributedtraining_tpu import runtime

    seed = int(argv[0]) if argv else 3200000031
    wanted = argv[1:]
    runtime.initialize()
    cell = cells.load_cell("smallthinker-21b-a3b.train-16k")
    family = cells.load_module("families", cell.config["family"], cell.roots)
    arch = reference.arch_of(cell.config)
    if not wanted or set(wanted) - {"layers", "attention", "kernel"}:
        whole_model_readings(cell, family, reference, arch, seed, wanted)
    if not wanted or "kernel" in wanted:
        kernel_readings(cell, family, reference, seed)
    probed = {"layers", "attention"}
    if not wanted or probed & set(wanted):
        params, probe = program_probe(cell, family, seed)
        if not wanted or "layers" in wanted:
            layer_readings(cell, family, reference, arch, params, probe, seed)
        if not wanted or "attention" in wanted:
            attention_readings(
                family, reference, arch,
                cell.workload["job_params"]["reference_query_chunk"], probe,
                seed,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
