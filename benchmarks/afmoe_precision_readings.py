"""The upper readings of ``trinity-mini.train-8k``'s step-0 limits (PR 35; the
pattern is ``smallthinker_precision_readings.py``): what the timed program's
expert layers and attention cores read against the float32 reference ON THEIR
OWN INPUTS, and what the reference's layers themselves read when computed
one precision lower or with a fault planted, which the cell has to refuse.
Same weights, same batch, the cell's sizes, on the chip:

    chiprun -- python3 benchmarks/afmoe_precision_readings.py [seed [reading ...]]

(``reading`` picks among "layers" and "attention"; both by default. The
whole model's first steps one precision lower are ``python3 -m
chipbench.control``'s.)

One JSON line per reading. "layers": the expert layers alone, as
``jobs/trainstep_counted.py`` holds them (``family.expert_layer_distances``:
each layer on the input the program gave it): the program's own distances,
then the reference's layers with bf16 operands, a bf16 router and fp8 e4m3
operands on those inputs. "attention": the attention cores alone, as
``jobs/trainstep_attention_checked.py`` holds them
(``family.attention_distances``: each core on the q, k, v the program gave
it, after the head norms and rotary): the program's own distances, then the
reference's attention with bf16 and with fp8 operands, under a band that is
a key short, a key long and not there at all, and on a sliding layer's q and
k with the rotary taken off again (a core that forgot its positions)."""

from __future__ import annotations

import functools
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)

CELL = "trinity-mini.train-8k"
LOWER = {
    "bf16 operands": {"operands": "bfloat16"},
    "bf16 router": {"router": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
}
ATTENTION_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
    "a band a key short": {"window": -1},
    "a band a key long": {"window": 1},
    "no band: causal alone": {"window": None},
    "rotary left off the sliding layers": {"unturned": True},
}


def program_probe(cell, family, seed):
    """``(params, zero biases, probe)``: the model's forward pass as the
    step runs it (the policy's cast, the kernels, the cell's sizes)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.afmoe import (
        MOE_PROBE, ROUTER_STATE, Afmoe,
    )
    from pytorch_distributedtraining_tpu.precision import Policy

    job = cell.workload["job_params"]
    task = family.task(cell.config, job)
    params, model_state = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(next(task.batches(seed))[0])
    model = Afmoe(
        family.model_config(cell.config, job),
        interpret=job.get("interpret", False),
    )
    cast = Policy.from_name(job["precision"]).cast_to_compute
    probe = jax.jit(lambda p, b, t: model.apply(
        {"params": cast(p), ROUTER_STATE: b}, t, mutable=[MOE_PROBE]
    )[1][MOE_PROBE])(params, model_state[ROUTER_STATE], tokens)
    return params, model_state[ROUTER_STATE], probe


def lowered_layers(reference, arch, operands, how, params, bias, probe):
    """The reference's expert layers on the inputs of ``probe`` (its ``moe``
    entries), computed as ``how`` says (``operands`` / ``router``: a dtype to
    round to), as a probe. The router's weights come as the policy hands
    them over (cast to the cell's ``operands``)."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            x = layer["moe"]["input"].astype(jnp.float32)
            p, b = params[name]["moe"], bias[name]["moe"]["bias"]
            w_r = p["router"].astype(operands).astype(jnp.float32)
            router = how.get("router")
            out[name] = {"moe": {
                "input": x,
                "scores": reference.router_scores(x, w_r, router),
                "picks": reference.route(x, w_r, b, arch, router)[0],
                "output": reference.expert_layer(
                    x, {**p, "router": w_r}, b, arch, how.get("operands"),
                    router,
                ),
            }}
    return out


def layer_readings(cell, family, reference, arch, params, bias, probe, seed):
    import jax
    import jax.numpy as jnp

    operands = jnp.dtype(cell.workload["job_params"]["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.expert_layer_distances, reference, arch, operands
    ))
    probe = {k: {"moe": v["moe"]} for k, v in probe.items() if "moe" in v}
    for name, how in {"the program's expert layers": None, **LOWER}.items():
        layers = probe if how is None else jax.jit(functools.partial(
            lowered_layers, reference, arch, operands,
            {k: jnp.dtype(v) for k, v in how.items()},
        ))(params, bias, probe)
        print(json.dumps({
            "reading": f"layers: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(params, bias, layers).items()},
        }), flush=True)


def unturned(reference, x, theta):
    """``x`` with ``reference.rotary`` taken off again: a turn by the
    negative angle is the turn of the mirrored pair, mirrored back."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    mirror = jnp.concatenate([jnp.ones(half), -jnp.ones(half)]).astype(x.dtype)
    return reference.rotary(x * mirror, theta) * mirror


def faulty_cores(reference, arch, chunk, how, probe):
    """The reference's attention on the q, k, v of ``probe`` (its ``attn``
    entries) with the fault ``how`` planted, as a probe: ``operands`` (a
    dtype to round to), ``window`` (keys added to a sliding layer's band,
    None: no band) or ``unturned`` (the sliding layers' rotary taken off)."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            windowed = arch["windowed"][int(name.rsplit("_", 1)[1])]
            window = arch["window"] if windowed else None
            q, k, v = (layer["attn"][x].astype(jnp.float32) for x in "qkv")
            if windowed and "window" in how:
                window = how["window"] and window + how["window"]
            if windowed and how.get("unturned"):
                q, k = (unturned(reference, x, arch["theta"]) for x in (q, k))
            operands = how.get("operands")
            out[name] = {"attn": {**layer["attn"], "output": (
                reference.banded_attention(
                    q, k, v, window, chunk, operands and jnp.dtype(operands)
                )
            )}}
    return out


def attention_readings(family, reference, arch, chunk, probe, seed):
    """``probe``: the program's, as ``program_probe`` took it."""
    import jax

    distances = jax.jit(functools.partial(
        family.attention_distances, reference, arch, chunk
    ))
    probe = {k: {"attn": v["attn"]} for k, v in probe.items()}
    for name, how in {"the program's cores": None, **ATTENTION_FAULTS}.items():
        cores = probe if how is None else jax.jit(functools.partial(
            faulty_cores, reference, arch, chunk, how
        ))(probe)
        print(json.dumps({
            "reading": f"attention: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(cores).items()},
        }), flush=True)


def main(argv):
    from chipbench import cells
    from chipbench.reference import afmoe as reference
    from pytorch_distributedtraining_tpu import runtime

    seed = int(argv[0]) if argv else 3500000035
    wanted = set(argv[1:]) or {"layers", "attention"}
    runtime.initialize()
    cell = cells.load_cell(CELL)
    family = cells.load_module("families", cell.config["family"], cell.roots)
    arch = reference.arch_of(cell.config)
    params, bias, probe = program_probe(cell, family, seed)
    if "layers" in wanted:
        layer_readings(cell, family, reference, arch, params, bias, probe, seed)
    if "attention" in wanted:
        attention_readings(
            family, reference, arch,
            cell.workload["job_params"]["reference_query_chunk"], probe, seed,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
