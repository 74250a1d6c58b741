"""The upper readings of ``lfm2-8b-a1b.train-4k``'s step-0 limits (the pattern
is ``afmoe_precision_readings.py``): what the timed program's expert layers,
attention cores and short convolutions read against the float32 reference ON
THEIR OWN INPUTS, and what the reference's layers themselves read when
computed one precision lower or with a fault planted, which the cell has to
refuse. Same weights, same batch, the cell's sizes, on the chip:

    python3 benchmarks/lfm2_precision_readings.py [seed [reading ...]]  # one chip

(``reading`` picks among "layers", "attention" and "conv"; all by default.
The whole model's first steps one precision lower are ``python3 -m
chipbench.control``'s.)

One JSON line per reading. "layers": the expert layers alone
(``family.expert_layer_distances``), the program's own distances, then the
reference's layers with bf16 operands, a bf16 router and fp8 e4m3 operands.
"attention": the attention cores alone (``family.attention_distances``, each
core on the q, k, v the program gave it), the program's own, then the
reference's attention with bf16 and fp8 e4m3 operands and on q and k with the
rotary taken off again. "conv": the short convolutions alone
(``family.short_conv_distances``, each on the ``bcx`` the program gave it),
the program's own, then the reference's convolution with bf16 and fp8 e4m3
operands, with the taps reversed (``w[:, 0]`` and ``w[:, 2]`` swapped) and
reading a step late (``z_{t+1}`` in place of ``z_t``)."""

from __future__ import annotations

import functools
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)
from afmoe_precision_readings import LOWER, lowered_layers, unturned

CELL = "lfm2-8b-a1b.train-4k"
ATTENTION_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
    "rotary left off": {"unturned": True},
}
CONV_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
    "taps reversed": {"reversed": True},
    "a step late": {"late": True},
}


def program_probe(cell, family, seed):
    """``(params, zero biases, probe)``: the model's forward pass as the
    step runs it (the policy's cast, the kernels, the cell's sizes)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.lfm2_moe import (
        MOE_PROBE, ROUTER_STATE, Lfm2Moe,
    )
    from pytorch_distributedtraining_tpu.precision import Policy

    job = cell.workload["job_params"]
    task = family.task(cell.config, job)
    params, model_state = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(next(task.batches(seed))[0])
    model = Lfm2Moe(
        family.model_config(cell.config, job),
        interpret=job.get("interpret", False),
    )
    cast = Policy.from_name(job["precision"]).cast_to_compute
    probe = jax.jit(lambda p, b, t: model.apply(
        {"params": cast(p), ROUTER_STATE: b}, t, mutable=[MOE_PROBE]
    )[1][MOE_PROBE])(params, model_state[ROUTER_STATE], tokens)
    return params, model_state[ROUTER_STATE], probe


def pick(probe, key, as_):
    return {k: {as_: v[key]} for k, v in probe.items() if key in v}


def layer_readings(cell, family, reference, arch, params, bias, probe, seed):
    import jax
    import jax.numpy as jnp

    operands = jnp.dtype(cell.workload["job_params"]["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.expert_layer_distances, reference, arch, operands
    ))
    probe = pick(probe, "moe", "moe")
    for name, how in {"the program's expert layers": None, **LOWER}.items():
        layers = probe if how is None else jax.jit(functools.partial(
            lowered_layers, reference, arch, operands,
            {k: jnp.dtype(v) for k, v in how.items()},
        ))(params, bias, probe)
        print(json.dumps({
            "reading": f"layers: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(params, bias, layers).items()},
        }), flush=True)


def faulty_cores(reference, arch, chunk, how, probe):
    """The reference's attention on the q, k, v of ``probe`` (its ``attn``
    entries) with the fault ``how`` planted, as a probe: ``operands`` (a
    dtype to round to) or ``unturned`` (the rotary taken off q and k)."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            q, k, v = (layer["attn"][x].astype(jnp.float32) for x in "qkv")
            if how.get("unturned"):
                q, k = (unturned(reference, x, arch["theta"]) for x in (q, k))
            operands = how.get("operands")
            out[name] = {"attn": {**layer["attn"], "output": (
                reference.banded_attention(
                    q, k, v, None, chunk, operands and jnp.dtype(operands)
                )
            )}}
    return out


def faulty_convs(reference, operands, how, params, probe):
    """The reference's short convolution on the ``bcx`` of ``probe`` (its
    ``conv`` entries, the taps as the policy hands them over) with the fault
    ``how`` planted, as a probe: ``operands`` (a dtype to round to),
    ``reversed`` (tap ``k`` in place of tap ``L - 1 - k``) or ``late`` (``B``
    and ``X`` a step early, so that step t reads ``z_{t+1}``)."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            bcx = layer["conv"]["bcx"].astype(jnp.float32)
            taps = params[name]["conv"]["weight"].astype(operands).astype(
                jnp.float32
            )
            if how.get("reversed"):
                taps = taps[:, ::-1]
            if how.get("late"):
                d = taps.shape[0]
                ahead = jnp.pad(bcx[:, 1:], ((0, 0), (0, 1), (0, 0)))
                bcx = jnp.concatenate(
                    [ahead[..., :d], bcx[..., d:2 * d], ahead[..., 2 * d:]], -1
                )
            lower = how.get("operands")
            out[name] = {"conv": {**layer["conv"], "output": reference.short_conv(
                bcx, taps, lower and jnp.dtype(lower)
            )}}
    return out


def attention_readings(family, reference, arch, chunk, probe, seed):
    import jax

    distances = jax.jit(functools.partial(
        family.attention_distances, reference, arch, chunk
    ))
    probe = pick(probe, "self_attn", "attn")
    for name, how in {"the program's cores": None, **ATTENTION_FAULTS}.items():
        cores = probe if how is None else jax.jit(functools.partial(
            faulty_cores, reference, arch, chunk, how
        ))(probe)
        print(json.dumps({
            "reading": f"attention: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(cores).items()},
        }), flush=True)


def conv_readings(cell, family, reference, params, probe, seed):
    import jax
    import jax.numpy as jnp

    operands = jnp.dtype(cell.workload["job_params"]["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.short_conv_distances, reference, operands
    ))
    probe = pick(probe, "conv", "conv")
    for name, how in {"the program's convolutions": None, **CONV_FAULTS}.items():
        convs = probe if how is None else jax.jit(functools.partial(
            faulty_convs, reference, operands, how
        ))(params, probe)
        print(json.dumps({
            "reading": f"conv: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(params, convs).items()},
        }), flush=True)


def main(argv):
    from chipbench import cells
    from chipbench.reference import lfm2_moe as reference
    from pytorch_distributedtraining_tpu import runtime

    seed = int(argv[0]) if argv else 3900000039
    wanted = set(argv[1:]) or {"layers", "attention", "conv"}
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
    if "conv" in wanted:
        conv_readings(cell, family, reference, params, probe, seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
