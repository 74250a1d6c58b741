"""The readings that ``keye-vl-2.0-30b-a3b.train-16k``'s limits lie between:
what the timed program reads against the float32 reference, and what the
reference itself reads when computed one precision lower or with a fault
planted, which the cell has to refuse. Same weights, same batch, the cell's
sizes, on the chip:

    python3 benchmarks/keye_precision_readings.py [seed [reading ...]]  # one chip

One JSON line per reading; ``reading`` picks among "layers", "attention" and
"steps" (all by default; "steps=kl,1024" only those of ``STEP_FAULTS``).

- "layers": the expert layers alone (``family.expert_layer_distances``): the
  program's own distances, then the reference's layers on the same inputs
  with bf16 operands, a bf16 router and fp8 e4m3 operands.
- "attention": the attention layers alone (``family.sparse_distances``, each
  on the q, k, v, qI, kI, w the program gave it): the program's own, then
  the reference PUT IN THE PROGRAM'S PLACE on those inputs, with its own
  selection: bf16 and fp8 e4m3 operands, the indexer's scores rounded to
  bf16, 1,024 keys a query, every causal key (dense attention).
- "steps": the whole model's first ``follow_steps`` steps
  (``first_steps.follow`` and ``compare``, the cell's limits), the
  reference in the program's place with a fault: fp8 e4m3 operands, the
  indexer's scores rounded to bf16, 1,024 keys, dense attention, and the
  KL's target left differentiable. Half the batch and a state left
  unchanged are ``python3 -m chipbench.control``'s."""

from __future__ import annotations

import functools
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)

CELL = "keye-vl-2.0-30b-a3b.train-16k"
LAYER_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "bf16 router": {"router": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
}
ATTENTION_FAULTS = {
    "bf16 operands": {"operands": "bfloat16"},
    "fp8 e4m3 operands": {"operands": "float8_e4m3fn"},
    "scores in bf16": {"scores": "bfloat16"},
    "1,024 keys": {"topk": 1024},
    "dense attention": {"topk": "seq"},
}
STEP_FAULTS = {  # by the short name a command line picks them with
    "fp8": ("fp8 e4m3 operands", {"operands": "float8_e4m3fn"}),
    "scores": ("scores in bf16", {"scores": "bfloat16"}),
    "1024": ("1,024 keys", {"topk": 1024}),
    "dense": ("dense attention", {"topk": "seq"}),
    "kl": ("KL target differentiable", {"kl_target_grad": True}),
}


def _dtypes(how: dict, seq: int) -> dict:
    """``how`` with its dtypes named and ``"seq"`` (every causal key) the
    sequence's length."""
    import jax.numpy as jnp

    return {
        k: jnp.dtype(v) if k in ("operands", "router", "scores")
        else seq if v == "seq" else v
        for k, v in how.items()
    }


def program_probe(cell, family, seed):
    """``(params, probe)``: the model's forward pass as the step runs it
    (the policy's cast, the kernels, the cell's sizes)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.keye_vl2 import MOE_PROBE, KeyeVL2
    from pytorch_distributedtraining_tpu.precision import Policy

    job = cell.workload["job_params"]
    task = family.task(cell.config, job)
    params, _ = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(next(task.batches(seed))[0])
    model = KeyeVL2(family.model_config(cell.config, job),
                    interpret=job.get("interpret", False))
    cast = Policy.from_name(job["precision"]).cast_to_compute
    probe = jax.jit(lambda p, t: model.apply(
        {"params": cast(p)}, t, mutable=[MOE_PROBE]
    )[1][MOE_PROBE])(params, tokens)
    return params, probe


def lowered_layers(reference, arch, operands, how, params, probe):
    """The reference's expert layers on the inputs of ``probe``, computed as
    ``how`` says, as a probe."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            x = layer["moe"]["input"].astype(jnp.float32)
            p = params[name]
            w_r = p["router"]["kernel"].astype(operands).astype(jnp.float32)
            router = how.get("router")
            sel, w = reference.route(x, w_r, arch, router)
            out[name] = {"moe": {
                "router_input": x, "input": x, "picks": sel,
                "scores": reference.router_logits(x, w_r, router),
                "output": reference.expert_layer(
                    x, p["moe"], sel, w, arch, how.get("operands")
                ),
            }}
    return out


def layer_readings(cell, family, reference, arch, params, probe, seed):
    import jax
    import jax.numpy as jnp

    operands = jnp.dtype(cell.workload["job_params"]["compute_dtype"])
    distances = jax.jit(functools.partial(
        family.expert_layer_distances, reference, arch, operands
    ))
    for name, how in {"the program's expert layers": None, **LAYER_FAULTS}.items():
        layers = probe if how is None else jax.jit(functools.partial(
            lowered_layers, reference, arch, operands, _dtypes(how, 0)
        ))(params, probe)
        print(json.dumps({
            "reading": f"layers: {name}", "seed": seed,
            **{k: float(v) for k, v in distances(params, layers).items()},
        }), flush=True)


def in_the_programs_place(reference, arch, how, probe):
    """The four attention distances of the reference computed as ``how``
    says, with ITS OWN selection, against the float32 reference with its
    own, on the inputs of ``probe``: what ``family.sparse_distances`` would
    read of a program that computed so."""
    import jax
    import jax.numpy as jnp

    reads = {k: [] for k in (
        "sparse_attention_rel", "indexer_score_rel", "selection_agree",
        "indexer_kl_abs",
    )}
    topk = how.get("topk", arch["topk"])
    with jax.default_matmul_precision("highest"):
        for _, layer in sorted(probe.items()):
            got = layer["self_attn"]
            f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
            q, k, v, qi, ki, w = (f32(got[n]) for n in ("q", "k", "v", "qi", "ki", "w"))
            b, t = q.shape[:2]
            blk = got["selection"].shape[-1]

            def one(args, k=k, v=v, ki=ki):
                q_c, qi_c, w_c, start = args
                sound = reference.indexer_scores(qi_c, ki, w_c)
                mask = reference.selection(sound, start, arch["topk"])
                out, kl = reference.masked_attention(q_c, k, v, mask, sound)
                lse = jax.nn.logsumexp(jnp.where(mask, sound, -jnp.inf), -1)
                scores = reference.indexer_scores(
                    qi_c, ki, w_c, how.get("operands"), how.get("scores")
                )
                theirs = reference.selection(scores, start, topk)
                out_c, kl_c = reference.masked_attention(
                    q_c, k, v, theirs, scores, how.get("operands")
                )
                lse_c = jax.nn.logsumexp(jnp.where(theirs, scores, -jnp.inf), -1)
                return (out, out_c, kl, kl_c, lse, lse_c,
                        jnp.sum(theirs & mask), jnp.sum(theirs))

            split = lambda x: x.reshape(b, t // blk, blk, *x.shape[2:]).swapaxes(0, 1)  # noqa: E731
            out, out_c, kl, kl_c, lse, lse_c, agree, total = jax.lax.map(
                one, (split(q), split(qi), split(w), jnp.arange(0, t, blk))
            )
            rel = lambda a, ref: jnp.linalg.norm(a - ref) / jnp.linalg.norm(ref)  # noqa: E731
            reads["sparse_attention_rel"].append(rel(out_c, out))
            reads["indexer_score_rel"].append(rel(lse_c, lse))
            reads["selection_agree"].append(
                1.0 - jnp.sum(total - agree) / jnp.sum(total)
            )
            reads["indexer_kl_abs"].append(jnp.abs(jnp.mean(kl_c) - jnp.mean(kl)))
    worst = {k: (jnp.min if k == "selection_agree" else jnp.max)(jnp.stack(v))
             for k, v in reads.items()}
    return worst


def attention_readings(family, reference, arch, probe, seed, seq):
    import jax

    mine = jax.jit(functools.partial(family.sparse_distances, reference, arch))
    print(json.dumps({
        "reading": "attention: the program's", "seed": seed,
        **{k: float(v) for k, v in mine(probe).items()},
    }), flush=True)
    for name, how in ATTENTION_FAULTS.items():
        read = jax.jit(functools.partial(
            in_the_programs_place, reference, arch, _dtypes(how, seq)
        ))(probe)
        print(json.dumps({
            "reading": f"attention: {name}", "seed": seed,
            **{k: float(v) for k, v in read.items()},
        }), flush=True)


def step_readings(cell, family, seed, faults):
    from chipbench import first_steps
    from chipbench.run import over_limit

    job = cell.workload["job_params"]
    task, tol = family.task(cell.config, job), cell.workload["tolerance"]
    steps = cell.workload["follow_steps"]
    reference = first_steps.follow(task, job["optimizer"], seed, steps)
    for name, how in (STEP_FAULTS[f] for f in faults):
        put = first_steps.follow(
            task, job["optimizer"], seed, steps,
            lower=_dtypes(how, job["seq"]),
        )
        read = first_steps.compare(put, reference)
        worst = read.pop("worst")
        compared = {k: [v, tol[k]] for k, v in read.items()}
        print(json.dumps({
            "reading": f"steps: {name}", "seed": seed,
            "correct": not over_limit(compared),
            "compared": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in compared.items()},
            "worst": worst, "loss": put["loss"],
            "reference_loss": reference["loss"],
        }), flush=True)


def main(argv, cell_name=CELL, rehearse=None):
    from chipbench import cells
    from chipbench.reference import keye_vl2 as reference
    from pytorch_distributedtraining_tpu import runtime

    seed = int(argv[0]) if argv else 3420000042
    wanted = {a.split("=")[0] for a in argv[1:]} or {"layers", "attention", "steps"}
    faults = next(
        (a.split("=")[1].split(",") for a in argv[1:] if a.startswith("steps=")),
        list(STEP_FAULTS),
    )
    runtime.initialize()
    cell = cells.load_cell(cell_name, rehearse)
    family = cells.load_module("families", cell.config["family"], cell.roots)
    arch = reference.arch_of(cell.config)
    if wanted & {"layers", "attention"}:
        params, probe = program_probe(cell, family, seed)
        if "layers" in wanted:
            layer_readings(cell, family, reference, arch, params, probe, seed)
        if "attention" in wanted:
            attention_readings(
                family, reference, arch, probe, seed,
                cell.workload["job_params"]["seq"],
            )
        del params, probe
    if "steps" in wanted:
        step_readings(cell, family, seed, faults)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
