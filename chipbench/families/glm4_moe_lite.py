"""GLM-4.7-Flash (``models/glm4_moe_lite.py``): what a training job needs of
it, built from a configuration file that holds the published ``config.json``
keys and the chip's share (``n_routed_experts`` = experts HELD, ids 0..n-1,
of ``n_routed_experts_published``; ``vocab_size`` = rows held)."""

from __future__ import annotations

import dataclasses
import functools

from chipbench import seeded
from chipbench.families.gpt2 import TRAIN_MULT


def model_config(config: dict, job: dict):
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig,
    )

    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("models/glm4_moe_lite.py routes without group limits")
    same = (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
        "norm_topk_prob", "rms_norm_eps", "bias_update_rate",
    )
    return Glm4MoeLiteConfig(
        **{key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]),
        initializer_range=config["initializer"]["range"],
        n_routed_experts=config["n_routed_experts_published"],
        held_experts=tuple(range(config["n_routed_experts"])),
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        remat=job.get("remat", False),
    )


# -- what the work costs, by shape (kept with the benchmark) ----------------------


def layer_params(config: dict) -> dict:
    """Matmul parameters a token passes through, by part of a layer."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        "mla": (
            d * config["q_lora_rank"] + config["q_lora_rank"] * h * qk
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * h * (
                config["qk_nope_head_dim"] + config["v_head_dim"]
            )
            + h * config["v_head_dim"] * d
        ),
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": expert,
        "router": d * config["n_routed_experts_published"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward). Convention, stated: the routed
    experts are counted at the EXPECTED assignments a token that land on
    the experts held (experts per token x held / published = 0.5 here), not
    at what a run's router sent; the two attention matmuls over the full T
    x T square, not the causal half, as ``families/gpt2.py`` counts them;
    the head over the vocabulary held; embedding lookups, norms, rotary,
    softmax, routing and the optimizer are not counted, recomputation is
    not."""
    p = layer_params(config)
    h = config["num_attention_heads"]
    core = 2 * seq * h * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"]
    )
    expected = (
        config["num_experts_per_tok"] * config["n_routed_experts"]
        / config["n_routed_experts_published"]
    )
    dense = config["first_k_dense_replace"]
    sparse = config["num_hidden_layers"] - dense
    per_token = (
        config["num_hidden_layers"] * (2 * p["mla"] + core)
        + dense * 2 * p["dense_mlp"]
        + sparse * 2 * (
            p["router"] + (config["n_shared_experts"] + expected) * p["expert"]
        )
        + 2 * config["hidden_size"] * config["vocab_size"]
    )
    return TRAIN_MULT * per_token


def attention_cost(config: dict, batch: int, seq: int, itemsize: int = 2):
    """``{"forward": (FLOPs, bytes), "backward": ...}`` one causal attention
    call needs: the causal half of the two (forward) and four (backward: dV,
    dP, dQ, dK) matmuls, whatever kernel computes it (a flash backward's
    recomputed scores are its own affair); q, k, v, out read or written once
    (backward: those, dO and the three gradients)."""
    h = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    pairs = batch * h * seq * (seq + 1) // 2
    tensor = batch * seq * h * itemsize
    return {
        "forward": (2 * pairs * (qk + dv), tensor * (2 * qk + 2 * dv)),
        "backward": (4 * pairs * (qk + dv), tensor * (4 * qk + 4 * dv)),
    }


def grouped_matmul_cost(config: dict, rows: float, active: float,
                        itemsize: int = 2):
    """``(FLOPs, bytes)`` of ONE forward pass of the three grouped matmuls
    (gate, up, down) of expert layers over the ``rows`` rows that really
    arrived on ``active`` experts (both summed over the layers): 2mnk each;
    the three matrices of every expert that got a row read once (an expert
    without rows is not read), the rows read and the results written once.
    The backward pass (the rows' gradient and the weights') is twice
    that."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return (
        3 * 2 * rows * d * f,
        (active * 3 * d * f + rows * (2 * d + 2 * f + f + d)) * itemsize,
    )


def kernel_costs(config: dict, job: dict, rows: float, active: float) -> dict:
    """``{kernel: (FLOPs, bytes)}`` an optimizer step needs of the two
    kernels, over all layers: each layer's forward (twice where the layer is
    rematerialised: the kernel really runs twice, and its time is in the
    trace twice) and its backward. ``rows`` and ``active`` are a step's
    counters: assignments that landed on held experts and held experts that
    got any, summed over the expert layers."""
    layers = config["num_hidden_layers"]
    forwards = 2 if job.get("remat", False) else 1
    attn = attention_cost(config, job["batch"], job["seq"])
    return {
        "attention": tuple(
            layers * (forwards * f + b)
            for f, b in zip(attn["forward"], attn["backward"])
        ),
        "grouped_matmul": tuple(
            (forwards + 2) * x
            for x in grouped_matmul_cost(config, rows, active)
        ),
    }


@dataclasses.dataclass
class Task:
    init_fn: object
    loss_fn: object
    units_per_step: int
    flops_per_step: float
    batches: object  # seed -> iterator of host (tokens, targets)
    # the plain reference, for chipbench/first_steps.py:
    reference_grads: object  # (params, model_state, batch) -> (loss, grads)
    reference_state: object  # (params, model_state, batch) -> model_state
    layers: object  # (params, batch) -> the program's layers' distances
    kernel_costs: object  # (rows, active experts) a step -> {kernel: (FLOPs, bytes)}


def expert_layer_distances(reference, arch, operands, params, bias, probe):
    """How far the program's expert layers are from the reference's, each ON
    ITS OWN INPUT as the program had it (``probe``: the model's ``moe_probe``
    collection; ``params`` the float32 master weights), so that what the
    layers before it rounded is not counted against it:

    - ``router_score_rms``: root mean square of (program's sigmoid scores -
      reference's) over tokens, all published experts and the layers, the
      reference's router given the operands the policy gives the program's
      (the layer's input, the router's weights cast to ``operands``): what
      is left is the router's own arithmetic, which is to be float32. A
      difference of scores, not of picks: two experts of near-equal score
      swap under any rounding, and one hot token id swaps in a thousand rows;
    - ``picks_agree``: the share of tokens whose picks are the reference's,
      routed from those operands (reported, not limited);
    - ``expert_layer_rel``: the worst layer's ``|out - reference| /
      |reference|`` over those tokens, the reference's experts in float32
      from the master weights."""
    import jax
    import jax.numpy as jnp

    score_sq, rel, agree = [], [], []
    with jax.default_matmul_precision("highest"):
        for name, layer in sorted(probe.items()):
            got, p = layer["moe"], params[name]["moe"]
            b = bias[name]["moe"]["bias"]
            x = got["input"].astype(jnp.float32)
            w_r = p["router"].astype(operands).astype(jnp.float32)
            scores = reference.router_scores(x, w_r)
            sel, _ = reference.route(x, w_r, b, arch)
            want = reference.expert_layer(x, {**p, "router": w_r}, b, arch)
            same = jnp.all(
                jnp.sort(sel, -1) == jnp.sort(got["picks"], -1), -1
            )[:, None]
            off = jnp.where(same, got["output"].astype(jnp.float32) - want, 0)
            rel.append(
                jnp.linalg.norm(off) / jnp.linalg.norm(jnp.where(same, want, 0))
            )
            score_sq.append(jnp.mean(jnp.square(got["scores"] - scores)))
            agree.append(jnp.mean(same))
    return {
        "router_score_rms": jnp.sqrt(jnp.mean(jnp.stack(score_sq))),
        "expert_layer_rel": jnp.max(jnp.stack(rel)),
        "picks_agree": jnp.mean(jnp.stack(agree)),
    }


def moved_biases(reference, arch, rate, chunk, params, router_state, tokens):
    """The selection biases after a step on ``tokens``, as the reference
    routes them: every expert layer's ``b + rate * sign(mean load - load)``,
    the loads the assignments that each published expert got in the step's
    forward pass (the configuration's ``assumed.bias_update_rate``). The
    reference's own forward pass gives no picks back and is held to one text
    with the repository's copy, so its layers are walked once more here."""
    import jax
    import jax.numpy as jnp

    moved = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens]
        for i in range(arch["layers"]):
            name = f"layers_{i}"
            p = params[name]
            h = x + reference.mla(
                reference.rms(x, p["norm_attn"], arch["eps"]), p["mla"], arch,
                chunk, None,
            )
            y = reference.rms(h, p["norm_ffn"], arch["eps"])
            if i < arch["first_dense"]:
                x = h + reference.gated_mlp(y, p["mlp_dense"], None)
                continue
            flat = y.reshape(-1, y.shape[-1])
            bias = router_state[name]["moe"]["bias"]
            sel, _ = reference.route(flat, p["moe"]["router"], bias, arch)
            load = jnp.zeros(bias.shape, jnp.float32).at[sel.reshape(-1)].add(1.0)
            moved[name] = {"moe": {
                "bias": bias + rate * jnp.sign(jnp.mean(load) - load)
            }}
            x = h + reference.expert_layer(
                flat, p["moe"], bias, arch
            ).reshape(y.shape)
    return moved


def task(config: dict, job: dict) -> Task:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import glm4_moe_lite as reference
    from pytorch_distributedtraining_tpu.models import cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.glm4_moe_lite import (
        MOE_COUNTERS, MOE_PROBE, ROUTER_STATE, Glm4MoeLite, routing_counters,
    )
    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    cfg = model_config(config, job)
    batch, seq = job["batch"], job["seq"]
    # the model's own kernels; "interpret" only where a CPU rehearsal says so
    model = Glm4MoeLite(cfg, interpret=job.get("interpret", False))

    factors = seeded.leaf_factors(config["initializer"])

    def init_fn(rng):
        # parameters do not depend on the attention function: XLA's, so
        # that no kernel is compiled for the init's 8 tokens; the model's
        # own draw, its named leaves at the configuration's scales
        variables = Glm4MoeLite(cfg, default_attention, interpret=True).init(
            rng, jnp.zeros((1, 8), jnp.int32)
        )
        return seeded.rescale(variables["params"], factors), {
            ROUTER_STATE: variables[ROUTER_STATE]
        }

    def loss_fn(params, batch, rng, model_state):
        tokens, targets = batch
        logits, new = model.apply(
            {"params": params, **model_state}, tokens,
            mutable=[ROUTER_STATE, MOE_COUNTERS],
        )
        return cross_entropy_loss(logits, targets), {
            "model_state": {ROUTER_STATE: new[ROUTER_STATE]},
            **routing_counters(new[MOE_COUNTERS]),
        }

    arch = reference.arch_of(config)
    chunk = job["reference_query_chunk"]

    def reference_grads(params, model_state, batch, **lower):
        return reference.loss_and_grads(
            params, model_state[ROUTER_STATE], *batch, arch, chunk=chunk,
            **lower,
        )

    def reference_state(params, model_state, batch):
        return {ROUTER_STATE: moved_biases(
            reference, arch, config["bias_update_rate"], chunk, params,
            model_state[ROUTER_STATE], batch[0],
        )}

    cast = Precision.from_name(job["precision"]).cast_to_compute

    @jax.jit
    def probe(params, bias, tokens):
        """The model's forward pass as the step runs it (the policy's cast,
        the kernels, the cell's sizes), its expert layers probed."""
        return model.apply(
            {"params": cast(params), ROUTER_STATE: bias}, tokens,
            mutable=[MOE_PROBE],
        )[1][MOE_PROBE]

    # a program of its own: compiled with the model's, the reference's
    # router would be merged into the very instructions it is held against
    distances = jax.jit(functools.partial(
        expert_layer_distances, reference, arch, cfg.dtype
    ))

    def layers(params, first_batch):
        bias = {  # before step 0 every selection bias is zero
            f"layers_{i}": {"moe": {"bias": jnp.zeros(
                (cfg.n_routed_experts,), jnp.float32
            )}}
            for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers)
        }
        tokens = jnp.asarray(first_batch[0])
        read = distances(params, bias, probe(params, bias, tokens))
        return {k: float(v) for k, v in read.items()}

    return Task(
        init_fn=init_fn, loss_fn=loss_fn,
        units_per_step=batch * seq,
        flops_per_step=train_flops_per_token(config, seq) * batch * seq,
        batches=lambda seed: seeded.even_batches(
            seed, batch, seq, cfg.vocab_size
        ),
        reference_grads=reference_grads, reference_state=reference_state,
        layers=layers,
        kernel_costs=functools.partial(kernel_costs, config, job),
    )
