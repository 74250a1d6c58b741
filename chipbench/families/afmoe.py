"""Trinity-Mini (``models/afmoe.py``): what a training job needs of it, built
from a configuration file that holds the published ``config.json`` keys and
the chip's share (``num_experts`` = experts HELD, ids 0..n-1, of
``num_experts_published``; ``vocab_size`` = rows held; ``layer_types`` and
``num_dense_layers`` cut with the depth).

What is the same arithmetic as a family the benchmark has is that family's
function: a layer kind's attention (``families/smallthinker.py``: the pairs
under a band, the core's FLOPs and bytes, the cores held to the reference on
their own q, k, v) and the expert layer (``families/glm4_moe_lite.py``: the
grouped matmuls' cost, the layer held to the reference on its own input)."""

from __future__ import annotations

import functools

from chipbench import seeded
from chipbench.families.glm4_moe_lite import (  # noqa: F401 (the family's too)
    Task, expert_layer_distances, grouped_matmul_cost,
)
from chipbench.families.gpt2 import TRAIN_MULT
from chipbench.families.smallthinker import (  # noqa: F401 (the family's too)
    attention_cost, attention_distances, visible_pairs,
)

SLIDING = "sliding_attention"


def model_config(config: dict, job: dict):
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.afmoe import AfmoeConfig

    groups = ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")
    if any(config[key] != 1 for key in groups) or config["rope_scaling"]:
        raise ValueError(
            "models/afmoe.py routes without group limits and scales no "
            "rotary frequency"
        )
    same = (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts_per_tok", "num_shared_experts", "route_norm",
        "route_scale", "score_func", "sliding_window", "rms_norm_eps",
        "mup_enabled", "load_balance_coeff",
    )
    return AfmoeConfig(
        **{key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]),
        initializer_range=config["initializer"]["range"],
        layer_types=tuple(config["layer_types"]),
        num_experts=config["num_experts_published"],
        held_experts=tuple(range(config["num_experts"])),
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        remat=job.get("remat", False),
    )


# -- what the work costs, by shape (kept with the benchmark) ----------------------


def layer_params(config: dict) -> dict:
    """Matmul parameters a token passes through, by part of a layer."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        "attention": d * dh * (3 * h + 2 * kvh),  # q, gate, o; k, v
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_published"],
    }


def layer_windows(config: dict) -> list:
    """Each layer's window, None where it sees the whole causal past."""
    return [
        config["sliding_window"] if kind == SLIDING else None
        for kind in config["layer_types"]
    ]


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward). Convention, stated, and
    ``families/smallthinker.py``'s: the two attention matmuls over the pairs
    a query may SEE, the band of a sliding layer and the causal half of a
    full one; the routed experts at the EXPECTED assignments a token that
    land on the experts held (experts per token x held / published = 0.5
    here), not at what a run's router sent, the shared expert whole; the
    head over the vocabulary held; embedding lookups, norms, the gate's
    sigmoid and product, rotary, softmax, routing and the optimizer are not
    counted, recomputation is not."""
    p = layer_params(config)
    h, dh = config["num_attention_heads"], config["head_dim"]
    core = sum(
        2 * 2 * h * dh * visible_pairs(seq, window) / seq
        for window in layer_windows(config)
    )
    expected = (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["num_experts_published"]
    )
    dense = config["num_dense_layers"]
    sparse = config["num_hidden_layers"] - dense
    per_token = (
        config["num_hidden_layers"] * 2 * p["attention"] + core
        + dense * 2 * p["dense_mlp"]
        + sparse * 2 * (
            p["router"]
            + (config["num_shared_experts"] + expected) * p["expert"]
        )
        + 2 * config["hidden_size"] * config["vocab_size"]
    )
    return TRAIN_MULT * per_token


def kernel_costs(config: dict, job: dict, rows: float, active: float) -> dict:
    """``{kernel: (FLOPs, bytes)}`` an optimizer step needs of its kernels,
    over all layers: each layer's forward (twice where the layer is
    rematerialised: the kernel really runs twice, and its time is in the
    trace twice) and its backward. ``attention`` is every layer's core (the
    band of 2,048 in a sliding layer; q and out of 32 heads, k and v of 4),
    ``attention_sliding`` the sliding layers' alone. ``rows`` and ``active``
    are a step's counters: assignments that landed on held experts and held
    experts that got any, summed over the expert layers."""
    forwards = 2 if job.get("remat", False) else 1

    def cores(windows):
        total = [0, 0]
        for window in windows:
            c = attention_cost(config, job["batch"], job["seq"], window)
            for i, (f, b) in enumerate(zip(c["forward"], c["backward"])):
                total[i] += forwards * f + b
        return tuple(total)

    windows = layer_windows(config)
    return {
        "attention": cores(windows),
        "attention_sliding": cores([w for w in windows if w is not None]),
        "grouped_matmul": tuple(
            (forwards + 2) * x
            for x in grouped_matmul_cost(config, rows, active)
        ),
    }


def moved_biases(reference, arch, rate, chunk, params, router_state, tokens):
    """The selection biases after a step on ``tokens``, as the reference
    routes them: every expert layer's ``b + rate * sign(mean load - load)``,
    the loads the assignments that each published expert got in the step's
    forward pass (the configuration's ``load_balance_coeff`` and
    ``assumed.bias_update``). The reference's own forward pass gives no
    picks back and is held to one text with the repository's copy, so its
    layers are walked once more here."""
    import jax
    import jax.numpy as jnp

    moved = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens] * arch["embed_scale"]
        for i in range(arch["layers"]):
            name = f"layers_{i}"
            p, bias = params[name], reference.layer_bias(router_state, arch, i)
            h = reference.attention_branch(x, p, arch, i, chunk)
            if bias is not None:  # routed from the expert layer's own input
                u = reference.rms(h, p["pre_mlp_layernorm"], arch["eps"])
                sel, _ = reference.route(
                    u.reshape(-1, u.shape[-1]), p["moe"]["router"], bias, arch
                )
                load = jnp.zeros(bias.shape, jnp.float32).at[
                    sel.reshape(-1)
                ].add(1.0)
                moved[name] = {"moe": {
                    "bias": bias + rate * jnp.sign(jnp.mean(load) - load)
                }}
            x = reference.mlp_branch(h, p, bias, arch, i)
    return moved


def task(config: dict, job: dict) -> Task:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import afmoe as reference
    from pytorch_distributedtraining_tpu.models import cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.afmoe import (
        MOE_COUNTERS, MOE_PROBE, ROUTER_STATE, Afmoe, banded_attention,
        routing_counters,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    cfg = model_config(config, job)
    batch, seq = job["batch"], job["seq"]
    # the model's own kernels; "interpret" only where a CPU rehearsal says so
    model = Afmoe(cfg, interpret=job.get("interpret", False))

    factors = seeded.leaf_factors(config["initializer"])

    def init_fn(rng):
        # parameters do not depend on the attention function: the einsum,
        # so that no kernel is compiled for the init's 8 tokens; the model's
        # own draw, its named leaves at the configuration's scales
        variables = Afmoe(cfg, banded_attention, interpret=True).init(
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
            reference, arch, config["load_balance_coeff"], chunk, params,
            model_state[ROUTER_STATE], batch[0],
        )}

    cast = Precision.from_name(job["precision"]).cast_to_compute

    @jax.jit
    def probe(params, bias, tokens):
        """The model's forward pass as the step runs it (the policy's cast,
        the kernels, the cell's sizes), its expert layers and its attention
        cores probed."""
        return model.apply(
            {"params": cast(params), ROUTER_STATE: bias}, tokens,
            mutable=[MOE_PROBE],
        )[1][MOE_PROBE]

    # programs of their own: compiled with the model's, the reference's
    # router would be merged into the very instructions it is held against
    distances = jax.jit(functools.partial(
        expert_layer_distances, reference, arch, cfg.dtype
    ))
    cores = jax.jit(functools.partial(
        attention_distances, reference, arch, chunk
    ))

    def layers(params, first_batch):
        bias = {  # before step 0 every selection bias is zero
            f"layers_{i}": {"moe": {"bias": jnp.zeros(
                (cfg.num_experts,), jnp.float32
            )}}
            for i in range(cfg.num_dense_layers, cfg.num_hidden_layers)
        }
        probed = probe(params, bias, jnp.asarray(first_batch[0]))
        sparse = {k: v for k, v in probed.items() if "moe" in v}
        read = {**distances(params, bias, sparse), **cores(probed)}
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
