"""LFM2-8B-A1B (``models/lfm2_moe.py``): what a training job needs of it,
built from a configuration file that holds the published ``config.json``
keys and the chip's share (``num_experts`` = experts HELD, ids 0..n-1, of
``num_experts_published``; ``vocab_size`` = rows held; ``layer_types`` and
``num_dense_layers`` cut with the depth).

What is the same arithmetic as a family the benchmark has is that family's
function: the attention core's FLOPs and bytes and the cores held to the
reference on their own q, k, v (``families/smallthinker.py``), the expert
layer's grouped matmuls and the layer held to the reference on its own input
(``families/glm4_moe_lite.py``). New here: the short convolution's bytes
(``short_conv_cost``), the conv mixers held to the reference on their own
``bcx`` and taps (``short_conv_distances``), and kernel costs that count the
forward passes the trace ran (``kernel_calls``), not the job's ``remat``."""

from __future__ import annotations

import functools

from chipbench import named_scopes, program_trace, seeded
from chipbench.families.glm4_moe_lite import (  # noqa: F401 (the family's too)
    Task, expert_layer_distances, grouped_matmul_cost,
)
from chipbench.families.gpt2 import TRAIN_MULT
from chipbench.families.smallthinker import (  # noqa: F401 (the family's too)
    attention_cost, attention_distances, visible_pairs,
)

FULL = "full_attention"
# the scopes a kernel is found under, and how many of its custom-calls one
# layer's forward pass makes
KERNEL_SCOPES = {"attention": 1, "experts": 3, "short_conv": 1}


def model_config(config: dict, job: dict):
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.lfm2_moe import Lfm2MoeConfig

    same = (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
        "norm_topk_prob", "use_expert_bias", "conv_L_cache", "conv_bias",
        "norm_eps", "bias_update_rate",
    )
    return Lfm2MoeConfig(
        **{key: config[key] for key in same},
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        initializer_range=config["initializer"]["range"],
        layer_types=tuple(config["layer_types"]),
        num_experts=config["num_experts_published"],
        held_experts=tuple(range(config["num_experts"])),
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        remat=job.get("remat", False),
    )


# -- what the work costs, by shape (kept with the benchmark) ----------------------


def with_head_dim(config: dict) -> dict:
    """The configuration with the head size it implies (the published file
    gives none: hidden size over query heads), for the functions of
    ``families/smallthinker.py`` that read ``head_dim``."""
    return {
        **config,
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
    }


def layer_params(config: dict) -> dict:
    """Matmul parameters a token passes through, by part of a layer."""
    d = config["hidden_size"]
    kv = config["num_key_value_heads"] * with_head_dim(config)["head_dim"]
    return {
        "conv": 3 * d * d + d * d,  # in_proj, out_proj
        "attention": 2 * d * d + 2 * d * kv,  # q, out; k, v
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_published"],
    }


def layer_counts(config: dict) -> dict:
    kinds = config["layer_types"]
    dense = config["num_dense_layers"]
    return {
        "attention": sum(kind == FULL for kind in kinds),
        "conv": sum(kind != FULL for kind in kinds),
        "dense": dense, "sparse": len(kinds) - dense,
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward). Convention, stated, and
    ``families/smallthinker.py``'s: the two attention matmuls over the
    causal half; the routed experts at the EXPECTED assignments a token that
    land on the experts held (experts per token x held / published = 1
    here), not at what a run's router sent; the tied head over the
    vocabulary held; the short convolution's element-wise products (a few
    per element, no matmul), embedding lookups, norms, rotary, softmax,
    routing and the optimizer are not counted, recomputation is not."""
    p, n = layer_params(config), layer_counts(config)
    h = config["num_attention_heads"]
    dh = with_head_dim(config)["head_dim"]
    expected = (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["num_experts_published"]
    )
    per_token = (
        n["conv"] * 2 * p["conv"]
        + n["attention"] * (
            2 * p["attention"] + 2 * 2 * h * dh * visible_pairs(seq, None) / seq
        )
        + n["dense"] * 2 * p["dense_mlp"]
        + n["sparse"] * 2 * (p["router"] + expected * p["expert"])
        + 2 * config["hidden_size"] * config["vocab_size"]
    )
    return TRAIN_MULT * per_token


def short_conv_cost(config: dict, job: dict, itemsize: int = 2) -> dict:
    """``{"forward": (FLOPs, bytes), "backward": ...}`` of one conv
    layer's short convolution (``ops/short_conv.py``), the bytes that the two
    kernels must read and write, each array once at the program's dtypes:
    forward ``B``, ``C``, ``X`` and the taps in, ``C * y`` out; backward the
    output's gradient, ``B``, ``C``, ``X`` and the taps in, ``d[B | C | X]``
    and the taps' float32 partials (one set a sequence) out. FLOPs: the
    element-wise products and sums, ``2L + 1`` an element forward (``z``,
    the taps, the gate) and ``6L + 3`` backward (``z`` and the taps again,
    the gate's two products, the taps' gradient, ``dz``, ``dB`` and
    ``dX``): the bytes bound it."""
    b, t, d = job["batch"], job["seq"], config["hidden_size"]
    taps = config["conv_L_cache"]
    elements = b * t * d
    return {
        "forward": (
            (2 * taps + 1) * elements,
            4 * elements * itemsize + d * taps * itemsize,
        ),
        "backward": (
            (6 * taps + 3) * elements,
            7 * elements * itemsize + d * taps * itemsize + b * taps * d * 4,
        ),
    }


def is_pallas_kernel(program: dict, name: str) -> bool:
    """A Pallas kernel of the program: a ``custom-call`` whose own
    ``op_name`` ends in ``pallas_call``. Not ``scope_trace.is_kernel``, which
    counts every ``custom-call`` and every fusion that holds one
    (``AssumeGatherIndicesInBound``, a buffer's allocation) and is right for
    the seconds it sums, not for a count of passes."""
    instr = program["instructions"].get(name)
    return (
        instr is not None and instr.opcode == "custom-call"
        and program_trace.tokens(instr.op_name)[-1:] == ["pallas_call"]
    )


def kernel_calls() -> dict | None:
    """``{scope: {"forward": n, "backward": n}}``: the Pallas kernels a
    traced step ran under each of ``KERNEL_SCOPES``, by phase (the
    rematerialised forward counts as a forward), from the run's profile
    joined to the program's compiled text (``named_scopes``). None where
    there is no profile or nothing to join."""
    found = named_scopes.joined()
    if found is None:
        return None
    trace, programs, window = found
    calls = {s: {"forward": 0, "backward": 0} for s in KERNEL_SCOPES}
    for ev, mod in program_trace.with_modules(
        trace.ops.get(0, []), trace.modules.get(0, []), window["lo"],
        window["hi"],
    ):
        program = programs.get(mod)
        name = program_trace.instruction_name(ev.name)
        if program is None or not is_pallas_kernel(program, name):
            continue
        op_name = program["instructions"][name].op_name
        phase = program_trace.phase(op_name)
        for scope in KERNEL_SCOPES:
            if scope in program_trace.tokens(op_name) and phase != "optimizer":
                calls[scope]["backward" if phase == "backward" else "forward"] += 1
    steps = window["steps"]
    return {
        s: {k: v / steps for k, v in c.items()} for s, c in calls.items()
    } if steps else None


def forwards_ran(config: dict, calls: dict | None) -> dict:
    """Forward passes a step ran of each kernel's layers, from
    ``kernel_calls`` (a rematerialised layer whose kernel's residuals are
    kept runs one; one that recomputes runs two); one where the trace says
    nothing: the least a step needs."""
    n = layer_counts(config)
    layers = {
        "attention": n["attention"], "experts": n["sparse"],
        "short_conv": n["conv"],
    }
    out = {}
    for scope, per_pass in KERNEL_SCOPES.items():
        made = calls and calls[scope]["forward"]
        out[scope] = (
            max(1, round(made / (per_pass * layers[scope])))
            if made and layers[scope] else 1
        )
    return out


def kernel_costs(config: dict, job: dict, rows: float, active: float,
                 calls: dict | None = None) -> dict:
    """``{kernel: (FLOPs, bytes)}`` an optimizer step needs of its kernels,
    over all layers: each layer's forward as many times as the trace shows
    it ran (``forwards_ran``; ``calls`` from ``kernel_calls`` unless
    given) and its backward. ``attention`` is every attention layer's core
    (the causal half; q and out of 32 heads, k and v of 8, at 64 a head),
    ``short_conv`` every conv layer's convolution. ``rows`` and ``active``
    are a step's counters: assignments that landed on held experts and held
    experts that got any, summed over the expert layers."""
    if calls is None:
        calls = kernel_calls()
    ran, n = forwards_ran(config, calls), layer_counts(config)
    core = attention_cost(
        with_head_dim(config), job["batch"], job["seq"], None
    )
    conv = short_conv_cost(config, job)

    def total(cost, forwards, layers):
        return tuple(
            layers * (forwards * f + b)
            for f, b in zip(cost["forward"], cost["backward"])
        )

    return {
        "attention": total(core, ran["attention"], n["attention"]),
        "short_conv": total(conv, ran["short_conv"], n["conv"]),
        "grouped_matmul": tuple(
            (ran["experts"] + 2) * x
            for x in grouped_matmul_cost(config, rows, active)
        ),
    }


def short_conv_distances(reference, operands, params, probe):
    """How far the program's short convolutions are from the reference's,
    each ON ITS OWN INPUTS as the program had them (``probe[layer]
    ["conv"]``: the in-projection's output ``bcx`` and the mixer's output;
    the taps as the policy hands them over, cast to ``operands``): per conv
    layer ``|out - reference| / |reference|``, the reference's taps in
    float32. Taps reversed or read a step late or early show here and
    nowhere else: the whole model's loss hardly feels them.
    ``short_conv_rel`` is the worst layer's."""
    import jax
    import jax.numpy as jnp

    rel = []
    with jax.default_matmul_precision("highest"):
        for name, layer in sorted(probe.items()):
            got = layer["conv"]
            taps = params[name]["conv"]["weight"].astype(operands)
            want = reference.short_conv(
                got["bcx"].astype(jnp.float32), taps.astype(jnp.float32)
            )
            rel.append(
                jnp.linalg.norm(got["output"].astype(jnp.float32) - want)
                / jnp.linalg.norm(want)
            )
    return {"short_conv_rel": jnp.max(jnp.stack(rel))}


def moved_biases(reference, arch, rate, chunk, params, router_state, tokens):
    """The selection biases after a step on ``tokens``, as the reference
    routes them: every expert layer's ``b + rate * sign(mean load - load)``,
    the loads the assignments that each published expert got in the step's
    forward pass (the configuration's ``bias_update_rate``). The reference's
    own forward pass gives no picks back and is held to one text with the
    repository's copy, so its layers are walked once more here."""
    import jax
    import jax.numpy as jnp

    moved = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens]
        for i in range(arch["layers"]):
            name = f"layers_{i}"
            p, bias = params[name], reference.layer_bias(router_state, arch, i)
            h = reference.mixer_branch(x, p, arch, i, chunk)
            if bias is not None:  # routed from the expert layer's own input
                u = reference.rms(h, p["ffn_norm"], arch["eps"])
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

    from chipbench.reference import lfm2_moe as reference
    from pytorch_distributedtraining_tpu.models import cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.lfm2_moe import (
        MOE_COUNTERS, MOE_PROBE, ROUTER_STATE, Lfm2Moe, banded_attention,
        routing_counters,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    cfg = model_config(config, job)
    batch, seq = job["batch"], job["seq"]
    # the model's own kernels; "interpret" only where a CPU rehearsal says so
    model = Lfm2Moe(cfg, interpret=job.get("interpret", False))

    factors = seeded.leaf_factors(config["initializer"])

    def init_fn(rng):
        # parameters do not depend on the attention function: the einsum,
        # so that no kernel is compiled for the init's 8 tokens; the model's
        # own draw, its named leaves at the configuration's scales
        variables = Lfm2Moe(cfg, banded_attention, interpret=True).init(
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
        the kernels, the cell's sizes), its expert layers, conv mixers and
        attention cores probed."""
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
    convs = jax.jit(functools.partial(
        short_conv_distances, reference, cfg.dtype
    ))

    def layers(params, first_batch):
        bias = {  # before step 0 every selection bias is zero
            f"layers_{i}": {"moe": {"bias": jnp.zeros(
                (cfg.num_experts,), jnp.float32
            )}}
            for i in range(cfg.num_dense_layers, cfg.num_hidden_layers)
        }
        probed = probe(params, bias, jnp.asarray(first_batch[0]))
        pick = lambda key, as_: {  # noqa: E731
            k: {as_: v[key]} for k, v in probed.items() if key in v
        }
        read = {
            **distances(params, bias, pick("moe", "moe")),
            **cores(pick("self_attn", "attn")),
            **convs(params, pick("conv", "conv")),
        }
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
