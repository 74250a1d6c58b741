"""SmallThinker-21BA3B (``models/smallthinker.py``): what a training job needs
of it, built from a configuration file that holds the published
``config.json`` keys and the chip's share (``moe_num_primary_experts`` =
experts HELD, ids 0..n-1, of ``moe_num_primary_experts_published``;
``vocab_size`` = rows held; the two layout lists cut with the depth)."""

from __future__ import annotations

import dataclasses
import functools

from chipbench import seeded
from chipbench.families.gpt2 import TRAIN_MULT


def model_config(config: dict, job: dict):
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.smallthinker import (
        SmallThinkerConfig,
    )

    if not config["moe_primary_router_apply_softmax"] or config["rope_scaling"]:
        raise ValueError(
            "models/smallthinker.py weighs by softmax over the picked logits "
            "and scales no rotary frequency"
        )
    same = (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_ffn_hidden_size", "moe_num_active_primary_experts",
        "sliding_window_size", "rms_norm_eps",
    )
    return SmallThinkerConfig(
        **{key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]),
        initializer_range=config["initializer"]["range"],
        sliding_window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        moe_num_primary_experts=config["moe_num_primary_experts_published"],
        held_experts=tuple(range(config["moe_num_primary_experts"])),
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        remat=job.get("remat", False),
    )


# -- what the work costs, by shape (kept with the benchmark) ----------------------


def layer_params(config: dict) -> dict:
    """Matmul parameters a token passes through, by part of a layer."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        "attention": d * dh * (2 * h + 2 * kvh),  # q, o; k, v
        "expert": 3 * d * config["moe_ffn_hidden_size"],
        "router": d * config["moe_num_primary_experts_published"],
    }


def visible_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs of one head over a sequence: the causal half, or
    under a window its band (query t sees min(t + 1, window) keys)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_windows(config: dict) -> list:
    """Each layer's window, None where it sees the whole causal past."""
    return [
        config["sliding_window_size"] if flag else None
        for flag in config["sliding_window_layout"]
    ]


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward). Convention, stated: the two
    attention matmuls over the pairs a query may SEE, the band of a window
    layer and the causal half of a full one (not the full square
    ``families/gpt2.py`` and ``families/glm4_moe_lite.py`` count: at 16,384
    tokens under a window of 4,096 that would credit the step with 4.6 x
    the work a window layer has); the routed experts at the EXPECTED
    assignments a token that land on the experts held (experts per token x
    held / published = 0.75 here), not at what a run's router sent; the
    head over the vocabulary held; embedding lookups, norms, rotary,
    softmax, routing and the optimizer are not counted, recomputation is
    not."""
    p = layer_params(config)
    h, dh = config["num_attention_heads"], config["head_dim"]
    core = sum(
        2 * 2 * h * dh * visible_pairs(seq, window) / seq
        for window in layer_windows(config)
    )
    expected = (
        config["moe_num_active_primary_experts"]
        * config["moe_num_primary_experts"]
        / config["moe_num_primary_experts_published"]
    )
    per_token = (
        config["num_hidden_layers"] * 2 * (
            p["attention"] + p["router"] + expected * p["expert"]
        )
        + core
        + 2 * config["hidden_size"] * config["vocab_size"]
    )
    return TRAIN_MULT * per_token


def attention_cost(config: dict, batch: int, seq: int, window: int | None,
                   itemsize: int = 2):
    """``{"forward": (FLOPs, bytes), "backward": ...}`` one attention call
    of a layer kind needs (``window`` None: a full layer): the two (forward)
    and four (backward: dV, dP, dQ, dK) matmuls over the pairs a query may
    see, whatever kernel computes them (a flash backward's recomputed
    scores, and the blocks it runs beyond the band, are its own affair);
    q and out of every query head, k and v of every key-value head read or
    written once (backward: those, dO and the three gradients)."""
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    pairs = batch * h * visible_pairs(seq, window)
    row = batch * seq * dh * itemsize
    return {
        "forward": (2 * pairs * 2 * dh, row * (2 * h + 2 * kvh)),
        "backward": (4 * pairs * 2 * dh, row * (4 * h + 4 * kvh)),
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
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    return (
        3 * 2 * rows * d * f,
        (active * 3 * d * f + rows * (2 * d + 2 * f + f + d)) * itemsize,
    )


def kernel_costs(config: dict, job: dict, rows: float, active: float) -> dict:
    """``{kernel: (FLOPs, bytes)}`` an optimizer step needs of its kernels,
    over all layers: each layer's forward (twice where the layer is
    rematerialised: the kernel really runs twice, and its time is in the
    trace twice) and its backward. ``attention`` is every layer's core,
    ``attention_sliding`` the window layers' alone. ``rows`` and ``active``
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


@dataclasses.dataclass
class Task:
    init_fn: object
    loss_fn: object
    units_per_step: int
    flops_per_step: float
    batches: object  # seed -> iterator of host (tokens, targets)
    # the plain reference, for chipbench/first_steps.py:
    reference_grads: object  # (params, model_state, batch) -> (loss, grads)
    reference_state: object  # None: a step moves nothing but the weights
    layers: object  # (params, batch) -> the program's layers' distances
    kernel_costs: object  # (rows, active experts) a step -> {kernel: (FLOPs, bytes)}


def expert_layer_distances(reference, arch, operands, params, probe):
    """How far the program's expert layers are from the reference's, each ON
    ITS OWN INPUTS as the program had them (``probe``: the model's
    ``moe_probe`` collection, which holds the router's input, the layer's
    raw input, beside the experts'; ``params`` the float32 master weights),
    so that what the layers before it rounded is not counted against it:

    - ``router_score_rms``: root mean square of (program's router logits -
      reference's) over tokens, all published experts and the layers, the
      reference's router given the operands the policy gives the program's
      (the layer's raw input, the router's weights cast to ``operands``):
      what is left is the router's own arithmetic, which is to be float32;
    - ``picks_agree``: the share of tokens whose picks are the reference's,
      routed from those operands (reported, not limited: two experts of
      near-equal logit swap under any rounding);
    - ``expert_layer_rel``: the worst layer's ``|out - reference| /
      |reference|`` over those tokens, the reference's experts in float32
      from the master weights on the experts' input."""
    import jax
    import jax.numpy as jnp

    score_sq, rel, agree = [], [], []
    with jax.default_matmul_precision("highest"):
        for name, layer in sorted(probe.items()):
            got, p = layer["moe"], params[name]
            x = got["router_input"].astype(jnp.float32)
            w_r = p["router"]["kernel"].astype(operands).astype(jnp.float32)
            logits = reference.router_logits(x, w_r)
            sel, w = reference.route(x, w_r, arch)
            want = reference.expert_layer(
                got["input"].astype(jnp.float32), p["moe"], sel, w, arch
            )
            same = jnp.all(
                jnp.sort(sel, -1) == jnp.sort(got["picks"], -1), -1
            )[:, None]
            off = jnp.where(same, got["output"].astype(jnp.float32) - want, 0)
            rel.append(
                jnp.linalg.norm(off) / jnp.linalg.norm(jnp.where(same, want, 0))
            )
            score_sq.append(jnp.mean(jnp.square(got["scores"] - logits)))
            agree.append(jnp.mean(same))
    return {
        "router_score_rms": jnp.sqrt(jnp.mean(jnp.stack(score_sq))),
        "expert_layer_rel": jnp.max(jnp.stack(rel)),
        "picks_agree": jnp.mean(jnp.stack(agree)),
    }


def attention_distances(reference, arch, chunk, probe):
    """How far the program's attention cores are from the reference's, each
    ON ITS OWN INPUTS as the program had them (``probe[layer]["attn"]``: q
    and k after rotary, v, and the core's output): per layer ``|out -
    reference| / |reference|``, the reference's chunked masked attention in
    float32 on the same q, k, v under the layer's window. A band that
    starts or ends a key off, a head read from the wrong key-value head or
    a score lost at a block's edge shows here and nowhere else: the loss
    and the gradient norm of the whole model hardly feel one layer's core.
    ``attention_rel`` is the worst layer's, ``attention_global_rel`` /
    ``attention_sliding_rel`` the worst of each kind (reported)."""
    import jax
    import jax.numpy as jnp

    rel = {"global": [], "sliding": []}
    with jax.default_matmul_precision("highest"):
        for name, layer in sorted(probe.items()):
            got = layer["attn"]
            windowed = arch["windowed"][int(name.rsplit("_", 1)[1])]
            want = reference.banded_attention(
                *(got[x].astype(jnp.float32) for x in "qkv"),
                arch["window"] if windowed else None, chunk,
            )
            rel["sliding" if windowed else "global"].append(
                jnp.linalg.norm(got["output"].astype(jnp.float32) - want)
                / jnp.linalg.norm(want)
            )
    kinds = {
        f"attention_{kind}_rel": jnp.max(jnp.stack(v))
        for kind, v in rel.items() if v
    }
    return {"attention_rel": jnp.max(jnp.stack(list(kinds.values()))), **kinds}


def task(config: dict, job: dict) -> Task:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import smallthinker as reference
    from pytorch_distributedtraining_tpu.models import cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.smallthinker import (
        MOE_COUNTERS, MOE_PROBE, SmallThinker, banded_attention,
        routing_counters,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    cfg = model_config(config, job)
    batch, seq = job["batch"], job["seq"]
    # the model's own kernels; "interpret" only where a CPU rehearsal says so
    model = SmallThinker(cfg, interpret=job.get("interpret", False))

    factors = seeded.leaf_factors(config["initializer"])

    def init_fn(rng):
        # parameters do not depend on the attention function: the einsum,
        # so that no kernel is compiled for the init's 8 tokens; the model's
        # own draw, its named leaves at the configuration's scales
        params = SmallThinker(cfg, banded_attention, interpret=True).init(
            rng, jnp.zeros((1, 8), jnp.int32)
        )["params"]
        return seeded.rescale(params, factors), {}

    def loss_fn(params, batch, rng, model_state):
        tokens, targets = batch
        logits, new = model.apply(
            {"params": params}, tokens, mutable=[MOE_COUNTERS]
        )
        return cross_entropy_loss(logits, targets), routing_counters(
            new[MOE_COUNTERS]
        )

    arch = reference.arch_of(config)

    def reference_grads(params, model_state, batch, **lower):
        return reference.loss_and_grads(
            params, *batch, arch, chunk=job["reference_query_chunk"], **lower
        )

    cast = Precision.from_name(job["precision"]).cast_to_compute

    @jax.jit
    def probe(params, tokens):
        """The model's forward pass as the step runs it (the policy's cast,
        the kernels, the cell's sizes), its expert layers and its attention
        cores probed."""
        return model.apply(
            {"params": cast(params)}, tokens, mutable=[MOE_PROBE]
        )[1][MOE_PROBE]

    # a program of its own: compiled with the model's, the reference's
    # router would be merged into the very instructions it is held against
    distances = jax.jit(functools.partial(
        expert_layer_distances, reference, arch, cfg.dtype
    ))
    cores = jax.jit(functools.partial(
        attention_distances, reference, arch, job["reference_query_chunk"]
    ))

    def layers(params, first_batch):
        probed = probe(params, jnp.asarray(first_batch[0]))
        read = {**distances(params, probed), **cores(probed)}
        return {k: float(v) for k, v in read.items()}

    return Task(
        init_fn=init_fn, loss_fn=loss_fn,
        units_per_step=batch * seq,
        flops_per_step=train_flops_per_token(config, seq) * batch * seq,
        batches=lambda seed: seeded.even_batches(
            seed, batch, seq, cfg.vocab_size
        ),
        reference_grads=reference_grads, reference_state=None,
        layers=layers,
        kernel_costs=functools.partial(kernel_costs, config, job),
    )
