"""Keye-VL-2.0-30B-A3B's language trunk (``models/keye_vl2.py``): what a
training job needs of it, built from a configuration file that holds the
published ``config.json`` keys and the chip's share (``num_experts`` =
experts HELD, ids 0..n-1, of ``num_experts_published``; ``vocab_size`` =
rows held).

What is the same arithmetic as a family the benchmark has is that family's
function: the expert layers held to the reference on their own input
(``families/smallthinker.expert_layer_distances``: the same router, softmax
over the picked logits) and their grouped matmuls' cost
(``families/glm4_moe_lite.grouped_matmul_cost``). New here: the indexer's
scoring cost over the causal pairs (``indexer_cost``), the core's over the
SELECTED pairs (``sparse_attention_cost``), the seconds of each sparse kernel
by its own name (``kernel_seconds``), and the attention layers held to the
reference on their own q, k, v, indexer inputs and selection
(``sparse_distances``)."""

from __future__ import annotations

import functools

from chipbench import named_scopes, program_trace, seeded
from chipbench.families.glm4_moe_lite import grouped_matmul_cost
from chipbench.families.gpt2 import TRAIN_MULT
from chipbench.families.smallthinker import (  # noqa: F401 (the family's too)
    Task, expert_layer_distances, visible_pairs,
)

# the Pallas kernels of ops/sparse_attn.py by their own names: the
# indexer's selection, the core's forward and backward, the KL's value
SCORING = ("sparse_select",)
CORE = ("sparse_core_fwd", "sparse_core_dq", "sparse_core_dkv")
KERNELS = SCORING + CORE + ("sparse_kl_fwd",)


def model_config(config: dict, job: dict):
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.keye_vl2 import KeyeVL2Config

    sa = config["sa_config"]
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("models/keye_vl2.py has an expert layer in every layer")
    if sa["q_chunk_size"] != sa["kv_chunk_size"] or sa["indexer_num_kv_heads"] != 1:
        raise ValueError(
            "ops/sparse_attn.py takes square blocks and one indexer key head"
        )
    same = (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
        "rms_norm_eps",
    )
    return KeyeVL2Config(
        **{key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_topk=sa["topk"], indexer_block=sa["q_chunk_size"],
        initializer_range=config["initializer"]["range"],
        num_experts=config["num_experts_published"],
        held_experts=tuple(range(config["num_experts"])),
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        remat=job.get("remat", False),
    )


# -- what the work costs, by shape (kept with the benchmark) ----------------------


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs the selection hands the core over a sequence:
    query t takes min(t + 1, topk) keys."""
    head = min(seq, topk)
    return head * (head + 1) // 2 + (seq - head) * topk


def layer_params(config: dict) -> dict:
    """Matmul parameters a token passes through, by part of a layer."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        "attention": d * dh * (2 * h + 2 * kvh),  # q, o; k, v
        "indexer": d * (hi * di + di + hi),  # qI, kI, w
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["num_experts_published"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward). Convention, stated: the core's
    two matmuls over the pairs the selection HANDS it (a query's min(t + 1,
    topk) keys: 31.5 M of the 134.2 M causal pairs at 16,384), not the
    causal half; the indexer's scoring, one matmul of the indexer's heads
    over every causal pair (its backward, the KL's gradient into qI and kI,
    the two matmuls of the same size); the routed experts at the EXPECTED
    assignments a token that land on the experts held (experts per token x
    held / published = 1 here); the head over the vocabulary held; the KL's
    value (a pass of the core's scores again), the selection's bisection,
    embedding lookups, norms, rotary, softmax, routing and the optimizer are
    not counted, recomputation is not."""
    p = layer_params(config)
    h, dh = config["num_attention_heads"], config["head_dim"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    expected = (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["num_experts_published"]
    )
    core = 2 * 2 * h * dh * selected_pairs(seq, sa["topk"]) / seq
    scoring = 2 * hi * di * visible_pairs(seq, None) / seq
    per_token = (
        config["num_hidden_layers"] * (
            2 * (p["attention"] + p["indexer"] + p["router"]
                 + expected * p["expert"])
            + core + scoring
        )
        + 2 * config["hidden_size"] * config["vocab_size"]
    )
    return TRAIN_MULT * per_token


def indexer_cost(config: dict, job: dict, itemsize: int = 2) -> tuple:
    """``(FLOPs, bytes)`` of ONE layer's selection kernel: the scores of
    every causal pair (a dot of the indexer's ``HI x DI`` a pair), ``qI``,
    ``kI`` and the weights read once, the selection's bits and the
    log-sum-exp written once. The bisection's compares are not counted."""
    b, t = job["batch"], job["seq"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (
        b * 2 * hi * di * visible_pairs(t, None),
        b * (t * (hi * di + di) * itemsize + t * hi * 4 + t * t // 8 + t * 4),
    )


def sparse_attention_cost(config: dict, job: dict, itemsize: int = 2) -> dict:
    """``{"forward": (FLOPs, bytes), "backward": ...}`` of one layer's core
    over the pairs the selection hands it: two matmuls forward, four
    backward (dV, dP, dQ, dK) of every query head, whatever computes them
    (the tiles the kernels run beyond the selection, the KL's value and
    its gradient into the indexer are their own affair); q and out of every
    query head, k and v of every key-value head read or written once
    (backward: those, dO and the three gradients)."""
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    pairs = job["batch"] * h * selected_pairs(job["seq"], config["sa_config"]["topk"])
    row = job["batch"] * job["seq"] * dh * itemsize
    return {
        "forward": (2 * pairs * 2 * dh, row * (2 * h + 2 * kvh)),
        "backward": (4 * pairs * 2 * dh, row * (4 * h + 4 * kvh)),
    }


def _kernel_of(instruction: str):
    """Which of ``KERNELS`` an instruction is (``sparse_core_dq.3``), or
    None."""
    base = instruction.split(".", 1)[0]
    return base if base in KERNELS else None


def kernel_seconds() -> dict | None:
    """``{kernel: {"seconds": s, "calls": n}}`` a traced step spent in each
    of ``KERNELS``, by the instruction names the kernels compile to, from
    the run's profile joined to the program's compiled text
    (``named_scopes``). None where there is no profile or nothing to
    join."""
    found = named_scopes.joined()
    if found is None:
        return None
    trace, programs, window = found
    out = {k: {"seconds": 0.0, "calls": 0} for k in KERNELS}
    lo, hi = window["lo"], window["hi"]
    for ev, mod in program_trace.with_modules(
        trace.ops.get(0, []), trace.modules.get(0, []), lo, hi
    ):
        name = program_trace.instruction_name(ev.name)
        kernel = _kernel_of(name)
        if kernel is None or mod not in programs:
            continue
        out[kernel]["seconds"] += min(ev.end, hi) - max(ev.start, lo)
        out[kernel]["calls"] += 1
    steps = window["steps"]
    if not steps:
        return None
    return {
        k: {key: v / steps for key, v in c.items()} for k, c in out.items()
    }


def roofline(config: dict, job: dict, device_kind: str, which: str):
    """Percent of its roofline a step's ``which`` kernels reach ("indexer":
    ``SCORING``, "sparse_attention": ``CORE``): the least time the chip
    needs for their work over the seconds the trace shows them taking. The
    work counts the passes the trace ran: a layer's forward as often as its
    forward kernel ran (once: the selection and the core's residuals are
    kept under remat). None where the trace shows none of them."""
    from chipbench.peaks import peaks_for

    found = kernel_seconds()
    names = SCORING if which == "indexer" else CORE
    if found is None or device_kind is None:
        return None
    seconds = sum(found[k]["seconds"] for k in names)
    if not seconds > 0:
        return None
    layers = config["num_hidden_layers"]
    if which == "indexer":
        runs = found["sparse_select"]["calls"]
        flops, nbytes = (runs * x for x in indexer_cost(config, job))
    else:
        cost = sparse_attention_cost(config, job)
        forwards = found["sparse_core_fwd"]["calls"] / layers
        flops, nbytes = (
            layers * (forwards * f + b)
            for f, b in zip(cost["forward"], cost["backward"])
        )
    peaks = peaks_for(device_kind)
    least = max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds


def kernel_costs(config: dict, job: dict, rows: float, active: float) -> dict:
    """``{kernel: (FLOPs, bytes)}`` of a step's grouped matmuls over the
    ``rows`` that landed on ``active`` held experts (both summed over the
    layers): the forward twice (a rematerialised layer runs it again) and
    the backward. The sparse kernels' rooflines are ``roofline``'s."""
    forwards = 2 if job.get("remat", False) else 1
    return {
        "grouped_matmul": tuple(
            (forwards + 2) * x
            for x in grouped_matmul_cost(config, rows, active)
        ),
    }


# -- the program's layers against the reference ---------------------------------


def unpack_block(words):
    """``[B, nk, blk/32, blk]`` int32 words of one query block of
    ``ops/sparse_attn.py``'s selection -> the block's mask ``[B, blk, T]``
    (bit r of word row g is the block's query ``r blk/32 + g``)."""
    import jax.numpy as jnp

    b, nk, g, blk = words.shape
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.int32)[None, :, None, None, None]) & 1
    # [B, 32, nk, g, blk] -> rows (r, g), columns (nk, blk)
    return bits.transpose(0, 1, 3, 2, 4).reshape(b, blk, nk * blk) != 0


def sparse_distances(reference, arch, probe):
    """How far the program's attention layers are from the reference's,
    each ON ITS OWN INPUTS as the program had them (``probe[layer]
    ["self_attn"]``: q and k after the head norms and M-RoPE, v, the
    indexer's qI, kI (after rotary) and w, the program's selection and
    ``lse_I``, the core's output and the KL by query), a query block at a
    time, float32:

    - ``sparse_attention_rel``: ``|out - reference| / |reference|``, the
      reference's masked softmax over the PROGRAM's selection, worst layer;
    - ``indexer_score_rel``: the log-sum-exp of each query's selected
      scores (the one number of the scores the selection kernel hands on)
      against the reference's scores over the same selection, ``|lse -
      reference| / |reference|``, worst layer;
    - ``selection_agree``: the share of the program's picks that the
      reference's top-k of its own float32 scores also picks, worst layer
      (a lower bound: the two differ only where scores tie within
      rounding);
    - ``indexer_kl_abs``: the layer's KL (the mean over queries) against
      the reference's over the program's selection, worst layer."""
    import jax
    import jax.numpy as jnp

    reads = {
        "sparse_attention_rel": [], "indexer_score_rel": [],
        "selection_agree": [], "indexer_kl_abs": [],
    }
    with jax.default_matmul_precision("highest"):
        for _, layer in sorted(probe.items()):
            got = layer["self_attn"]
            f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
            q, k, v, qi, ki, w = (f32(got[n]) for n in ("q", "k", "v", "qi", "ki", "w"))
            words = got["selection"]
            b, t = q.shape[:2]
            nq, blk = words.shape[1], words.shape[-1]

            def one(args, k=k, v=v, ki=ki):
                q_c, qi_c, w_c, words_c, start = args
                scores = reference.indexer_scores(qi_c, ki, w_c)
                mask = unpack_block(words_c)
                theirs = reference.selection(scores, start, arch["topk"])
                out, kl = reference.masked_attention(q_c, k, v, mask, scores)
                lse = jax.nn.logsumexp(jnp.where(mask, scores, -jnp.inf), -1)
                return out, kl, lse, jnp.sum(mask & theirs), jnp.sum(mask)

            split = lambda x: x.reshape(b, nq, blk, *x.shape[2:]).swapaxes(0, 1)  # noqa: E731
            out, kl, lse, agree, total = jax.lax.map(one, (
                split(q), split(qi), split(w), words.swapaxes(0, 1),
                jnp.arange(0, t, blk),
            ))
            out = out.swapaxes(0, 1).reshape(got["output"].shape)
            lse = lse.swapaxes(0, 1).reshape(b, t)
            rel = lambda a, ref: jnp.linalg.norm(a - ref) / jnp.linalg.norm(ref)  # noqa: E731
            reads["sparse_attention_rel"].append(rel(f32(got["output"]), out))
            reads["indexer_score_rel"].append(rel(got["lse_i"], lse))
            # by the picks missed, so that full agreement reads 1 exactly
            reads["selection_agree"].append(
                1.0 - jnp.sum(total - agree) / jnp.sum(total)
            )
            reads["indexer_kl_abs"].append(
                jnp.abs(jnp.mean(got["kl"]) - jnp.mean(kl))
            )
    return {
        "sparse_attention_rel": jnp.max(jnp.stack(reads["sparse_attention_rel"])),
        "indexer_score_rel": jnp.max(jnp.stack(reads["indexer_score_rel"])),
        "selection_agree": jnp.min(jnp.stack(reads["selection_agree"])),
        "indexer_kl_abs": jnp.max(jnp.stack(reads["indexer_kl_abs"])),
    }


def task(config: dict, job: dict) -> Task:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import keye_vl2 as reference
    from pytorch_distributedtraining_tpu.models import cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.keye_vl2 import (
        INDEXER_STATS, MOE_COUNTERS, MOE_PROBE, KeyeVL2, indexer_counters,
        routing_counters,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    cfg = model_config(config, job)
    batch, seq = job["batch"], job["seq"]
    interpret = job.get("interpret", False)  # a CPU rehearsal's kernels
    model = KeyeVL2(cfg, interpret=interpret)
    factors = seeded.leaf_factors(config["initializer"])

    def init_fn(rng):
        # the init's tokens are one block: the kernels see the smallest
        # shape they take; the model's own draw, its named leaves at the
        # configuration's scales
        small = min(cfg.indexer_block, seq)
        params = KeyeVL2(cfg, interpret=True).init(
            rng, jnp.zeros((1, small), jnp.int32)
        )["params"]
        return seeded.rescale(params, factors), {}

    def loss_fn(params, batch, rng, model_state):
        tokens, targets = batch
        logits, new = model.apply(
            {"params": params}, tokens, mutable=[MOE_COUNTERS, INDEXER_STATS]
        )
        ce = cross_entropy_loss(logits, targets)
        found = indexer_counters(new[INDEXER_STATS], tokens.size)
        return ce + found["indexer_kl"], {
            **routing_counters(new[MOE_COUNTERS]), **found, "lm_loss": ce,
        }

    arch = reference.arch_of(config)

    def reference_grads(params, model_state, batch, **lower):
        return reference.loss_and_grads(
            params, *batch, arch, chunk=job["reference_query_chunk"], **lower
        )

    cast = Precision.from_name(job["precision"]).cast_to_compute

    @jax.jit
    def probe(params, tokens):
        """The model's forward pass as the step runs it (the policy's cast,
        the kernels, the cell's sizes), its layers probed."""
        return model.apply(
            {"params": cast(params)}, tokens, mutable=[MOE_PROBE]
        )[1][MOE_PROBE]

    # programs of their own: compiled with the model's, the reference would
    # be merged into the very instructions it is held against
    experts = jax.jit(functools.partial(
        expert_layer_distances, reference, arch, cfg.dtype
    ))
    attention = jax.jit(functools.partial(sparse_distances, reference, arch))

    def layers(params, first_batch):
        probed = probe(params, jnp.asarray(first_batch[0]))
        read = {**experts(params, probed), **attention(probed)}
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
