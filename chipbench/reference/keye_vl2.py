"""Keye-VL-2.0-30B-A3B's language trunk (``KeyeVL2``) as its ``config.json``
and DeepSeek Sparse Attention describe it, in plain ``jax.numpy``: float32
throughout, under ``jax.default_matmul_precision("highest")``, no Flax
module, no kernel.

    layer l: a = rms(x)
             q = rms_q(a W_q) -> H x d;  k = rms_k(a W_k), v = a W_v -> KVH x d
             q, k <- M-RoPE (split halves, theta; frequency i of d/2 turns by
                     the time, height or width position, by mrope_section)
             indexer on stop_gradient(a):
               qI = a W_qI -> HI x DI;  kI = layer_norm(a W_kI)
               qI, kI <- rotary (split halves) on their first DI/2 dims, time
               w = (a W_w) HI^-1/2 DI^-1/2
               I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
               S_t = lax.top_k(I[t, 0..t], topk) (all while t < topk; equal
                     scores: the lower s first)
             o = softmax over S_t of q k^T / sqrt(d), head h on h // (H / KVH)
             h = x + o W_o
             u = rms(h);  sel = top_k(u W_r);  g = softmax(logits[sel])
             y = h + sum_{i in sel and held} g_i W2_i(silu(u W1_i) * (u W3_i))
             kl_l = mean_t KL(p_t || softmax(I[t, S_t])),
                    p_t = stop_gradient(mean_h softmax over S_t)
    then a final rms and an untied head
    loss:    mean token cross-entropy over the vocabulary held
             + mean over layers of kl_l

It is given the same share as the program: ``arch["held"]`` lists the
experts held, the router keeps its published width, and what the absent
experts would add is left out. The held experts run as a loop, every expert
over every token under a mask.

Takes the parameter tree of ``models/keye_vl2.py`` (names only). Departures
that change no arithmetic, so that the gradient pass at 16,384 tokens fits
beside the training state: attention takes the queries in chunks (the
indexer's scores ``[chunk, T]``, the selection a mask of each chunk's rows,
never ``[T, T]``), and each layer, each chunk and each expert of the loop
is recomputed in the backward pass (``jax.checkpoint``).

``operands`` / ``router`` (default ``None``: float32) round every matmul's
operands, or the router's operands and logits, to a narrower dtype first;
``scores`` rounds the indexer's scores; ``topk`` replaces the
configuration's (``None``: its own; the sequence's length: dense causal
attention); ``kl_target_grad`` lets the KL's target be differentiated. None
of those is the reference: they are the readings that a cell's tolerance has
to refuse.

This text lives twice and is held to one: ``chipbench/reference/
keye_vl2.py`` (the benchmark's copy: the comparison that decides
``correct`` reads nothing of the program's own model code) and
``pytorch_distributedtraining_tpu/models/keye_vl2_reference.py`` (the
repository's); ``tests/test_keye_vl2.py`` compares the two files.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def arch_of(config: dict) -> dict:
    """What the equations need, from a configuration file's published keys
    (``num_experts`` there is the number HELD: experts 0..n-1)."""
    sa = config["sa_config"]
    return {
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "top_k": config["num_experts_per_tok"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "section": tuple(config["rope_scaling"]["mrope_section"]),
        "indexer_heads": sa["indexer_num_heads"],
        "indexer_dim": sa["indexer_head_dim"],
        "topk": sa["topk"],
        "held": tuple(range(config["num_experts"])),
    }


def _rounded(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _mm(a, b, operands=None):
    return _rounded(a, operands) @ _rounded(b, operands)


def rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def turn(x, angle):
    """[B, T, H, R]: the pair (i, i + R/2) turns by ``angle [B, T, R/2]``."""
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    a, b = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def frequencies(n, theta):
    return theta ** (-jnp.arange(n, dtype=jnp.float32) / n)


def mrope(x, positions, theta, section):
    """``positions [3, B, T]``: frequency i of the R/2 takes the time stream
    for i < s0, height for s0 <= i < s0 + s1, width after."""
    half = x.shape[-1] // 2
    which = jnp.concatenate([
        jnp.full((n,), stream, jnp.int32) for stream, n in enumerate(section)
    ])
    pos = jnp.moveaxis(positions.astype(jnp.float32)[which], 0, -1)
    return turn(x, pos * frequencies(half, theta))


def rotary_front(x, positions, theta, dims):
    """1-D rotary on the first ``dims`` of the last axis, by ``positions
    [B, T]``."""
    angle = positions.astype(jnp.float32)[..., None] * frequencies(dims // 2, theta)
    return jnp.concatenate([turn(x[..., :dims], angle), x[..., dims:]], -1)


def attention_qkv(a, p, arch, positions, operands=None):
    b, t, _ = a.shape
    heads = lambda x: x.reshape(b, t, -1, arch["head_dim"])  # noqa: E731
    q, k, v = (
        heads(_mm(a, p[name]["kernel"], operands))
        for name in ("q_proj", "k_proj", "v_proj")
    )
    q, k = rms(q, p["q_norm"], arch["eps"]), rms(k, p["k_norm"], arch["eps"])
    rope = lambda x: mrope(x, positions, arch["theta"], arch["section"])  # noqa: E731
    return rope(q), rope(k), v


def indexer_inputs(a, p, arch, positions, operands=None):
    """qI [B, T, HI, DI], kI [B, T, DI], w [B, T, HI] of the indexer's input
    ``a`` (the layer's normed input, stopped)."""
    b, t, _ = a.shape
    hi, di = arch["indexer_heads"], arch["indexer_dim"]
    qi = _mm(a, p["wq"]["kernel"], operands).reshape(b, t, hi, di)
    ki = layer_norm(_mm(a, p["wk"]["kernel"], operands), p["k_norm"])
    w = _mm(a, p["weights_proj"], operands) * (hi**-0.5 * di**-0.5)
    pos = positions[0]
    qi = rotary_front(qi, pos, arch["theta"], di // 2)
    ki = rotary_front(ki[:, :, None], pos, arch["theta"], di // 2)[:, :, 0]
    return qi, ki, w


def indexer_scores(qi, ki, w, operands=None, scores=None):
    """I [B, C, T] of a chunk of queries (qi [B, C, HI, DI], w [B, C, HI])
    against every key (ki [B, T, DI]). A ``scores`` dtype rounds I by
    ``reduce_precision``, not by a pair of casts, which XLA:TPU drops."""
    z = jnp.einsum(
        "bchd,bsd->bchs", _rounded(qi, operands), _rounded(ki, operands)
    )
    i = jnp.einsum("bchs,bch->bcs", jax.nn.relu(z), w)
    if scores is not None:
        info = jnp.finfo(scores)
        i = jax.lax.reduce_precision(i, info.nexp, info.nmant)
    return i


def selection(i, start, topk):
    """Mask [B, C, T] of each query's ``topk`` largest causal scores among
    ``i [B, C, T]``, queries ``start .. start + C - 1``."""
    b, c, t = i.shape
    causal = jnp.arange(t)[None, :] <= start + jnp.arange(c)[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, i, -jnp.inf), min(topk, t))
    mask = jnp.zeros((b, c, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(c)[None, :, None], idx
    ].set(True)
    return mask & causal


def masked_attention(q, k, v, mask, i, operands=None, kl_target_grad=False):
    """``(o [B, C, H, d], kl [B, C])`` of a chunk of queries over ``mask
    [B, C, T]``, with the chunk's indexer scores ``i`` for the KL."""
    b, c, h, d = q.shape
    kvh = k.shape[2]
    q = _rounded(q, operands).reshape(b, c, kvh, h // kvh, d)
    s = jnp.einsum("bqjgd,bkjd->bjgqk", q, _rounded(k, operands)) / jnp.sqrt(
        jnp.float32(d)
    )
    keep = mask[:, None, None]
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    o = jnp.einsum(
        "bjgqk,bkjd->bqjgd", _rounded(probs, operands), _rounded(v, operands)
    ).reshape(b, c, h, d)
    p = jnp.mean(probs, (1, 2))  # [B, C, T]
    if not kl_target_grad:
        p = jax.lax.stop_gradient(p)
    # nought off the selection: the product's gradient there is then 0, not
    # 0 x inf, where the target is differentiated
    logq = jnp.where(mask, jax.nn.log_softmax(jnp.where(mask, i, -jnp.inf), -1), 0.0)
    logp = jnp.log(jnp.where(p > 0, p, 1.0))
    kl = jnp.sum(jnp.where(mask, p * (logp - logq), 0.0), -1)
    return o, kl


def sparse_attention(q, k, v, qi, ki, w, topk, chunk, operands=None,
                     scores=None, kl_target_grad=False, mask_of=None):
    """``(o [B, T, H, d], kl [B, T])``, ``chunk`` queries at a time.
    ``mask_of(start, i)`` gives a chunk's selection where another's is to be
    used (a program's), else ``selection``."""
    b, t, h, d = q.shape
    chunk = min(chunk, t)

    @jax.checkpoint
    def one(args):
        q_c, qi_c, w_c, start = args
        i = indexer_scores(qi_c, ki, w_c, operands, scores)
        mask = (
            selection(jax.lax.stop_gradient(i), start, topk)
            if mask_of is None else mask_of(start, i)
        )
        return masked_attention(q_c, k, v, mask, i, operands, kl_target_grad)

    split = lambda x: x.reshape(b, t // chunk, chunk, *x.shape[2:]).swapaxes(0, 1)  # noqa: E731
    o, kl = jax.lax.map(
        one, (split(q), split(qi), split(w), jnp.arange(0, t, chunk))
    )
    return o.swapaxes(0, 1).reshape(b, t, h, d), kl.swapaxes(0, 1).reshape(b, t)


def attention(a, p, arch, positions, chunk, operands=None, **faults):
    """The attention branch and its layer's KL (the mean over queries)."""
    q, k, v = attention_qkv(a, p, arch, positions, operands)
    qi, ki, w = indexer_inputs(
        jax.lax.stop_gradient(a), p["indexer"], arch, positions, operands
    )
    topk = faults.pop("topk", None) or arch["topk"]
    out, kl = sparse_attention(q, k, v, qi, ki, w, topk, chunk, operands,
                               **faults)
    o = _mm(out.reshape(*a.shape[:2], -1), p["o_proj"]["kernel"], operands)
    return o, jnp.mean(kl)


def router_logits(x, w_router, router=None):
    """``x W_r`` [N, E] over all published experts. A ``router`` dtype
    rounds the logits by ``reduce_precision``, not by a pair of casts:
    XLA:TPU drops such a pair (it allows excess precision)."""
    logits = _mm(x, w_router, router)
    if router is not None:
        info = jnp.finfo(router)
        logits = jax.lax.reduce_precision(logits, info.nexp, info.nmant)
    return logits


def route(x, w_router, arch, router=None):
    """Chosen experts [N, k] (the largest logits) and their weights [N, k]
    (softmax over the chosen logits)."""
    top, sel = jax.lax.top_k(router_logits(x, w_router, router), arch["top_k"])
    return sel, jax.nn.softmax(top, -1)


def expert_layer(u, p, sel, w, arch, operands=None):
    """[N, D] -> the HELD experts' part of the routed sum on the picks
    ``sel`` weighed by ``w``; expert ``arch["held"][j]`` has the weights
    ``p["experts_*"][j]``."""

    @jax.checkpoint
    def add_expert(y, expert):
        ident, gate, up, down = expert
        mine = jnp.sum(jnp.where(sel == ident, w, 0.0), -1)  # [N]
        out = _mm(
            jax.nn.silu(_mm(u, gate, operands)) * _mm(u, up, operands), down,
            operands,
        )
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.asarray(arch["held"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    return y


def decoder_layer(x, p, arch, positions, chunk, operands=None, router=None,
                  **faults):
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
    o, kl = attention(
        rms(x, p["input_layernorm"], arch["eps"]), p["self_attn"], arch,
        positions, chunk, operands, **faults,
    )
    h = x + o
    u = flat(rms(h, p["post_attention_layernorm"], arch["eps"]))
    sel, w = route(u, p["router"]["kernel"], arch, router)
    return h + expert_layer(u, p["moe"], sel, w, arch, operands).reshape(
        h.shape
    ), kl


def text_positions(batch, t):
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, batch, t))


def forward(params, tokens, arch, *, positions=None, chunk=512, operands=None,
            router=None, **faults):
    """Logits [B, T, V] of ``tokens`` [B, T] and the KL term (the mean of
    the layers')."""
    if positions is None:
        positions = text_positions(*tokens.shape)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens]
        kls = []
        for i in range(arch["layers"]):
            layer = jax.checkpoint(
                lambda x, p: decoder_layer(
                    x, p, arch, positions, chunk, operands, router, **faults
                )
            )
            x, kl = layer(x, params[f"layers_{i}"])
            kls.append(kl)
        logits = _mm(
            rms(x, params["norm"], arch["eps"]), params["lm_head"], operands
        )
        return logits, jnp.mean(jnp.stack(kls))


def losses(params, tokens, targets, arch, **kw):
    """(cross-entropy, KL term)."""
    logits, kl = forward(params, tokens, arch, **kw)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1)), kl


def loss(params, tokens, targets, arch, **kw):
    ce, kl = losses(params, tokens, targets, arch, **kw)
    return ce + kl


def loss_and_grads(params, tokens, targets, arch, **kw):
    """Loss (cross-entropy + KL) and its gradient for every parameter,
    float32."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, tokens, targets, arch, **kw)
