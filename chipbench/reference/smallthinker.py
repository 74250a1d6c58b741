"""SmallThinker-21BA3B-Instruct (``smallthinker_21b_instruct``) as its
``config.json`` and its model card describe it, in plain ``jax.numpy``:
float32 throughout, under ``jax.default_matmul_precision("highest")``, no
Flax module, no kernel, no sorting, no buffer.

    layer l: r = x W_r                       (the router reads the RAW input)
             a = rms(x); q = a W_q -> H x d; k = a W_k, v = a W_v -> KVH x d
             rope[l]:   rotary (split halves, theta) on all d of q and k,
                        else no positions at all
             head h reads key-value head h // (H / KVH); causal;
             window[l]: query t sees keys t - W + 1 .. t, else 0 .. t
             h = x + concat(heads) W_o
             u = rms(h); sel = top_k(r); w = softmax(r[sel])
             y = h + sum_{i in sel and held} w_i (relu(u G_i) * (u U_i)) D_i
    then a final rms and an untied head
    loss:    mean token cross-entropy over the vocabulary held

It is given the same share as the program: ``arch["held"]`` lists the
experts held, the router keeps its published width, and what the absent
experts would add is left out. The held experts run as a loop, every expert
over every token under a mask: nothing is sorted, nothing can be dropped.

Takes the parameter tree of ``models/smallthinker.py`` (names only).
Departures that change no arithmetic, so that the gradient pass at 16,384
tokens fits beside the training state: attention takes the queries in
chunks (28 x 16,384 x 16,384 scores never exist at once; the mask is a
``where`` over each chunk's scores), and each layer, each chunk and each
expert of the loop is recomputed in the backward pass (``jax.checkpoint``).

``operands`` / ``router`` (default ``None``: float32) round every matmul's
operands, or the router's operands and logits, to a narrower dtype first:
not the reference, but the reading of "one precision lower" that a cell's
tolerance has to refuse.

This text lives twice and is held to one: ``chipbench/reference/
smallthinker.py`` (the benchmark's copy: the comparison that decides
``correct`` reads nothing of the program's own model code) and
``pytorch_distributedtraining_tpu/models/smallthinker_reference.py`` (the
repository's); ``tests/test_smallthinker.py`` compares the two files.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def arch_of(config: dict) -> dict:
    """What the equations need, from a configuration file's published keys
    (``moe_num_primary_experts`` there is the number HELD: experts 0..n-1)."""
    return {
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window_size"],
        "windowed": tuple(config["sliding_window_layout"]),
        "rope": tuple(config["rope_layout"]),
        "top_k": config["moe_num_active_primary_experts"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "held": tuple(range(config["moe_num_primary_experts"])),
    }


def _rounded(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _mm(a, b, operands=None):
    return _rounded(a, operands) @ _rounded(b, operands)


def rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def rotary(x, theta):
    """[B, T, H, R]: the pair (i, i + R/2) turns by position * theta^(-2i/R)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def banded_attention(q, k, v, window, chunk, operands=None):
    """softmax(q k^T / sqrt(d)) v, causal, query head h on key-value head
    h // (H / KVH), under a ``window`` (None: none) keys t - window + 1 .. t
    only; ``chunk`` queries at a time against all keys ([B, H, chunk, T]
    scores, never [T, T])."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    chunk = min(chunk, t)
    kpos = jnp.arange(t)
    k, v = _rounded(k, operands), _rounded(v, operands)

    @jax.checkpoint
    def one(args):
        q_c, start = args
        q_c = _rounded(q_c, operands).reshape(b, chunk, kvh, h // kvh, d)
        s = jnp.einsum("bqjgd,bkjd->bjgqk", q_c, k) / jnp.sqrt(jnp.float32(d))
        qpos = start + jnp.arange(chunk)
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep = keep & (kpos[None, :] > qpos[:, None] - window)
        p = _rounded(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), operands)
        return jnp.einsum("bjgqk,bkjd->bqjgd", p, v).reshape(b, chunk, h, d)

    chunks = q.reshape(b, t // chunk, chunk, h, d).swapaxes(0, 1)
    out = jax.lax.map(one, (chunks, jnp.arange(0, t, chunk)))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def attention_qkv(a, p, arch, rope, operands=None):
    b, t, _ = a.shape
    heads = lambda x: x.reshape(b, t, -1, arch["head_dim"])  # noqa: E731
    q, k, v = (
        heads(_mm(a, p[name]["kernel"], operands))
        for name in ("q_proj", "k_proj", "v_proj")
    )
    if rope:
        q, k = rotary(q, arch["theta"]), rotary(k, arch["theta"])
    return q, k, v


def attention(a, p, arch, layer, chunk, operands=None):
    q, k, v = attention_qkv(a, p, arch, arch["rope"][layer], operands)
    window = arch["window"] if arch["windowed"][layer] else None
    out = banded_attention(q, k, v, window, chunk, operands)
    return _mm(out.reshape(*a.shape[:2], -1), p["o_proj"]["kernel"], operands)


def router_logits(x, w_router, router=None):
    """``x W_r`` [N, E] over all published experts. A ``router`` dtype
    rounds the logits by ``reduce_precision``, not by a pair of casts:
    XLA:TPU drops such a pair (it allows excess precision) and the reading
    would be float32's."""
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
            jax.nn.relu(_mm(u, gate, operands)) * _mm(u, up, operands), down,
            operands,
        )
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.asarray(arch["held"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    return y


def decoder_layer(x, p, arch, layer, chunk, operands=None, router=None):
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
    sel, w = route(flat(x), p["router"]["kernel"], arch, router)
    h = x + attention(
        rms(x, p["norm_attn"], arch["eps"]), p["attn"], arch, layer, chunk,
        operands,
    )
    u = rms(h, p["norm_ffn"], arch["eps"])
    return h + expert_layer(flat(u), p["moe"], sel, w, arch, operands).reshape(
        u.shape
    )


def forward(params, tokens, arch, *, chunk=512, operands=None, router=None):
    """Logits [B, T, V] of ``tokens`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens]
        for i in range(arch["layers"]):
            layer = jax.checkpoint(
                lambda x, p, i=i: decoder_layer(
                    x, p, arch, i, chunk, operands, router
                )
            )
            x = layer(x, params[f"layers_{i}"])
        return _mm(
            rms(x, params["norm_f"], arch["eps"]), params["lm_head"], operands
        )


def loss(params, tokens, targets, arch, **kw):
    logp = jax.nn.log_softmax(forward(params, tokens, arch, **kw))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grads(params, tokens, targets, arch, **kw):
    """Mean token loss and its gradient for every parameter, float32."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, tokens, targets, arch, **kw)


def loss_and_grad_norm(params, tokens, targets, arch, **kw):
    value, grads = loss_and_grads(params, tokens, targets, arch, **kw)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
