"""Trinity-Mini (``afmoe``) as its ``config.json`` and the public
``transformers`` ``models/afmoe/modeling_afmoe.py`` describe it, in plain
``jax.numpy``: float32 throughout, under
``jax.default_matmul_precision("highest")``, no Flax module, no kernel, no
sorting, no buffer.

    x0 = embed[tokens] * sqrt(D)
    layer l: a = rms_in(x); q = a W_q -> H x d; k = a W_k, v = a W_v -> KVH x d
             g = a W_g -> H x d; q = rms_q(q), k = rms_k(k) over each head's d
             sliding[l]: rotary (split halves, theta) on all d of q and k and
                         query t sees keys t - W + 1 .. t; else no positions
                         at all and keys 0 .. t
             head h reads key-value head h // (H / KVH)
             h = x + rms_post_attn((softmax(q k^T / sqrt(d)) v * sigmoid(g)) W_o)
             u = rms_pre_mlp(h)
             l < dense: f = W_down(silu(W_gate u) * W_up u)
             else:      s = sigmoid(u W_r); sel = top_k(s + b);
                        w = s[sel] / (sum s[sel] + 1e-20) * scale;
                        f = sum_{i in sel and held} w_i E_i(u) + E_shared(u)
             y = h + rms_post_mlp(f)
    then a final rms and an untied head
    loss:    mean token cross-entropy over the vocabulary held

It is given the same share as the program: ``arch["held"]`` lists the
experts held, the router keeps its published width, and what the absent
experts would add is left out. The held experts run as a loop, every expert
over every token under a mask: nothing is sorted, nothing can be dropped.

Takes the parameter tree of ``models/afmoe.py`` (names only) and the
``router_state`` biases. Departures that change no arithmetic, so that the
gradient pass at 8,192 tokens fits beside the training state: attention
takes the queries in chunks (32 x 8,192 x 8,192 scores never exist at once;
the mask is a ``where`` over each chunk's scores), and each layer, each chunk
and each expert of the loop is recomputed in the backward pass
(``jax.checkpoint``).

``operands`` / ``router`` (default ``None``: float32) round every matmul's
operands, or the router's operands and scores, to a narrower dtype first:
not the reference, but the reading of "one precision lower" that a cell's
tolerance has to refuse.

This text lives twice and is held to one: ``chipbench/reference/afmoe.py``
(the benchmark's copy: the comparison that decides ``correct`` reads nothing
of the program's own model code) and ``pytorch_distributedtraining_tpu/
models/afmoe_reference.py`` (the repository's); ``tests/test_afmoe.py``
compares the two files.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def arch_of(config: dict) -> dict:
    """What the equations need, from a configuration file's published keys
    (``num_experts`` there is the number HELD: experts 0..n-1)."""
    return {
        "layers": config["num_hidden_layers"],
        "dense": config["num_dense_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "windowed": tuple(
            kind == "sliding_attention" for kind in config["layer_types"]
        ),
        "top_k": config["num_experts_per_tok"],
        "scaling": config["route_scale"],
        "norm_topk": config["route_norm"],
        "embed_scale": (
            float(config["hidden_size"]) ** 0.5 if config["mup_enabled"]
            else 1.0
        ),
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "held": tuple(range(config["num_experts"])),
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


def attention_qkv(a, p, arch, windowed, operands=None):
    """q [B, T, H, d], k and v [B, T, KVH, d] as the core takes them: after
    the head norms and, in a sliding layer, rotary."""
    b, t, _ = a.shape
    heads = lambda x: x.reshape(b, t, -1, arch["head_dim"])  # noqa: E731
    q, k, v = (
        heads(_mm(a, p[name]["kernel"], operands))
        for name in ("q_proj", "k_proj", "v_proj")
    )
    q, k = rms(q, p["q_norm"], arch["eps"]), rms(k, p["k_norm"], arch["eps"])
    if windowed:
        q, k = rotary(q, arch["theta"]), rotary(k, arch["theta"])
    return q, k, v


def attention(a, p, arch, layer, chunk, operands=None):
    windowed = arch["windowed"][layer]
    q, k, v = attention_qkv(a, p, arch, windowed, operands)
    out = banded_attention(
        q, k, v, arch["window"] if windowed else None, chunk, operands
    )
    gate = jax.nn.sigmoid(_mm(a, p["gate_proj"]["kernel"], operands))
    return _mm(
        out.reshape(*a.shape[:2], -1) * gate, p["o_proj"]["kernel"], operands
    )


def gated(x, gate, up, down, operands=None):
    return _mm(
        jax.nn.silu(_mm(x, gate, operands)) * _mm(x, up, operands), down,
        operands,
    )


def gated_mlp(x, p, operands=None):
    return gated(
        x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"], operands,
    )


def router_scores(x, w_router, router=None):
    """``sigmoid(x W_r)`` [N, E] over all published experts. A ``router``
    dtype rounds the logits by ``reduce_precision``, not by a pair of casts:
    XLA:TPU drops such a pair (it allows excess precision) and the reading
    would be float32's."""
    logits = _mm(x, w_router, router)
    if router is not None:
        info = jnp.finfo(router)
        logits = jax.lax.reduce_precision(logits, info.nexp, info.nmant)
    return jax.nn.sigmoid(logits)


def route(x, w_router, bias, arch, router=None):
    """Chosen experts [N, k] (by score + bias) and their weights [N, k] (by
    score alone, renormalised, scaled)."""
    scores = router_scores(x, w_router, router)
    _, sel = jax.lax.top_k(scores + bias[None, :], arch["top_k"])
    w = jnp.take_along_axis(scores, sel, -1)
    if arch["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return sel, w * arch["scaling"]


def expert_layer(x, p, bias, arch, operands=None, router=None):
    """[N, D] -> the shared expert, unweighted, plus the HELD experts' part
    of the routed sum; expert ``arch["held"][j]`` has the weights
    ``p["experts_*"][j]``."""
    sel, w = route(x, p["router"], jax.lax.stop_gradient(bias), arch, router)

    @jax.checkpoint
    def add_expert(y, expert):
        ident, gate, up, down = expert
        mine = jnp.sum(jnp.where(sel == ident, w, 0.0), -1)  # [N]
        return y + mine[:, None] * gated(x, gate, up, down, operands), None

    y, _ = jax.lax.scan(
        add_expert, gated_mlp(x, p["mlp_shared"], operands),
        (jnp.asarray(arch["held"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    return y


def attention_branch(x, p, arch, layer, chunk, operands=None):
    """``h``: the stream once the layer's attention branch has written."""
    eps = arch["eps"]
    branch = attention(
        rms(x, p["input_layernorm"], eps), p["attn"], arch, layer, chunk,
        operands,
    )
    return x + rms(branch, p["post_attention_layernorm"], eps)


def mlp_branch(h, p, bias, arch, layer, operands=None, router=None):
    """``y``: the stream once the layer's dense MLP or expert layer has;
    ``bias`` is the expert layer's selection bias, None in a dense layer."""
    eps = arch["eps"]
    u = rms(h, p["pre_mlp_layernorm"], eps)
    if layer < arch["dense"]:
        branch = gated_mlp(u, p["mlp_dense"], operands)
    else:
        branch = expert_layer(
            u.reshape(-1, u.shape[-1]), p["moe"], bias, arch, operands, router
        ).reshape(u.shape)
    return h + rms(branch, p["post_mlp_layernorm"], eps)


def decoder_layer(x, p, bias, arch, layer, chunk, operands=None, router=None):
    h = attention_branch(x, p, arch, layer, chunk, operands)
    return mlp_branch(h, p, bias, arch, layer, operands, router)


def layer_bias(router_state, arch, layer):
    if layer < arch["dense"]:
        return None
    return router_state[f"layers_{layer}"]["moe"]["bias"]


def forward(params, router_state, tokens, arch, *, chunk=512, operands=None,
            router=None):
    """Logits [B, T, V] of ``tokens`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens] * arch["embed_scale"]
        for i in range(arch["layers"]):
            layer = jax.checkpoint(
                lambda x, p, bias, i=i: decoder_layer(
                    x, p, bias, arch, i, chunk, operands, router
                )
            )
            x = layer(x, params[f"layers_{i}"], layer_bias(router_state, arch, i))
        return _mm(
            rms(x, params["norm_f"], arch["eps"]), params["lm_head"], operands
        )


def loss(params, router_state, tokens, targets, arch, **kw):
    logp = jax.nn.log_softmax(forward(params, router_state, tokens, arch, **kw))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grads(params, router_state, tokens, targets, arch, **kw):
    """Mean token loss and its gradient for every parameter, float32."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(
            params, router_state, tokens, targets, arch, **kw
        )


def loss_and_grad_norm(params, router_state, tokens, targets, arch, **kw):
    value, grads = loss_and_grads(
        params, router_state, tokens, targets, arch, **kw
    )
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
