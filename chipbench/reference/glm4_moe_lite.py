"""GLM-4.7-Flash (``glm4_moe_lite``) as its ``config.json`` and the
DeepSeek-V3 line of ``modeling_*.py`` describe it, in plain ``jax.numpy``:
float32 throughout, under ``jax.default_matmul_precision("highest")``, no
Flax module, no kernel, no sorting.

    block:   h = x + MLA(rms(x));  y = h + FFN(rms(h));  final rms; untied head
    MLA:     c_q = rms(x W_qa); q = c_q W_qb -> heads x (nope + rope)
             [c_kv, k_r] = x W_kva; [k_nope, v] = rms(c_kv) W_kvb
             rotary (split halves, theta) on q's rope part and on k_r, which
             is ONE key part for all heads; k = [k_nope, k_r]
             softmax_causal(q k^T / sqrt(nope + rope)) v, then W_o
    FFN:     layer < first_dense: W_down(silu(W_gate x) * W_up x)
             later: s = sigmoid(x W_r); sel = top_k(s + b);
                    w = s[sel] / (sum s[sel] + 1e-20) * scaling;
                    sum_{i in sel and held} w_i E_i(x) + E_shared(x)
    loss:    mean token cross-entropy over the vocabulary held

It is given the same share as the program: ``arch["held"]`` lists the
experts held, the router keeps its published width, and what the absent
experts would add is left out. The held experts run as a loop, every expert
over every token under a mask: nothing is sorted, nothing can be dropped.

Takes the parameter tree of ``models/glm4_moe_lite.py`` (names only) and the
``router_state`` biases. Departures that change no arithmetic, so that the
gradient pass at 2 x 4,096 tokens fits beside 7.1 GB of training state:
attention takes the queries in chunks, and each layer, each chunk and each
expert of the loop is recomputed in the backward pass (``jax.checkpoint``).

``operands`` / ``router`` (default ``None``: float32) round every matmul's
operands, or the router's operands and scores, to a narrower dtype first:
not the reference, but the reading of "one precision lower" that a cell's
tolerance has to refuse (PERF.md, PR 27).

This text lives twice and is held to one: ``chipbench/reference/
glm4_moe_lite.py`` (the benchmark's copy: the comparison that decides
``correct`` reads nothing of the program's own model code) and
``pytorch_distributedtraining_tpu/models/glm4_moe_lite_reference.py`` (the
repository's); ``tests/test_glm4_moe_lite.py`` compares the two files.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def arch_of(config: dict) -> dict:
    """What the equations need, from a configuration file's published keys
    (``n_routed_experts`` there is the number HELD: experts 0..n-1)."""
    return {
        "layers": config["num_hidden_layers"],
        "first_dense": config["first_k_dense_replace"],
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"],
        "top_k": config["num_experts_per_tok"],
        "scaling": config["routed_scaling_factor"],
        "norm_topk": config["norm_topk_prob"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "held": tuple(range(config["n_routed_experts"])),
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


def causal_attention(q, k, v, chunk, operands=None):
    """softmax(q k^T / sqrt(d)) v under a causal mask, ``chunk`` queries at
    a time against all keys ([B, H, chunk, T] scores, never [T, T])."""
    b, t, h, d = q.shape
    chunk = min(chunk, t)
    kpos = jnp.arange(t)
    k, v = _rounded(k, operands), _rounded(v, operands)

    @jax.checkpoint
    def one(args):
        q_c, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", _rounded(q_c, operands), k)
        s = s / jnp.sqrt(jnp.float32(d))
        qpos = start + jnp.arange(chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = _rounded(jax.nn.softmax(s, -1), operands)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    chunks = q.reshape(b, t // chunk, chunk, h, d).swapaxes(0, 1)
    out = jax.lax.map(one, (chunks, jnp.arange(0, t, chunk)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def mla_qkv(x, p, arch, operands=None):
    b, t, _ = x.shape
    h, nope, rope = arch["heads"], arch["nope"], arch["rope"]
    c_q = rms(_mm(x, p["q_a_proj"]["kernel"], operands), p["norm_q"], arch["eps"])
    q = _mm(c_q, p["q_b_proj"]["kernel"], operands).reshape(b, t, h, nope + rope)
    latent = _mm(x, p["kv_a_proj_with_mqa"]["kernel"], operands)
    c_kv, k_r = latent[..., : arch["kv_rank"]], latent[..., arch["kv_rank"]:]
    kv = _mm(
        rms(c_kv, p["norm_kv"], arch["eps"]), p["kv_b_proj"]["kernel"], operands
    ).reshape(b, t, h, nope + arch["v_dim"])
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], arch["theta"])], -1
    )
    k_r = rotary(k_r[:, :, None, :], arch["theta"])  # one key part, all heads
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], -1
    )
    return q, k, kv[..., nope:]


def mla(x, p, arch, chunk, operands=None):
    q, k, v = mla_qkv(x, p, arch, operands)
    out = causal_attention(q, k, v, chunk, operands)
    return _mm(out.reshape(*x.shape[:2], -1), p["o_proj"]["kernel"], operands)


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
    """[N, D] -> the shared expert plus the HELD experts' part of the routed
    sum; expert ``arch["held"][j]`` has the weights ``p["experts_*"][j]``."""
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


def forward(params, router_state, tokens, arch, *, chunk=512, operands=None,
            router=None):
    """Logits [B, T, V] of ``tokens`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        return _forward(
            params, router_state, tokens, arch, chunk, operands, router
        )


def _forward(params, router_state, tokens, arch, chunk, operands, router):
    x = params["embed_tokens"][tokens]
    for i in range(arch["layers"]):
        name = f"layers_{i}"
        bias = None if i < arch["first_dense"] else (
            router_state[name]["moe"]["bias"]
        )

        @jax.checkpoint
        def layer(x, p, bias=bias, dense=i < arch["first_dense"]):
            h = x + mla(
                rms(x, p["norm_attn"], arch["eps"]), p["mla"], arch, chunk,
                operands,
            )
            y = rms(h, p["norm_ffn"], arch["eps"])
            if dense:
                return h + gated_mlp(y, p["mlp_dense"], operands)
            flat = y.reshape(-1, y.shape[-1])
            return h + expert_layer(
                flat, p["moe"], bias, arch, operands, router
            ).reshape(y.shape)

        x = layer(x, params[name])
    return _mm(rms(x, params["norm_f"], arch["eps"]), params["lm_head"], operands)


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
