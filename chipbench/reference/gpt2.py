"""GPT-2 as published (Radford et al. 2019; openai-community/gpt2
``modeling_gpt2.py``): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU(tanh) MLP, a final
LayerNorm, the output head tied to the token embedding, mean token
cross-entropy. Float32 throughout, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul runs
in bf16 passes otherwise).

Takes the parameter tree of ``models/gpt2.py`` in either layout (``h_<i>``
per layer, or ``h`` stacked on a leading axis) and nothing else from it.
Departures from the description, neither changing the arithmetic: the
layers run under ``lax.scan`` and each is recomputed in the backward pass
(``jax.checkpoint``), and the batch is taken in chunks with the gradient
summed over them, so that the 48-layer model's float32 attention fits
beside the training state it is compared with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def stacked_layers(params: dict, n_layer: int) -> dict:
    """Per-layer parameters on a leading axis, from either layout."""
    if "h" in params:
        return params["h"]
    layers = [params[f"h_{i}"] for i in range(n_layer)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)
    ))


def block(x, p, n_head: int):
    b, t, d = x.shape
    q, k, v = jnp.split(dense(layer_norm(x, p["ln_1"]), p["c_attn"]), 3, -1)
    heads = lambda a: a.reshape(b, t, n_head, d // n_head)  # noqa: E731
    scores = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k))
    scores = scores / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), heads(v)
    ).reshape(b, t, d)
    x = x + dense(attn, p["c_proj"])
    y = gelu_tanh(dense(layer_norm(x, p["ln_2"]), p["mlp_fc"]))
    return x + dense(y, p["mlp_proj"])


def nll_sum(params, tokens, targets, *, n_layer: int, n_head: int):
    """Summed next-token negative log-likelihood over ``tokens`` [B, T]."""
    t = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:t]

    @jax.checkpoint
    def body(x, layer):
        return block(x, layer, n_head), None

    x, _ = jax.lax.scan(body, x, stacked_layers(params, n_layer))
    logits = layer_norm(x, params["ln_f"]) @ params["wte"].T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params, tokens, targets, *, n_layer, n_head, chunks):
    """Mean loss over the batch and the global L2 norm of its gradient,
    the batch taken in ``chunks`` equal parts."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    split = lambda a: a.reshape(chunks, -1, *a.shape[1:])  # noqa: E731
    grad_fn = jax.value_and_grad(nll_sum)

    def body(carry, chunk):
        total, grads = carry
        tok, tgt = chunk
        loss, g = grad_fn(params, tok, tgt, n_layer=n_layer, n_head=n_head)
        return (total + loss, jax.tree.map(jnp.add, grads, g)), None

    with jax.default_matmul_precision("highest"):
        zero = jax.tree.map(jnp.zeros_like, params)
        (total, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), zero), (split(tokens), split(targets))
        )
    n = tokens.size
    sq = sum(jnp.sum(jnp.square(g / n)) for g in jax.tree.leaves(grads))
    return total / n, jnp.sqrt(sq)
