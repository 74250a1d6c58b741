"""AdamW as published (Loshchilov and Hutter 2019, algorithm 2, with
``torch.optim.AdamW``'s defaults, which ``optim.adamw`` states as its own),
after a clip of the gradient's global norm, at a rate that may warm up
linearly: plain ``jax.numpy``, float32, no optax, nothing of the program.

    g   = grads * min(1, clip / |grads|)                (where a clip is set)
    m   = b1 m + (1 - b1) g;    v = b2 v + (1 - b2) g^2
    u   = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps) + decay * p
    p   = p - rate(t - 1) * u                           (t = 1 at the first step)

``optimizer`` is a cell's ``job_params.optimizer`` block, the same that
``jobs/trainstep.optimizer`` hands the program: ``lr``, ``warmup_steps``
(the rate climbs linearly from 0 at step 0 to ``lr`` at that step, then
stays), ``clip_grad_norm``, ``betas``, ``eps``, ``weight_decay``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

BETAS, EPS, WEIGHT_DECAY = (0.9, 0.999), 1e-8, 0.01  # torch.optim.AdamW's


def rate(optimizer: dict, step: int) -> float:
    """The learning rate of optimizer step ``step`` (0 the first)."""
    warm = optimizer.get("warmup_steps")
    if not warm:
        return optimizer["lr"]
    return optimizer["lr"] * min(1.0, step / warm)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def leaf_norms(tree) -> list:
    """The norm of every leaf, in ``jax.tree.leaves``' order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def clipped(optimizer: dict, grads):
    """The gradient as the update gets it, and its norm before the clip."""
    norm = global_norm(grads)
    clip = optimizer.get("clip_grad_norm")
    if clip is None:
        return grads, norm
    scale = jnp.where(norm > clip, clip / jnp.maximum(norm, 1e-38), 1.0)
    return jax.tree.map(lambda g: g * scale, grads), norm


def update(optimizer: dict, step, rate_now, params, m, v, grads):
    """``(params, m, v)`` after optimizer step ``step`` (0 the first; may be
    traced, as may ``rate_now``, so that one program serves every step) on
    the already clipped ``grads``."""
    b1, b2 = optimizer.get("betas", BETAS)
    eps = optimizer.get("eps", EPS)
    decay = optimizer.get("weight_decay", WEIGHT_DECAY)
    t = jnp.asarray(step, jnp.float32) + 1.0
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - rate_now * (
            (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps) + decay * p
        ),
        params, m, v,
    )
    return params, m, v
