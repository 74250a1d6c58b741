"""The held part of a routed expert layer: one piece of code for every model
whose expert layer is told which experts it holds.

A caller (``models/glm4_moe_lite.py``, ``models/smallthinker.py``) routes:
it scores its tokens over all the published experts however its model says
(sigmoid and a selection bias; softmax over the picked logits), picks k a
token and weighs them. ``held_experts_sum`` does what is the same for all of
them, inside the caller's Flax module (the parameters are the caller's:
``experts_gate``, ``experts_up``, ``experts_down``, one matrix a held
expert): the assignments are sorted by held expert into a buffer of N x k
rows (the worst case: all of a token's experts may be here, so **no
assignment is dropped**), ``ops.grouped_matmul`` runs the gated MLPs over the
rows that exist, and the results go back to their tokens, weighed and
summed. Assignments to absent experts are left out: what one chip of an
expert-parallel job computes before the exchange. What differs between
models comes as arguments: the picks and weights, the gate's activation, an
optional shared expert.

Counters of the routing are sown into the ``moe_counters`` collection as
device scalars; ``routing_counters`` reduces them over a model's expert
layers into the scalars a step reports.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.grouped_matmul import grouped_matmul

MOE_COUNTERS = "moe_counters"  # what the routing did this call
MOE_PROBE = "moe_probe"  # what a layer was given and gave, for a reference


@jax.custom_vjp
def spread_rows(tokens, order, slot):
    """``tokens`` [N, D] -> one row an assignment, in sorted order: row ``r``
    is the token of assignment ``order[r]`` (a token has k = M / N
    assignments, ``n * k .. n * k + k - 1``). ``slot`` is ``order``'s
    inverse. The gradient of a gather is a scatter-add, which XLA:TPU runs
    at a fraction of a gather's pace; since every row is read by exactly
    one assignment, the gradient is a gather too (by ``slot``, summed over
    a token's k rows), and is written as one."""
    return tokens[order // (order.shape[0] // tokens.shape[0])]


def _spread_fwd(tokens, order, slot):
    return spread_rows(tokens, order, slot), (slot, tokens.shape[0])


def _spread_bwd(res, g):
    slot, n = res
    per_token = g[slot].reshape(n, -1, g.shape[-1]).astype(jnp.float32)
    return jnp.sum(per_token, 1).astype(g.dtype), None, None


spread_rows.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def collect_rows(rows, slot, order):
    """``rows`` [M, D] in sorted order -> in assignment order (``rows[slot]``);
    ``slot`` and ``order`` are inverse permutations, so the gradient is the
    gather by ``order`` (see :func:`spread_rows`)."""
    return rows[slot]


def _collect_fwd(rows, slot, order):
    return rows[slot], order


def _collect_bwd(order, g):
    return g[order], None, None


collect_rows.defvjp(_collect_fwd, _collect_bwd)


def expert_loads(sel, n_experts: int):
    """Assignments each published expert got: ``sel`` [N, k] -> [E] float32."""
    return jnp.zeros((n_experts,), jnp.float32).at[sel.reshape(-1)].add(1.0)


def held_experts_sum(
    module, tokens, sel, weights, load, *, held, width, gate, init, dtype,
    interpret=False, shared=None,
):
    """``tokens`` [N, D], the picks ``sel`` [N, k] (ids into the published
    experts), their ``weights`` [N, k] (float32) and the published experts'
    ``load`` [E] -> [N, D]: the weighted sum over a token's picked experts
    that are ``held`` (ids, in the order of the parameters' leading axis),
    each ``(gate(x @ G) * (x @ U)) @ D`` of ``width``, plus ``shared(tokens)``
    where a model has a shared expert. Called inside ``module``'s compact
    ``__call__``: the three expert parameters and the counters are its."""
    n, d = tokens.shape
    k, e = sel.shape[1], load.shape[0]
    n_held = len(held)

    with jax.named_scope("dispatch"):
        # every assignment gets a row: sorted by held expert, the
        # assignments to absent experts last (group ``n_held``)
        local = np.full((e,), n_held, np.int32)
        local[list(held)] = np.arange(n_held)
        group = jnp.asarray(local)[sel.reshape(-1)]  # [N * k]
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        slot = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32)
        )  # assignment -> its row: order's inverse
        here = group[order] < n_held  # rows that exist
        rows = load[jnp.asarray(held)].astype(jnp.int32)  # a held expert
        xs = jnp.where(here[:, None], spread_rows(tokens, order, slot), 0)

    with jax.named_scope("experts"):
        w_gate = module.param("experts_gate", init, (n_held, d, width))
        w_up = module.param("experts_up", init, (n_held, d, width))
        w_down = module.param("experts_down", init, (n_held, width, d))
        gmm = lambda a, w: grouped_matmul(  # noqa: E731
            a, w, rows, interpret=interpret
        )
        ys = gmm(gate(gmm(xs, w_gate)) * gmm(xs, w_up), w_down)

    with jax.named_scope("combine"):
        mine = (group < n_held).reshape(n, k)
        # masked before the product: a row no expert wrote is unwritten
        parts = jnp.where(
            mine[..., None],
            collect_rows(ys, slot, order).reshape(n, k, d), 0,
        ).astype(jnp.float32)
        routed = jnp.sum(weights[..., None] * parts, 1).astype(dtype)

    also = None
    if shared is not None:
        with jax.named_scope("shared_expert"):
            also = shared(tokens)

    landed = jnp.sum(mine)
    for name, value in (
        ("rows_max", jnp.max(rows)), ("rows_mean", jnp.mean(rows)),
        ("landed", landed), ("dropped", landed - jnp.sum(rows)),
        ("active", jnp.sum(rows > 0)),
    ):
        module.sow(MOE_COUNTERS, name, value.astype(jnp.float32),
                   reduce_fn=lambda _, new: new, init_fn=lambda: None)
    return routed if also is None else routed + also


def sow_probe(module, **values):
    """A layer's ``moe_probe`` entries (kept only where a caller asks for
    the collection)."""
    for name, value in values.items():
        module.sow(MOE_PROBE, name, value,
                   reduce_fn=lambda _, new: new, init_fn=lambda: None)


def routing_counters(counters: dict) -> dict:
    """The ``moe_counters`` collection of one call, over its expert layers,
    as the scalars a step reports: rows per held expert (the fullest
    expert's, and the mean), assignments that landed here, held experts
    that got any (both summed over the layers), assignments dropped (0: the
    buffer covers the worst case)."""
    layers = [v["moe"] for _, v in sorted(counters.items()) if "moe" in v]
    pick = lambda name: jnp.stack([c[name] for c in layers])  # noqa: E731
    return {
        "expert_rows_max": jnp.max(pick("rows_max")),
        "expert_rows_mean": jnp.mean(pick("rows_mean")),
        "expert_load_max_over_mean": jnp.mean(
            pick("rows_max") / jnp.maximum(pick("rows_mean"), 1e-9)
        ),
        "assignments_landed": jnp.sum(pick("landed")),
        "experts_active": jnp.sum(pick("active")),
        "dropped_assignments": jnp.sum(pick("dropped")),
    }
