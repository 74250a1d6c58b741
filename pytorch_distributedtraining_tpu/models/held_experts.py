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

**The buffer is sized for the worst case and worked by the rows that
land.** The sort is stable with the absent experts' group last, so rows
``[0, L)`` are exactly the landed assignments, ``L`` a device scalar
(``Landed.count``; an eighth of N x k where a chip holds 8 of 64 experts).
Every movement of ``[rows, D]`` data is a loop whose trip count is read from
the device, over tiles of ``ROW_TILE`` landed rows (:func:`spread` out of
the tokens) or of as many tokens that have any (:func:`gather_sum` back into
them); each is the other's transpose, so the four movements of a layer's
forward and backward are these two. The empty part of the buffer is neither
read nor written, and with every assignment landed the loops walk the whole
buffer: slower, never wrong.

**Who leaves which rows unwritten.** :func:`spread` writes rows ``[0, L)``
up to the end of L's tile, and the buffer it writes into is an allocation
(``jax.lax.empty``: whatever the memory held where lowered for a TPU);
``grouped_matmul`` leaves the rows past its groups unwritten in turn,
forward and backward. So from dispatch to combine, in both directions, rows
``>= L`` hold anything, NaN included, and the rule for every consumer is
that none of them meets a product or a sum: :func:`gather_sum` reads by the
landed assignments' own rows, under a mask where a token has fewer than its
tile fetches, the dots for the weights' gradient are un-permuted under
``rank >= 0``, and what runs between the grouped matmuls (the gate) is
row-wise.

Counters of the routing are sown into the ``moe_counters`` collection as
device scalars; ``routing_counters`` reduces them over a model's expert
layers into the scalars a step reports. Each traced layer emits the instant
``routing.path`` into the telemetry ring (how the rows are moved, and the
``n``, ``k``, ``d``, ``tile`` it adapted on); how much of the buffer was
worked is ``assignments_landed`` against layers x N x k.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul

MOE_COUNTERS = "moe_counters"  # what the routing did this call
MOE_PROBE = "moe_probe"  # what a layer was given and gave, for a reference


ROW_TILE = 512  # rows of the buffer (or tokens) one step of a loop moves


class Landed(NamedTuple):
    """Where the assignments that landed on a held expert are, twice over.

    Sorted by held expert (the buffer's order, ``M = N x k`` rows, the
    absent experts' assignments last): rows ``[0, count)`` are exactly the
    landed assignments. And by token, for the sums over a token's rows: the
    tokens sorted by how many of their assignments landed, most first, so
    that the tokens with more than ``r`` landed are the first so many, for
    every ``r``; the row of the ``r``-th landed assignment of the token in
    place ``h`` is ``pick_row[r, h]``.

    Permutations are applied and inverted by sorts that carry what is
    permuted (:func:`_permuted`): on a TPU a sort of 10^5 keys with what it
    carries takes 0.3 ms, a scatter of as many scalars 0.45, a gather 0.9
    (``benchmarks/glm4_kernels.py routing``; PERF.md, PR 33)."""

    count: jax.Array  # [] int32: assignments that landed, L
    order: jax.Array  # [M] row r holds assignment order[r]
    slot: jax.Array  # [M] assignment a sits in row slot[a]: order's inverse
    token: jax.Array  # [M] row r holds token token[r] (order // k)
    rank: jax.Array  # [N, k] its place among its token's landed, -1: not landed
    heads: jax.Array  # [] int32: tokens with any landed assignment
    landed_of: jax.Array  # [N] how many the token in place h has
    pick_row: jax.Array  # [k, N] the row of its r-th landed (0 where none)
    place: jax.Array  # [N] token n's place h


def _permuted(to, *values):
    """``out[to[i]] = value[i]`` for each value, ``to`` a permutation."""
    return jax.lax.sort((to, *values), num_keys=1)[1:]


def _nth(rank, values):
    """``values`` [N, k] -> [k, N]: each token's value at its ``r``-th
    landed assignment, 0 where it has fewer (at most one j has rank r)."""
    return [
        jnp.sum(jnp.where(rank == r, values, 0), 1)
        for r in range(rank.shape[1])
    ]


@partial(jax.jit, static_argnames=("n_held", "k"))
def find_landed(group, n_held: int, k: int) -> Landed:
    """``group`` [M]: each assignment's held expert, ``n_held`` for the
    absent ones (assignment order, k a token). Jitted, as :func:`spread`
    and :func:`gather_sum` are, so that a model's layers, the forward, its
    rematerialisation and the backward rules share one trace of each a
    shape: traced afresh at every call they cost a run's set-up seconds."""
    m = group.shape[0]
    n = m // k
    ids, tokens = (jnp.arange(size, dtype=jnp.int32) for size in (m, n))
    _, order = jax.lax.sort((group, ids), num_keys=1, is_stable=True)
    (slot,) = _permuted(order, ids)
    by_token = (group < n_held).reshape(n, k)
    among = jnp.cumsum(by_token, 1, dtype=jnp.int32)
    rank = jnp.where(by_token, among - 1, -1)
    per_token = among[:, -1]
    fewest_last, by_place, *pick_row = jax.lax.sort(
        (-per_token, tokens, *_nth(rank, slot.reshape(n, k))), num_keys=1,
        is_stable=True,
    )
    return Landed(
        count=jnp.sum(per_token), order=order, slot=slot, token=order // k,
        rank=rank, heads=jnp.sum(per_token > 0, dtype=jnp.int32),
        landed_of=-fewest_last, pick_row=jnp.stack(pick_row),
        place=_permuted(by_place, tokens)[0],
    )


def _unwritten(shape, dtype):
    """A buffer nobody has written: an allocation where the program is
    lowered for a TPU (whatever the memory held), zeros elsewhere."""
    return jax.lax.empty(shape, dtype)


def _row_tiles(count, rows: int, tile: int, body, init):
    """``body(start, carry)`` for each tile ``[start, start + tile)`` of a
    buffer of ``rows`` that holds any of its first ``count`` rows: a trip
    count read from the device, so the empty part of the buffer costs
    nothing. The last start is held inside the buffer (tiles then overlap,
    and what a body writes to a row depends on the row alone)."""
    return jax.lax.fori_loop(
        0, (count + tile - 1) // tile,
        lambda i, carry: body(jnp.minimum(i * tile, rows - tile), carry),
        init,
    )


def _rows(table, at):
    """``table[at]``, every index known to lie inside (no clamp, no fill)."""
    return table.at[at].get(mode="promise_in_bounds")


@partial(jax.jit, static_argnames=("tile",))
def spread(src, landed: Landed, *, scale=None, beside=None, tile=None):
    """``src`` [N, D] -> the buffer [M, D]: row ``r < count`` is
    ``src[token[r]]``. With ``scale`` [N, k] (float32, by assignment) the
    row is ``scale[order[r]] * src[token[r]]``, the product in float32; with
    ``beside`` [M, D] too, also the rows' dots ``<src[token[r]], beside[r]>``
    [M] in float32. Rows past ``count``'s tile are NOT WRITTEN (nor their
    dots), and of ``beside`` none past it is read."""
    m, d = landed.token.shape[0], src.shape[1]
    tile = min(tile or ROW_TILE, m)
    if scale is not None:  # by row
        (scale,) = _permuted(landed.slot, scale.reshape(-1))

    def body(start, carry):
        out, dots = carry
        got = _rows(src, jax.lax.dynamic_slice(landed.token, (start,), (tile,)))
        if scale is not None:
            wide = got.astype(jnp.float32)
            if beside is not None:
                other = jax.lax.dynamic_slice(beside, (start, 0), (tile, d))
                dots = jax.lax.dynamic_update_slice(
                    dots, jnp.sum(wide * other.astype(jnp.float32), -1),
                    (start,),
                )
            by = jax.lax.dynamic_slice(scale, (start,), (tile,))
            got = (by[:, None] * wide).astype(src.dtype)
        return jax.lax.dynamic_update_slice(out, got, (start, 0)), dots

    out, dots = _row_tiles(
        landed.count, m, tile, body,
        (_unwritten((m, d), src.dtype), _unwritten((m,), jnp.float32)),
    )
    return out if beside is None else (out, dots)


@partial(jax.jit, static_argnames=("dtype", "tile"))
def gather_sum(rows, landed: Landed, *, weight=None, dtype=None, tile=None):
    """The buffer ``rows`` [M, D] -> [N, D]: token ``n``'s sum over its
    landed assignments of ``weight * rows[the assignment's row]``
    (``weight`` [N, k] float32, or ones), products and sum in float32, then
    cast. Two passes, neither over the buffer. The tokens that have any, by
    tiles in the order of :class:`Landed` (most landed first): a tile
    fetches its tokens' first landed rows, then their second, as many times
    as its first token has, so about ``count`` rows are fetched in all and
    every fetch is a whole tile under a mask. Then every token fetches its
    sum from its place, unless nothing landed at all. No row at or past
    ``count`` meets a product."""
    d, n = rows.shape[1], landed.place.shape[0]
    dtype = dtype or rows.dtype
    tile = min(tile or ROW_TILE, n)
    if weight is not None:  # [k, N]: the r-th landed's, by place
        weight = jnp.stack(_permuted(landed.place, *_nth(landed.rank, weight)))

    def body(start, sums):
        has = jax.lax.dynamic_slice(landed.landed_of, (start,), (tile,))
        cut = lambda table, r: jax.lax.dynamic_slice(  # noqa: E731
            table, (r, start), (1, tile)
        )[0]

        def add(r, total):
            got = jnp.where(
                (r < has)[:, None], _rows(rows, cut(landed.pick_row, r)), 0
            ).astype(jnp.float32)
            if weight is not None:
                got = cut(weight, r)[:, None] * got
            return total + got

        total = jax.lax.fori_loop(
            0, has[0], add, jnp.zeros((tile, d), jnp.float32)
        )
        return jax.lax.dynamic_update_slice(
            sums, total.astype(dtype), (start, 0)
        )

    sums = _row_tiles(landed.heads, n, tile, body, _unwritten((n, d), dtype))
    return jax.lax.cond(
        landed.count > 0,
        lambda: jnp.where(
            (landed.place < landed.heads)[:, None],
            _rows(sums, landed.place), 0,
        ),
        lambda: jnp.zeros((n, d), dtype),  # nothing landed: nothing fetched
    )


@jax.custom_vjp
def spread_rows(tokens, landed):
    """``tokens`` [N, D] -> one row a landed assignment, sorted by held
    expert (:func:`spread`). Every row is read by one assignment, so the
    gradient is no scatter-add: it is :func:`gather_sum` over a token's
    landed rows. Both are written by hand, so nothing differentiates
    through their loops."""
    return spread(tokens, landed)


def _spread_fwd(tokens, landed):
    return spread(tokens, landed), landed


def _spread_bwd(landed, g):
    return gather_sum(g, landed), None


spread_rows.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def for_two(rows, landed):
    """The buffer ``rows`` [M, D], once for each of two readers. Their
    cotangents are summed over the tiles that hold landed rows and no
    further (summed by autodiff they are one whole-buffer pass, which no
    loop downstream can take into itself)."""
    return rows, rows


def _for_two_bwd(landed, gs):
    first, second = gs
    m, d = first.shape
    tile = min(ROW_TILE, m)
    if m % tile:  # tiles would overlap, and this sum is made in place
        return first + second, None

    def body(start, total):
        cut = lambda a: jax.lax.dynamic_slice(a, (start, 0), (tile, d))  # noqa: E731
        return jax.lax.dynamic_update_slice(
            total, cut(total) + cut(second), (start, 0)
        )

    return _row_tiles(landed.count, m, tile, body, first), None


for_two.defvjp(lambda rows, landed: ((rows, rows), landed), _for_two_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def weighted_sum(rows, weights, landed, dtype):
    """The experts' results ``rows`` [M, D] in the buffer's order and the
    ``weights`` [N, k] -> [N, D] in ``dtype``: each token's weighted sum
    over its landed assignments (:func:`gather_sum`). The rows' gradient is
    :func:`spread` of the result's, scaled by each row's weight; the
    weights' gradient the dots that pass takes on its way."""
    return gather_sum(rows, landed, weight=weights, dtype=dtype)


def _weighted_fwd(rows, weights, landed, dtype):
    out = gather_sum(rows, landed, weight=weights, dtype=dtype)
    return out, (rows, weights, landed)


def _weighted_bwd(dtype, res, g):
    rows, weights, landed = res
    d_rows, dots = spread(
        g.astype(rows.dtype), landed, scale=weights, beside=rows
    )
    (by_assignment,) = _permuted(landed.order, dots)
    d_weights = jnp.where(
        landed.rank >= 0, by_assignment.reshape(weights.shape), 0
    )
    return d_rows, d_weights, None


weighted_sum.defvjp(_weighted_fwd, _weighted_bwd)


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
    from ..observe import trace

    n, d = tokens.shape
    k = sel.shape[1]
    n_held = len(held)
    trace.instant(
        "routing.path", path="jnp", n=n, k=k, d=d, tile=min(ROW_TILE, n * k),
        reason=(
            "row loops over the landed rows, one program for every "
            "platform: Mosaic takes no DMA of one row of a tiled [N, D] "
            "array, so there is no kernel to place by platform"
        ),
    )

    with jax.named_scope("dispatch"):
        # every assignment has a row: sorted by held expert, the
        # assignments to absent experts last (group ``n_held``); the rows
        # of those that landed come first and are the only ones moved
        at_held = sel.reshape(-1, 1) == jnp.asarray(held, sel.dtype)
        group = jnp.min(
            jnp.where(at_held, jnp.arange(n_held, dtype=jnp.int32), n_held), 1
        )
        landed = find_landed(group, n_held, k)
        rows = load[jnp.asarray(held)].astype(jnp.int32)  # a held expert
        xs_gate, xs_up = for_two(spread_rows(tokens, landed), landed)

    with jax.named_scope("experts"):
        w_gate = module.param("experts_gate", init, (n_held, d, width))
        w_up = module.param("experts_up", init, (n_held, d, width))
        w_down = module.param("experts_down", init, (n_held, width, d))
        gmm = lambda a, w: grouped_matmul(  # noqa: E731
            a, w, rows, interpret=interpret
        )
        ys = gmm(gate(gmm(xs_gate, w_gate)) * gmm(xs_up, w_up), w_down)

    with jax.named_scope("combine"):
        routed = weighted_sum(ys, weights, landed, dtype)

    also = None
    if shared is not None:
        with jax.named_scope("shared_expert"):
            also = shared(tokens)

    for name, value in (
        ("rows_max", jnp.max(rows)), ("rows_mean", jnp.mean(rows)),
        ("landed", landed.count),
        ("dropped", landed.count - jnp.sum(rows)),
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
