"""Pipeline parallelism: schedule-driven engine over the "pp" mesh axis.

Not present in the reference (`SURVEY.md` §2.2: TP/PP/SP absent) — a
TPU-native capability extension. Stages live on different devices along the
"pp" axis; activations hop stage→stage over ICI via ``ppermute``.

Two surfaces:

- :func:`pipeline_apply` — the forward-only GPipe apply (M microbatches
  fill the pipe: M + N - 1 ticks, bubble (N-1)/(M+N-1)). One `lax.scan`
  inside one `shard_map`; reverse-mode AD through the scan yields a GPipe
  backward automatically — but that AD saves every tick's residuals, so
  peak activation residency is O(M) microbatches.
- :class:`PipelineStep` — the schedule-driven train step. A static
  schedule table (:func:`build_schedule`: ``"gpipe"``, ``"1f1b"``, or
  ``"interleaved"`` with V virtual stages per rank) is executed as
  `lax.scan` over schedule ticks inside `shard_map`, with **explicit
  forward/backward tick kinds**: forward ticks run ``jax.vjp`` and park
  the pullback's residuals in a bounded circular buffer; backward ticks
  pop the slot and apply it. 1F1B drains each microbatch's backward as
  soon as it can, so the buffer needs only O(N) slots instead of GPipe's
  O(M) — that bound is static (``schedule.max_live_residuals``) and is
  what cuts peak activation residency.

Contract: every stage maps [mb, ...] -> [mb, ...] with the SAME shape
(transformer blocks). Embed/head layers stay OUTSIDE the pipe (replicated;
their grads are reduced over "pp" — only the first/last stage contributes
non-zeros). Stage params are a single stacked pytree with leading dim =
total layers, sharded P("pp") — the same stacked layout `nn.scan` models
use (`models/scan_utils.py`), so GPT-2/ViT/SwinIR scan checkpoints
partition into stages without a re-layout (interleaved schedules only
permute the leading axis).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.collectives import shard_map

SCHEDULES = ("gpipe", "1f1b", "interleaved")

# tick kinds in the schedule tables
_IDLE, _FWD, _BWD = 0, 1, 2


def stack_stage_params(params_list):
    """[tree_0, ..., tree_{n-1}] (same structure) -> stacked tree.

    One implementation with the scan-layout converters: this is
    ``models.scan_utils.stack_trees`` (the SwinIR layer-pair mapping
    layers on top of the same helper).
    """
    from ..models.scan_utils import stack_trees

    return stack_trees(params_list)


def unstack_stage_params(stacked):
    """Inverse of :func:`stack_stage_params` (leading-axis split)."""
    from ..models.scan_utils import unstack_tree

    # hoisted: one leaves() walk for the stage count, not one per index
    n = jax.tree.leaves(stacked)[0].shape[0]
    return unstack_tree(stacked, n)


def _batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)


# ---------------------------------------------------------------------------
# schedule tables
# ---------------------------------------------------------------------------


def _op_order(name: str, n: int, m: int, v: int):
    """Per-rank ordered op lists [(kind, micro, chunk), ...].

    The ORDER is what defines a schedule; tick times and buffer slots are
    derived by the simulator below, so every schedule shares one
    dependency-correct executor.
    """
    if name == "gpipe":
        one = [("F", mu, 0) for mu in range(m)] + [
            ("B", mu, 0) for mu in reversed(range(m))
        ]
        return [list(one) for _ in range(n)]
    if name == "1f1b":
        orders = []
        for r in range(n):
            w = min(n - 1 - r, m)  # warmup forwards before the first bwd
            seq = [("F", mu, 0) for mu in range(w)]
            for i in range(m - w):  # steady 1F1B: one fwd, one bwd
                seq.append(("F", w + i, 0))
                seq.append(("B", i, 0))
            for i in range(m - w, m):  # cooldown: drain remaining bwds
                seq.append(("B", i, 0))
            orders.append(seq)
        return orders
    # interleaved 1F1B (Megatron-style): v chunks per rank, microbatches
    # walked in groups of n so chunk c's fwd work interleaves with c+1's
    total = m * v

    def fwd_id(k):
        g = k % (n * v)
        return (k // (n * v)) * n + g % n, g // n

    def bwd_id(k):
        g = k % (n * v)
        return (k // (n * v)) * n + g % n, v - 1 - g // n

    orders = []
    for r in range(n):
        w = min((n - 1 - r) * 2 + (v - 1) * n, total)
        seq = [("F", *fwd_id(k)) for k in range(w)]
        nf, nb = w, 0
        while nf < total:
            seq.append(("F", *fwd_id(nf)))
            nf += 1
            seq.append(("B", *bwd_id(nb)))
            nb += 1
        while nb < total:
            seq.append(("B", *bwd_id(nb)))
            nb += 1
        orders.append(seq)
    return orders


def _simulate(orders, n: int, v: int):
    """Assign a tick to every op, respecting transfer latency (1 tick/hop).

    Each rank executes its op list in order, one op per tick, idling while
    a dependency is in flight. fwd(mu, s) needs fwd(mu, s-1) to have
    finished a tick earlier (one ppermute hop); bwd(mu, s) needs its own
    fwd's residuals (same rank, previous tick) and bwd(mu, s+1)'s grad
    (one hop).
    """
    S = n * v
    done: dict = {}
    ptr = [0] * n
    assigned = [[] for _ in range(n)]  # (tick, kind, micro, chunk)
    total_ops = sum(len(o) for o in orders)
    ndone, t = 0, 0
    while ndone < total_ops:
        if t > 4 * total_ops + 4 * S + 16:
            raise RuntimeError(
                f"schedule simulator wedged at tick {t} "
                f"({ndone}/{total_ops} ops) — op order has a cycle"
            )
        ready = []
        for r in range(n):
            if ptr[r] >= len(orders[r]):
                continue
            kind, mu, c = orders[r][ptr[r]]
            s = c * n + r
            if kind == "F":
                ok = s == 0 or done.get(("F", mu, s - 1), t) < t
            else:
                ok = done.get(("F", mu, s), t) < t and (
                    s == S - 1 or done.get(("B", mu, s + 1), t) < t
                )
            if ok:
                ready.append((r, kind, mu, c, s))
        for r, kind, mu, c, s in ready:
            done[(kind, mu, s)] = t
            assigned[r].append((t, kind, mu, c))
            ptr[r] += 1
            ndone += 1
        t += 1
    return assigned, done, t


def _alloc_slots(events):
    """Greedy interval slot allocation.

    ``events``: [(arrive_tick, consume_tick, key), ...]. A slot frees for
    re-use strictly AFTER its consume tick (a tick's receive phase runs
    before its compute phase, so same-tick reuse would clobber). Returns
    ({key: slot}, n_slots).
    """
    events = sorted(events)
    slot_of, free_at = {}, []  # free_at[slot] = consume tick
    for arrive, consume, key in events:
        slot = None
        for i, fa in enumerate(free_at):
            if fa < arrive:
                slot = i
                break
        if slot is None:
            slot = len(free_at)
            free_at.append(-1)
        free_at[slot] = consume
        slot_of[key] = slot
    return slot_of, len(free_at)


@dataclass(frozen=True)
class PipelineSchedule:
    """A static pipeline schedule: per-rank tick tables + buffer bounds.

    ``tables`` maps name -> np.int32 [n_stages, n_ticks]:

    - ``kind``: 0 idle / 1 fwd / 2 bwd
    - ``micro`` / ``chunk``: which microbatch / local virtual stage
    - ``res_slot``: residual-buffer slot the fwd writes and its bwd reads
    - ``in_slot``: fwd input slot (-1 = feed from the embed'd microbatch);
      for bwd ticks the grad slot (-1 never occurs; the LAST stage's slot
      holds the fwd output ``y`` and seeds through the head instead)
    - ``f_recv`` / ``b_recv``: slot an incoming ppermute value lands in
      this tick (-1 = channel carries nothing for this rank)
    - ``y_slot``: where a last-stage fwd parks its output for its own bwd
    - ``first`` / ``last``: this tick's op touches global stage 0 / S-1
    """

    name: str
    n_stages: int  # pp ranks
    n_micro: int
    v: int  # virtual stages (chunks) per rank
    n_ticks: int
    tables: dict = field(repr=False)
    segments: tuple  # ((start, end, fwd_active, bwd_active), ...)
    res_slots: int
    f_slots: int
    b_slots: int

    @property
    def total_stages(self) -> int:
        return self.n_stages * self.v

    @property
    def max_live_residuals(self) -> int:
        """Residual-buffer bound: O(N) for 1F1B, O(M) for GPipe."""
        return self.res_slots

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the rank×tick grid (fwd+bwd both counted)."""
        busy = 2 * self.n_micro * self.v * self.n_stages
        return 1.0 - busy / (self.n_stages * self.n_ticks)

    @property
    def expected_collective_permutes(self) -> int:
        """collective-permute instructions the compiled step must carry.

        The executor runs one `lax.scan` per segment (a maximal tick run
        with a constant set of active channels) and emits the fwd/bwd
        channel hop only in segments where the schedule actually moves
        data on it — so the instruction count discriminates schedules:
        GPipe's fwd and bwd phases are disjoint (2), 1F1B's steady state
        keeps both channels busy at once (4).
        """
        return sum(int(f) + int(b) for _, _, f, b in self.segments)

    def permute_pairs(self, direction: str) -> tuple:
        """Ring pairs for one channel: chains for v=1, full ring for v>1
        (chunk transitions wrap rank N-1 -> 0)."""
        n = self.n_stages
        if direction == "fwd":
            pairs = [(i, (i + 1) % n) for i in range(n if self.v > 1 else n - 1)]
        elif direction == "bwd":
            pairs = [((i + 1) % n, i) for i in range(n if self.v > 1 else n - 1)]
        else:
            raise ValueError(f"direction must be fwd|bwd, got {direction!r}")
        return tuple(pairs)


def build_schedule(
    name: str, n_stages: int, n_micro: int, v: int = 1
) -> PipelineSchedule:
    """Generate the static schedule table for a pipeline run.

    ``name``: "gpipe" | "1f1b" | "interleaved". ``n_stages`` is the pp
    axis size, ``n_micro`` the microbatch count per data shard, ``v`` the
    virtual stages per rank (interleaved only; gpipe/1f1b require v=1).
    """
    if name not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {name!r}")
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    if name == "interleaved":
        if v < 2:
            raise ValueError(
                "interleaved needs v >= 2 virtual stages per rank "
                f"(got v={v}); use '1f1b' for v=1"
            )
        if n_micro % n_stages:
            raise ValueError(
                f"interleaved requires n_micro ({n_micro}) divisible by "
                f"n_stages ({n_stages}) — pad the microbatch count"
            )
    elif v != 1:
        raise ValueError(f"schedule {name!r} supports v=1 only, got v={v}")

    n, m, S = n_stages, n_micro, n_stages * v
    orders = _op_order(name, n, m, v)
    assigned, done, T = _simulate(orders, n, v)

    # -- slot allocation ----------------------------------------------------
    res_events = [[] for _ in range(n)]  # residuals: fwd tick -> bwd tick
    f_events = [[] for _ in range(n)]  # fwd activations in flight
    b_events = [[] for _ in range(n)]  # grads in flight + last-stage y
    for mu in range(m):
        for s in range(S):
            r = s % n
            tf, tb = done[("F", mu, s)], done[("B", mu, s)]
            res_events[r].append((tf, tb, ("R", mu, s)))
            if s > 0:  # activation hop (s-1) -> s arrives one tick later
                f_events[r].append((done[("F", mu, s - 1)] + 1, tf, ("A", mu, s)))
            if s == S - 1:  # y parked locally at the fwd tick
                b_events[r].append((tf, tb, ("Y", mu, s)))
            else:  # grad hop (s+1) -> s
                b_events[r].append((done[("B", mu, s + 1)] + 1, tb, ("G", mu, s)))

    res_slot_of, f_slot_of, b_slot_of = {}, {}, {}
    n_res = n_f = n_b = 1
    for r in range(n):
        so, k = _alloc_slots(res_events[r])
        res_slot_of.update(so)
        n_res = max(n_res, k)
        so, k = _alloc_slots(f_events[r])
        f_slot_of.update(so)
        n_f = max(n_f, k)
        so, k = _alloc_slots(b_events[r])
        b_slot_of.update(so)
        n_b = max(n_b, k)

    # -- tables -------------------------------------------------------------
    tbl = {
        k: np.full((n, T), -1 if k.endswith(("slot", "recv")) else 0, np.int32)
        for k in (
            "kind", "micro", "chunk", "res_slot", "in_slot",
            "f_recv", "b_recv", "y_slot", "first", "last",
        )
    }
    for r in range(n):
        for t, kind, mu, c in assigned[r]:
            s = c * n + r
            tbl["kind"][r, t] = _FWD if kind == "F" else _BWD
            tbl["micro"][r, t] = mu
            tbl["chunk"][r, t] = c
            tbl["res_slot"][r, t] = res_slot_of[("R", mu, s)]
            tbl["first"][r, t] = int(s == 0)
            tbl["last"][r, t] = int(s == S - 1)
            if kind == "F":
                tbl["in_slot"][r, t] = (
                    -1 if s == 0 else f_slot_of[("A", mu, s)]
                )
                if s == S - 1:
                    tbl["y_slot"][r, t] = b_slot_of[("Y", mu, s)]
            else:
                tbl["in_slot"][r, t] = (
                    b_slot_of[("Y", mu, s)]
                    if s == S - 1
                    else b_slot_of[("G", mu, s)]
                )
    for (_, mu, s), slot in f_slot_of.items():
        tbl["f_recv"][s % n, done[("F", mu, s - 1)] + 1] = slot
    for (kind, mu, s), slot in b_slot_of.items():
        if kind == "G":
            tbl["b_recv"][s % n, done[("B", mu, s + 1)] + 1] = slot

    # -- segments: maximal tick runs with a constant active-channel set ----
    f_act = (tbl["f_recv"] >= 0).any(axis=0)
    b_act = (tbl["b_recv"] >= 0).any(axis=0)
    segments, start = [], 0
    for t in range(1, T + 1):
        if t == T or (f_act[t], b_act[t]) != (f_act[start], b_act[start]):
            segments.append((start, t, bool(f_act[start]), bool(b_act[start])))
            start = t
    return PipelineSchedule(
        name=name, n_stages=n, n_micro=m, v=v, n_ticks=T, tables=tbl,
        segments=tuple(segments), res_slots=n_res, f_slots=n_f, b_slots=n_b,
    )


# ---------------------------------------------------------------------------
# schedule executor (runs inside shard_map)
# ---------------------------------------------------------------------------


def _read(buf, slot):
    return jax.lax.dynamic_index_in_dim(
        buf, jnp.clip(slot, 0, buf.shape[0] - 1), 0, keepdims=False
    )


def _write(buf, slot, val):
    """Write ``val`` at ``slot`` when slot >= 0, else leave ``buf``."""
    upd = jax.lax.dynamic_update_index_in_dim(
        buf, val, jnp.clip(slot, 0, buf.shape[0] - 1), 0
    )
    return jnp.where(slot >= 0, upd, buf)


def _pipeline_vag_local(
    stages_rm,
    other,
    batch,
    rng,
    *,
    sched: PipelineSchedule,
    chunk_fn,
    embed_fn,
    head_fn,
    lpv: int,
    data_axes: tuple,
    axis_name: str,
):
    """Value-and-grad of the pipelined loss on ONE pp rank.

    ``stages_rm``: this rank's chunk params, [v*lpv, ...] leaves in
    rank-major order. Returns (loss, stage grads [v*lpv,...], other-param
    grads) — loss/other reduced over pp+data axes, stage grads pp-local.
    """
    r = jax.lax.axis_index(axis_name)
    m = sched.n_micro
    micro_batch = jax.tree.map(
        lambda a: a.reshape(m, a.shape[0] // m, *a.shape[1:]), batch
    )
    tb = {k: jnp.asarray(a) for k, a in sched.tables.items()}

    def rng_mu(mu):
        return jax.random.fold_in(rng, mu)

    def take_micro(mu):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, mu, 0, keepdims=False),
            micro_batch,
        )

    def chunk_params_at(c):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, c * lpv, lpv, 0),
            stages_rm,
        )

    # templates (shapes only — XLA dead-code-eliminates the values): the
    # pipe I/O template from the first microbatch through embed, the
    # residual pytree structure from one chunk vjp
    mb0 = jax.tree.map(lambda a: a[0], micro_batch)
    x_t = embed_fn(other, mb0, rng_mu(jnp.int32(0)))
    _, pb_t = jax.vjp(chunk_fn, chunk_params_at(jnp.int32(0)), x_t)
    res_leaves_t, res_treedef = jax.tree_util.tree_flatten(pb_t)

    zeros_x = jnp.zeros(x_t.shape, x_t.dtype)
    carry0 = (
        zeros_x,  # fwd channel (this rank's last sent activation)
        zeros_x,  # bwd channel (last sent grad)
        jnp.zeros((sched.f_slots,) + x_t.shape, x_t.dtype),
        jnp.zeros((sched.b_slots,) + x_t.shape, x_t.dtype),
        [
            jnp.zeros((sched.res_slots,) + l.shape, l.dtype)
            for l in res_leaves_t
        ],
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), stages_rm),
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), other),
        jnp.zeros((), jnp.float32),  # summed per-micro loss
    )
    inv_m = jnp.float32(1.0 / m)

    def fwd_branch(op):
        (fwd_send, bwd_send, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss), (
            mu, c, rs, ins, ys, _fr, _la,
        ) = op
        mb = take_micro(mu)
        x_in = jax.lax.cond(
            ins < 0,
            lambda _: embed_fn(other, mb, rng_mu(mu)),
            lambda _: _read(fwd_buf, ins),
            None,
        )
        y, pb = jax.vjp(chunk_fn, chunk_params_at(c), x_in)
        leaves = jax.tree_util.tree_flatten(pb)[0]
        res_buf = [_write(b, rs, l) for b, l in zip(res_buf, leaves)]
        bwd_buf = _write(bwd_buf, ys, y)  # last stage parks y for its bwd
        return (y, bwd_send, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss)

    def bwd_branch(op):
        (fwd_send, bwd_send, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss), (
            mu, c, rs, ins, _ys, first, last,
        ) = op
        mb = take_micro(mu)
        rk = rng_mu(mu)
        g_in = _read(bwd_buf, ins)  # grad — or y at the last stage

        def head_seed(args):
            o, y = args
            lm, hpb = jax.vjp(lambda oo, yy: head_fn(oo, yy, mb, rk), o, y)
            d_o, d_y = hpb(jnp.asarray(inv_m, lm.dtype))
            return lm.astype(jnp.float32), d_o, d_y

        def pass_grad(args):
            o, g = args
            return (
                jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, o),
                g,
            )

        lm, d_o_head, g = jax.lax.cond(
            last == 1, head_seed, pass_grad, (other, g_in)
        )
        pb = jax.tree_util.tree_unflatten(
            res_treedef, [_read(b, rs) for b in res_buf]
        )
        d_chunk, d_x = pb(g)
        g_st = jax.tree.map(
            lambda acc, d: jax.lax.dynamic_update_slice_in_dim(
                acc,
                jax.lax.dynamic_slice_in_dim(acc, c * lpv, lpv, 0)
                + d.astype(acc.dtype),
                c * lpv,
                0,
            ),
            g_st,
            d_chunk,
        )

        def embed_grads(args):
            o, dx = args
            _, epb = jax.vjp(lambda oo: embed_fn(oo, mb, rk), o)
            return epb(dx)[0]

        d_o_embed = jax.lax.cond(
            first == 1,
            embed_grads,
            lambda args: jax.tree.map(jnp.zeros_like, args[0]),
            (other, d_x),
        )
        g_ot = jax.tree.map(
            lambda a, h, e: a + h.astype(a.dtype) + e.astype(a.dtype),
            g_ot, d_o_head, d_o_embed,
        )
        return (fwd_send, d_x, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss + lm)

    def idle_branch(op):
        return op[0]

    def make_tick(t0: int, f_active: bool, b_active: bool):
        def tick(carry, t_rel):
            t = t_rel + t0
            fwd_send, bwd_send, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss = carry
            if f_active:  # receive phase: permute the PREVIOUS tick's sends
                fr = jax.lax.ppermute(
                    fwd_send, axis_name, sched.permute_pairs("fwd")
                )
                fwd_buf = _write(fwd_buf, tb["f_recv"][r, t], fr)
            if b_active:
                br = jax.lax.ppermute(
                    bwd_send, axis_name, sched.permute_pairs("bwd")
                )
                bwd_buf = _write(bwd_buf, tb["b_recv"][r, t], br)
            lookups = tuple(
                tb[k][r, t]
                for k in (
                    "micro", "chunk", "res_slot", "in_slot",
                    "y_slot", "first", "last",
                )
            )
            carry = (
                fwd_send, bwd_send, fwd_buf, bwd_buf, res_buf, g_st, g_ot, loss,
            )
            carry = jax.lax.switch(
                tb["kind"][r, t],
                (idle_branch, fwd_branch, bwd_branch),
                (carry, lookups),
            )
            return carry, None

        return tick

    carry = carry0
    for s0, s1, fa, ba in sched.segments:
        # t0 baked in as a constant so same-signature segments compile to
        # distinct scan bodies (no XLA dedup of the audited ppermutes)
        carry, _ = jax.lax.scan(
            make_tick(s0, fa, ba), carry, jnp.arange(s1 - s0)
        )
    *_, g_st, g_ot, loss = carry

    loss = loss * inv_m
    if data_axes:  # global batch = mean over data shards
        loss = jax.lax.pmean(loss, data_axes)
        g_st = jax.tree.map(lambda g: jax.lax.pmean(g, data_axes), g_st)
        g_ot = jax.tree.map(lambda g: jax.lax.pmean(g, data_axes), g_ot)
    # embed/head grads + loss live on the first/last rank only; stage
    # grads stay on the owning pp shard (no cross-stage reduction)
    loss = jax.lax.psum(loss, axis_name)
    g_ot = jax.tree.map(lambda g: jax.lax.psum(g, axis_name), g_ot)
    return loss, g_st, g_ot


def _rank_major_perm(n_layers: int, n: int, v: int, lpv: int) -> np.ndarray:
    """perm[p] = original layer index at rank-major position p.

    Rank-major: rank r holds positions [r*v*lpv, (r+1)*v*lpv) — its v
    chunks contiguous — while chunk c's global stage is c*n + r. Identity
    for v == 1.
    """
    p = np.arange(n_layers)
    r, rem = p // (v * lpv), p % (v * lpv)
    c, j = rem // lpv, rem % lpv
    return (c * n + r) * lpv + j


def pipeline_value_and_grad(
    params,
    batch,
    rng,
    *,
    mesh: Mesh,
    schedule: PipelineSchedule,
    block_fn: Callable,
    stages_key: str,
    embed_fn: Callable,
    head_fn: Callable,
    remat: bool | str = False,
    axis_name: str = "pp",
):
    """(loss, grads) of a pipelined model under a schedule table.

    ``params[stages_key]`` is the stacked per-layer tree ([L, ...] leaves,
    L divisible by n_stages*v); the rest of ``params`` is replicated and
    visible to ``embed_fn(other, micro_batch, rng) -> x`` and
    ``head_fn(other, y, micro_batch, rng) -> loss``.
    ``block_fn(one_layer_params, x) -> x`` applies ONE stacked layer.
    """
    if stages_key not in params:
        raise ValueError(
            f"params has no {stages_key!r} subtree — pipeline stages must "
            f"be a stacked tree under that key (have {sorted(params)})"
        )
    other = dict(params)
    stages = other.pop(stages_key)
    L = jax.tree.leaves(stages)[0].shape[0]
    n, v = schedule.n_stages, schedule.v
    if L % (n * v):
        raise ValueError(
            f"{L} stacked layers do not divide into {n} stages x {v} "
            f"virtual chunks — adjust pp/v or the layer count"
        )
    lpv = L // (n * v)
    m = schedule.n_micro
    dshards = 1
    for a in _batch_axes(mesh):
        dshards *= mesh.shape[a]
    b = jax.tree.leaves(batch)[0].shape[0]
    local_b, remainder = divmod(b, dshards)
    if remainder or local_b % m:
        raise ValueError(
            f"per-shard batch {b}/{dshards} not divisible by n_micro {m} "
            f"(microbatching is per data-parallel shard)"
        )

    from .remat import checkpoint_policy, note_remat, resolve_remat

    def chunk_fn(chunk_params, x):
        def body(h, p_layer):
            return block_fn(p_layer, h), None

        return jax.lax.scan(body, x, chunk_params)[0]

    rname = resolve_remat(remat)
    if rname != "none":
        # the chunk scans its layers: what it keeps is stacked over them
        note_remat(rname, "pipeline chunk", stacked=True)
        chunk_fn = jax.checkpoint(
            chunk_fn, prevent_cse=False,
            policy=checkpoint_policy(rname, stacked=True),
        )

    perm = _rank_major_perm(L, n, v, lpv)
    stages_rm = (
        stages if v == 1
        else jax.tree.map(lambda a: jnp.take(a, perm, axis=0), stages)
    )
    batch_ax = _batch_axes(mesh)
    stage_spec = jax.tree.map(lambda _: P(axis_name), stages_rm)
    other_spec = jax.tree.map(lambda _: P(), other)
    bspec = jax.tree.map(
        lambda a: P(batch_ax or None, *([None] * (a.ndim - 1))), batch
    )
    loss, g_st_rm, g_ot = shard_map(
        partial(
            _pipeline_vag_local,
            sched=schedule,
            chunk_fn=chunk_fn,
            embed_fn=embed_fn,
            head_fn=head_fn,
            lpv=lpv,
            data_axes=batch_ax,
            axis_name=axis_name,
        ),
        mesh=mesh,
        in_specs=(stage_spec, other_spec, bspec, P()),
        out_specs=(P(), stage_spec, other_spec),
        check_vma=False,
    )(stages_rm, other, batch, rng)
    g_st = (
        g_st_rm if v == 1
        else jax.tree.map(
            lambda a: jnp.take(a, np.argsort(perm), axis=0), g_st_rm
        )
    )
    grads = dict(g_ot)
    grads[stages_key] = g_st
    return loss, grads


# ---------------------------------------------------------------------------
# forward-only GPipe apply (AD through the scan = backward)
# ---------------------------------------------------------------------------


def _gpipe_local(stage_params, x, *, stage_fn, n_micro, axis_name):
    """Runs inside shard_map: one pp rank, local stage params [1, ...]."""
    sparams = jax.tree.map(lambda a: a[0], stage_params)
    n = jax.lax.axis_size(axis_name)
    r = jax.lax.axis_index(axis_name)

    b = x.shape[0]
    micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    # promote to pp-varying so scan carries have a uniform vma type
    micro = jax.lax.pcast(micro, (axis_name,), to="varying")

    state0 = micro[0] * 0
    outs0 = micro * 0
    send = [(i, i + 1) for i in range(n - 1)]  # stage r -> r+1

    def tick(carry, t):
        state, outs = carry
        mt = jax.lax.dynamic_index_in_dim(
            micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        inp = jnp.where(r == 0, mt, state)
        y = stage_fn(sparams, inp)
        # last stage banks microbatch t-(n-1) once it emerges from the pipe
        oi = t - (n - 1)
        valid = jnp.logical_and(r == n - 1, oi >= 0)
        banked = jax.lax.dynamic_update_index_in_dim(
            outs, y, jnp.clip(oi, 0, n_micro - 1), 0
        )
        outs = jnp.where(valid, banked, outs)
        state = jax.lax.ppermute(y, axis_name, send)
        return (state, outs), None

    (_, outs), _ = jax.lax.scan(
        tick, (state0, outs0), jnp.arange(n_micro + n - 1)
    )
    # replicate the last stage's outputs across the pp axis
    outs = jax.lax.psum(
        jnp.where(r == n - 1, outs, outs * 0), axis_name
    )
    return outs.reshape(b, *x.shape[1:])


def pipeline_apply(
    stage_params,
    x,
    *,
    stage_fn: Callable,
    mesh: Mesh,
    n_micro: int,
    axis_name: str = "pp",
):
    """Apply n_stages pipelined stages to x [B, ...] -> [B, ...].

    ``stage_params``: stacked tree, leading dim n_stages (= pp axis size).
    ``stage_fn(params_one_stage, x_micro) -> y_micro``, shape-preserving.

    Forward-only GPipe: differentiating through it replays the schedule in
    reverse but keeps every microbatch's residuals live (O(M) activation
    memory). Training loops should use :class:`PipelineStep`, whose
    explicit-backward schedules bound residency at O(N).
    """
    n_stages = mesh.shape.get(axis_name, 1)
    if n_stages <= 1:
        # degenerate pipe: run stages sequentially on one device
        out = x
        for p in unstack_stage_params(stage_params):
            out = stage_fn(p, out)
        return out
    batch = _batch_axes(mesh)
    dp_total = 1
    for a in batch:
        dp_total *= mesh.shape[a]
    local_b, rem = divmod(x.shape[0], dp_total)
    if rem or local_b % n_micro:
        raise ValueError(
            f"per-shard batch {x.shape[0]}/{dp_total} not divisible by "
            f"n_micro {n_micro} (microbatching is per data-parallel shard)"
        )
    pspec = jax.tree.map(lambda _: P(axis_name), stage_params)
    xspec = P(batch or None, *([None] * (x.ndim - 1)))
    return shard_map(
        partial(
            _gpipe_local, stage_fn=stage_fn, n_micro=n_micro,
            axis_name=axis_name,
        ),
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=xspec,
        check_vma=False,
    )(stage_params, x)


# ---------------------------------------------------------------------------
# PipelineStep: the pipelined TrainStep sibling
# ---------------------------------------------------------------------------


def pipeline_state_shardings(shardings, state, mesh: Mesh, stages_key: str):
    """Re-home the stacked stage leaves of a TrainState sharding tree onto
    the "pp" axis.

    ``create_train_state`` lays state out by the ZeRO policy, which knows
    nothing about the pipe; this rewrites every params/opt_state leaf
    under ``stages_key`` whose leading dim is the stacked layer axis to
    ``P("pp")`` (stage grads and the optimizer update then stay on the
    owning pp shard). Other leaves keep the policy's layout. Pass the
    matching ``state`` so leaf shapes are known; returns a new sharding
    tree — re-place the state with ``jax.device_put(state, new)``.
    """
    L = jax.tree.leaves(
        state.params[stages_key] if stages_key in state.params else {}
    )
    if not L:
        raise ValueError(
            f"state.params has no {stages_key!r} stacked subtree"
        )
    n_layers = L[0].shape[0]
    marker = f"'{stages_key}'"
    pp = NamedSharding(mesh, P("pp"))

    def rewrite(path, sh, leaf):
        if (
            marker in jax.tree_util.keystr(path)
            and hasattr(leaf, "ndim")
            and leaf.ndim >= 1
            and leaf.shape[0] == n_layers
        ):
            return pp
        return sh

    return shardings.replace(
        params=jax.tree_util.tree_map_with_path(
            rewrite, shardings.params, state.params
        ),
        opt_state=jax.tree_util.tree_map_with_path(
            rewrite, shardings.opt_state, state.opt_state
        ),
    )


class PipelineStep:
    """Schedule-driven pipelined train step — a `TrainStep` sibling.

    Same optimizer/donation/metrics contract as :class:`~.step.TrainStep`
    (``tx``/``mesh``/``policy``/``state_shardings``/``donate``,
    ``lr_factor`` argument, ``metrics["loss"]``/``["grad_norm"]``,
    ``compiled_text``/``memory_analysis``/``precompile``), but the loss is
    given DECOMPOSED so the engine can place it around the pipe::

        embed_fn(other_params, micro_batch, rng) -> x      # pre-pipe
        block_fn(one_layer_params, x) -> x                 # pipelined body
        head_fn(other_params, y, micro_batch, rng) -> loss # post-pipe

    ``other_params`` is the params tree **without** ``stages_key`` (the
    stacked [L, ...] layer tree that partitions into stages). ``n_micro``
    doubles as grad accumulation: the reported loss is the mean over
    microbatches, gradients match a single-device step on the full batch.

    Composes with DDP/ZeRO1/ZeRO2 over dp/fsdp: batch and loss reduce over
    the data axes, stage grads/updates stay on the owning pp shard, and
    the policy's grad constraint applies to the non-stage params.
    ZeRO3 (``shard_params``) does not compose — the pipe already shards
    the stage params over "pp".
    """

    def __init__(
        self,
        block_fn: Callable,
        tx,
        mesh: Mesh,
        policy=None,
        *,
        n_micro: int,
        schedule: str = "1f1b",
        v: int = 1,
        stages_key: str = "h",
        embed_fn: Callable | None = None,
        head_fn: Callable | None = None,
        state_shardings=None,
        extra_metrics: bool = True,
        donate: bool = True,
        numerics=None,
    ):
        from ..observe.numerics import NumericsProbe
        from ..runtime.mesh import batch_spec
        from .policy import Policy

        self.block_fn = block_fn
        self.tx = tx
        self.mesh = mesh
        self.policy = policy or Policy()
        if self.policy.shard_params:
            raise ValueError(
                "PipelineStep composes with DDP/ZeRO1/ZeRO2 only: ZeRO3 "
                "shards params over fsdp, but the pipe already owns the "
                "stage-param layout (P('pp') on the layer axis)"
            )
        n_stages = mesh.shape.get("pp", 1)
        self.schedule = build_schedule(schedule, max(n_stages, 1), n_micro, v)
        self.stages_key = stages_key
        self.embed_fn = embed_fn or (lambda other, mb, rng: mb[0])
        if head_fn is None:
            raise ValueError(
                "PipelineStep needs head_fn(other_params, y, micro_batch, "
                "rng) -> loss: the loss attaches behind the last stage"
            )
        self.head_fn = head_fn
        self.extra_metrics = extra_metrics
        self.donate = donate
        # numerics observability: TrainStep's fused-aux contract; the
        # scan-stacked stage axis is exactly the layer axis the probe's
        # blame vector resolves, so a NaN names its pipeline stage
        self.numerics = (
            NumericsProbe() if numerics is True else (numerics or None)
        )
        self._state_shardings = state_shardings
        data_sharding = NamedSharding(mesh, batch_spec(mesh))
        self._jitted = jax.jit(
            self._step,
            in_shardings=(state_shardings, data_sharding, None),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )

    @property
    def bubble_fraction(self) -> float:
        return self.schedule.bubble_fraction

    def comm_cost(self, params) -> dict:
        """`CostSurface` twin of ``TrainStep.comm_cost`` for the pipe.

        Stage grads never cross stages (pinned P("pp")), so each pp
        shard reduces only its 1/pp slice of the stage params over the
        data axis; non-stage (embed/head) params pay the full-size hop.
        Same convention otherwise: reduce-scatter n, all-reduce 2n,
        ``min_shard_size`` floors stay at the all-reduce rate.
        """
        from .spec import leaf_spec, shard_axis

        ax = shard_axis(self.mesh)
        size = int(self.mesh.shape.get(ax, 1)) if ax else 1
        pp = int(self.mesh.shape.get("pp", 1))
        if ax is None or size <= 1:
            return {
                "collective": None,
                "fp32_bytes": 0,
                "wire_bytes": 0,
                "wire_format": None,
                "axis": None,
                "axis_size": 1,
            }
        rs = bool(self.policy.shard_grads)
        total = 0
        for key, sub in params.items():
            per_stage = pp if (key == self.stages_key and pp > 1) else 1
            for p in jax.tree.leaves(sub):
                n = 1
                for s in p.shape:
                    n *= int(s)
                scattered = rs and leaf_spec(
                    p.shape, ax, size, self.policy.min_shard_size
                ) != P()
                hops = 1 if scattered else 2
                total += hops * (n // per_stage) * 4
        return {
            "collective": "reduce-scatter" if rs else "all-reduce",
            "fp32_bytes": int(total),
            "wire_bytes": int(total),
            "wire_format": None,
            "axis": ax,
            "axis_size": size,
        }

    def _step(self, state, batch, lr_factor):
        import optax

        from ..optim import refresh_params_ema
        from .spec import constrain

        rng = jax.random.fold_in(state.rng, state.step)
        loss, grads = pipeline_value_and_grad(
            state.params,
            batch,
            rng,
            mesh=self.mesh,
            schedule=self.schedule,
            block_fn=self.block_fn,
            stages_key=self.stages_key,
            embed_fn=self.embed_fn,
            head_fn=self.head_fn,
            remat=self.policy.remat,
        )
        # the policy's wire plan applies to the non-stage params; stage
        # grads are pinned to the owning pp shard (never cross-stage)
        gspecs = self.policy.grads_specs(state.params, self.mesh)
        if gspecs is None:
            gspecs = jax.tree.map(lambda _: P(), state.params)
        gspecs = dict(gspecs)
        gspecs[self.stages_key] = jax.tree.map(
            lambda _: P("pp"), state.params[self.stages_key]
        )
        grads = constrain(grads, gspecs, self.mesh)

        if self.numerics is not None:
            grads = self.numerics.inject(grads, state.step)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        updates = jax.tree.map(lambda u: u * lr_factor, updates)
        new_params = optax.apply_updates(state.params, updates)
        new_opt = refresh_params_ema(state.opt_state, new_opt, new_params)

        from ..optim import clip_stats

        recorded_clip = clip_stats(new_opt)
        metrics = {"loss": loss.astype(jnp.float32)}
        if self.extra_metrics:
            metrics["grad_norm"] = (
                recorded_clip.gnorm
                if recorded_clip is not None
                else optax.global_norm(grads)
            )
            metrics["bubble_fraction"] = jnp.float32(
                self.schedule.bubble_fraction
            )
        if self.numerics is not None:
            metrics["numerics"] = self.numerics.aux(
                grads,
                params=state.params,
                updates=updates,
                grad_norm=(
                    recorded_clip.gnorm
                    if recorded_clip is not None else None
                ),
            )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
        )
        return new_state, metrics

    def precompile(self, state, batch, lr_factor: float = 1.0):
        with self.mesh:
            self._jitted.lower(state, batch, jnp.float32(lr_factor)).compile()

    def compiled_text(self, state, batch, lr_factor: float = 1.0):
        """Compiled HLO, for `observe.hlo.pipeline_audit` (prove the wire
        plan matches the schedule table's hop count)."""
        with self.mesh:
            return (
                self._jitted.lower(state, batch, jnp.float32(lr_factor))
                .compile()
                .as_text()
            )

    def memory_analysis(self, state, batch, lr_factor: float = 1.0):
        """Compiler memory accounting (`observe.memory`): the source of
        ``pp_peak_residency_bytes`` in the bench record."""
        from ..observe.memory import compiled_memory_stats

        with self.mesh:
            compiled = self._jitted.lower(
                state, batch, jnp.float32(lr_factor)
            ).compile()
        return compiled_memory_stats(compiled)

    def __call__(self, state, batch, lr_factor: float = 1.0):
        from ..observe import trace as telemetry

        with telemetry.dispatch_span(self, "PipelineStep"):
            return self._jitted(state, batch, jnp.float32(lr_factor))
