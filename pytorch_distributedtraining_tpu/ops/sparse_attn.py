"""Learned sparse attention: DeepSeek Sparse Attention's indexer in front of
a grouped-query attention core (DeepSeek-V3.2-Exp technical report; its
public inference code, ``inference/model.py``, class ``Indexer``).

For one sequence, query ``t`` and key ``s <= t``::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (j over HI heads)
    S_t     = the topk largest I[t, 0..t]  (all of them while t < topk;
              among equal scores the lower s first)
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, h // g] / sqrt(D)) v[s, h // g]
    p_t     = stop_gradient(mean_h softmax over S_t of head h)
    kl[t]   = KL(p_t || softmax(I[t, S_t]))

``w`` arrives with the indexer's scales folded in (``HI^-1/2 DI^-1/2``). No
``[T, T]`` array is made. Five Pallas kernels, on blocks of ``blk`` queries
and ``blk`` keys (``blk`` a multiple of 32), causal blocks only:

- ``sparse_select``: one program a query block scores its causal key blocks
  into a VMEM scratch of int32 keys whose order is the scores' (the float's
  bits, negatives' magnitude bits flipped, -0 folded onto +0), finds each
  row's ``topk``-th largest by 32 steps of bisection over the key bits,
  settles ties at that key by bisection over the column (only where a row
  has more than ``topk`` at or above it), and writes the selection as bits,
  ``[nq, nk, blk / 32, blk]`` int32 (bit ``r`` of word row ``g`` of tile
  ``(i, j)`` is query ``i blk + r blk / 32 + g`` and key ``j blk + c``), with
  ``lse_I``, the logsumexp of each row's selected scores. Exact: no
  approximate top-k, no sort.
- ``sparse_core_fwd``: flash attention over the tiles at or before the
  diagonal, every head of a query block in one program, the tile's mask
  unpacked from the selection once and shared by the heads.
- ``sparse_kl_fwd`` (``kl_divergence``): ``p`` from the core's scores and
  statistics, ``I`` again, and per row ``sum p log p`` and ``sum p I``, so
  that ``kl = those two + lse_I``: a value, under ``stop_gradient``.
- ``sparse_core_dq`` / ``sparse_core_dkv``: the core's backward (dQ over the
  key blocks of a query block; dK, dV over the query blocks of a key block),
  each with the indexer's part of the KL's gradient beside it, since both
  already make every head's ``P``: ``dI = g_kl (softmax_S(I) - p)`` on the
  selection, then ``dw``, ``dqI`` in the first and ``dkI`` in the second.

``sparse_core`` is the ``custom_vjp`` of the core: ``(o, lse, zero)`` of q,
k, v and the indexer's ``qI``, ``kI``, ``w``, given the selection. ``zero``
is nought whose cotangent is the KL's: ``sparse_attention`` adds it to
``kl_divergence``'s value, so the KL's gradient arrives in the core's
backward, which takes the KL's target as a constant: the KL reaches only
``qI``, ``kI`` and ``w``, and the output's gradient only q, k and v. The
selection and ``lse_I`` are no function to differentiate; the forward rule
names the core's ``o`` and ``lse`` and the caller names the selection and
``lse_I`` ``attn_kernel_residuals``, which every rematerialising policy of
``parallel/remat.py`` keeps: a checkpointed layer's backward reads them
instead of selecting again (a bf16 recomputation could pick otherwise).

Layouts: heads lead (``q [H, T, D]``, ``k``, ``v [KVH, T, D]``, ``qI [HI,
T, DI]``, ``kI [T, DI]``); the per-row numbers of every head sit side by
side, ``w [T, HI]``, ``lse`` and ``delta [T, H]`` (a ``[H, T, 8]`` layout
pads its 8 lanes to 128 in HBM: 268 MB a layer at the cell's shapes, where
``[T, H]`` is 8 MB), ``lse_I`` in ``[T, 8]`` with the value in every lane.
``sparse_attention_layer`` takes a model's ``[B, T, heads, dim]`` and maps
over the batch. The kernels compile for the TPU or raise; CPU tests ask for
``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attn import RESIDUALS_NAME

_BIG_NEG = -1e30
_INT_MIN = -(2**31)
_STAT = 8  # lanes of a per-row number
_LANES = 128
_VMEM_LIMIT = 100 * 2**20  # ops/pallas_attn.py's ceiling
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT
    )


def _key(x):
    """float32 -> int32 in the same order; -0 and +0 are one key."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    k = b ^ ((b >> 31) & 0x7FFFFFFF)
    return jnp.where(k == -1, 0, k)


def _unkey(k):
    return jax.lax.bitcast_convert_type(k ^ ((k >> 31) & 0x7FFFFFFF), jnp.float32)


def _pack(keep):
    """``keep [blk, blk]`` int32 0/1 -> ``[blk / 32, blk]`` words: row ``r g +
    i`` of the tile is bit ``r`` of word row ``i`` (``g = blk / 32``)."""
    g = keep.shape[0] // 32
    words = keep[:g]
    for r in range(1, 32):
        words = words | (keep[r * g:(r + 1) * g] << r)
    return words


def _unpack(words):
    """The tile's mask (bool ``[blk, blk]``) from its ``[blk / 32, blk]``
    words."""
    bits = [
        jax.lax.shift_right_logical(words, jnp.int32(r)) & 1 for r in range(32)
    ]
    return jnp.concatenate(bits, axis=0) != 0


def _column(x, h):
    """Column ``h`` of ``x [rows, n]`` as ``[rows, 1]``: a per-row number of
    head ``h`` from a block that holds every head's side by side."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == h, x, 0.0), axis=1, keepdims=True)


def _scores(qi_ref, ki, w):
    """``I`` of a tile: ``qi_ref [HI, bq, DI]``, ``ki [bk, DI]``, ``w [bq,
    HI]``; float32, the heads summed in order."""
    heads, bq = qi_ref.shape[0], qi_ref.shape[1]

    def head(h, acc):
        z = _dot(qi_ref[h], ki, _NT)
        return acc + jnp.maximum(z, 0.0) * _column(w, h)

    return jax.lax.fori_loop(
        0, heads, head, jnp.zeros((bq, ki.shape[0]), jnp.float32)
    )


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _lanes(x, n=_STAT):
    return jnp.broadcast_to(x, (x.shape[0], n))


# -- the selection -------------------------------------------------------------


def _select_kernel(qi_ref, w_ref, ki_ref, words_ref, lse_ref, keys_ref, *,
                   blk, topk):
    i = pl.program_id(0)
    t = ki_ref.shape[0]
    n = i + 1  # key blocks at or before the diagonal
    row = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)

    def fill(j, top):
        at = pl.ds(pl.multiple_of(j * blk, blk), blk)
        s = _scores(qi_ref, ki_ref[at, :], w_ref[...])
        seen = col + j * blk <= row
        keys_ref[j] = jnp.where(seen, _key(s), _INT_MIN)
        return jnp.maximum(
            top, jnp.max(jnp.where(seen, s, -jnp.inf), axis=1, keepdims=True)
        )

    top = jax.lax.fori_loop(0, n, fill, jnp.full((blk, 1), -jnp.inf))

    def count(pred):
        def body(j, c):
            return c + _rowsum(pred(keys_ref[j], col + j * blk).astype(jnp.int32))

        return jax.lax.fori_loop(0, n, body, jnp.zeros((blk, 1), jnp.int32))

    def bisect():
        # the largest key u (offset binary) with count(key >= u) >= topk
        def bit(b, u):
            cand = u | jnp.left_shift(jnp.int32(1), 31 - b)
            c = count(lambda k, _: k >= (cand ^ _INT_MIN))
            return jnp.where(c >= topk, cand, u)

        u = jax.lax.fori_loop(0, 32, bit, jnp.zeros((blk, 1), jnp.int32))
        return u ^ _INT_MIN

    # rows that see no more than topk keys take all of them
    thr = jax.lax.cond(
        n * blk > topk, bisect, lambda: jnp.full((blk, 1), _INT_MIN, jnp.int32)
    )
    over = (count(lambda k, _: k >= thr) > topk) & (thr != _INT_MIN)
    nbits = max(1, (t - 1).bit_length())

    def tie_cut():
        # the column of the need-th key equal to thr, lowest column first
        need = topk - count(lambda k, _: k > thr)

        def bit(b, c):
            cand = c | jnp.left_shift(jnp.int32(1), nbits - 1 - b)
            m = count(lambda k, cols: (k == thr) & (cols < cand))
            return jnp.where(m < need, cand, c)

        cut = jax.lax.fori_loop(0, nbits, bit, jnp.zeros((blk, 1), jnp.int32))
        return jnp.where(over, cut, t)

    cut = jax.lax.cond(
        jnp.max(over.astype(jnp.int32)) > 0, tie_cut,
        lambda: jnp.full((blk, 1), t, jnp.int32),
    )

    words_ref[...] = jnp.zeros(words_ref.shape, jnp.int32)

    def finish(j, l):
        k = keys_ref[j]
        cols = col + j * blk
        keep = (cols <= row) & ((k > thr) | ((k == thr) & (cols <= cut)))
        words_ref[0, j] = _pack(keep.astype(jnp.int32))
        return l + _rowsum(jnp.where(keep, jnp.exp(_unkey(k) - top), 0.0))

    l = jax.lax.fori_loop(0, n, finish, jnp.zeros((blk, 1), jnp.float32))
    lse_ref[...] = _lanes(top + jnp.log(l))


@functools.partial(jax.jit, static_argnames=("topk", "blk", "interpret"))
def sparse_select(qi, ki, w, *, topk, blk, interpret=False):
    """``qi [HI, T, DI]``, ``ki [T, DI]``, ``w [T, HI]`` (float32) ->
    ``(selection [T/blk, T/blk, blk/32, blk] int32, lse_I [T, 8] float32)``."""
    heads, t, di = qi.shape
    nq = t // blk
    if t % blk or blk % 32:
        raise ValueError(f"T={t} needs blocks of a multiple of 32 dividing it")
    return pl.pallas_call(
        functools.partial(_select_kernel, blk=blk, topk=topk),
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((heads, blk, di), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, heads), lambda i: (i, 0)),
            pl.BlockSpec((t, di), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nq, blk // 32, blk), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((blk, _STAT), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, nq, blk // 32, blk), jnp.int32),
            jax.ShapeDtypeStruct((t, _STAT), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nq, blk, blk), jnp.int32)],
        compiler_params=_params("arbitrary"),
        interpret=interpret,
        name="sparse_select",
    )(qi, w, ki)


def selected_keys(selection):
    """Keys selected over all queries of ``selection`` (a device scalar)."""
    return jnp.sum(jax.lax.population_count(selection))


def unpack_selection(selection):
    """The ``[T, T]`` bool mask of a selection: for tests at small T."""
    nq, nk, g, blk = selection.shape
    shifts = jnp.arange(32, dtype=jnp.int32)[:, None, None]
    bits = (selection[:, :, None] >> shifts) & 1  # [nq, nk, 32, g, blk]
    return bits.reshape(nq, nk, blk, blk).transpose(0, 2, 1, 3).reshape(
        nq * blk, nk * blk
    ) != 0


# -- the core and the KL ---------------------------------------------------------


def _tile(i, j):
    """Index maps of a (query block i, key block j) grid that fetch nothing
    new for the tiles past the diagonal, which do not run."""
    return jnp.minimum(j, i)


def _core_fwd_kernel(sel_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                     acc_ref, m_ref, l_ref, *, group, scale):
    i, j = pl.program_id(0), pl.program_id(1)
    heads, blk = q_ref.shape[0], q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, _BIG_NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        keep = _unpack(sel_ref[0, 0])

        def head(h, c):
            kv = h // group
            s = _dot(q_ref[h], k_ref[kv], _NT) * scale
            m_prev, l_prev = m_ref[h][:, :1], l_ref[h][:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(jnp.where(keep, s, _BIG_NEG), axis=1, keepdims=True)
            )
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            m_ref[h] = _lanes(m_new, _LANES)
            l_ref[h] = _lanes(l_prev * corr + _rowsum(p), _LANES)
            acc_ref[h] = acc_ref[h] * corr + _dot(
                p.astype(v_ref.dtype), v_ref[kv], _NN
            )
            return c

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(j == i)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape, 1)

        def head(h, lse):
            l = l_ref[h][:, :1]
            o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)
            return jnp.where(lane == h, m_ref[h][:, :1] + jnp.log(l), lse)

        lse_ref[...] = jax.lax.fori_loop(
            0, heads, head, jnp.zeros(lse_ref.shape, jnp.float32)
        )


def _head_probs(keep, q_ref, k_ref, lse_ref, group, scale, each=None):
    """``sum_h P_h`` of a tile (``P_h`` from the forward's ``lse``);
    ``each(h, kv, p_h)`` runs beside it for every head."""
    heads, blk = q_ref.shape[0], q_ref.shape[1]
    lse = lse_ref[...]

    def head(h, acc):
        kv = h // group
        s = _dot(q_ref[h], k_ref[kv], _NT) * scale
        p = jnp.where(keep, jnp.exp(s - _column(lse, h)), 0.0)
        if each is not None:
            each(h, kv, p)
        return acc + p

    return jax.lax.fori_loop(
        0, heads, head, jnp.zeros((blk, k_ref.shape[1]), jnp.float32)
    )


def _kl_fwd_kernel(sel_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref,
                   ent_ref, pi_ref, *, group, scale):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        ent_ref[...] = jnp.zeros(ent_ref.shape, jnp.float32)
        pi_ref[...] = jnp.zeros(pi_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        keep = _unpack(sel_ref[0, 0])
        p = _head_probs(keep, q_ref, k_ref, lse_ref, group, scale) * (
            1.0 / q_ref.shape[0]
        )
        score = _scores(qi_ref, ki_ref[...], w_ref[...])
        logp = jnp.log(jnp.where(p > 0, p, 1.0))
        ent_ref[...] += _lanes(_rowsum(p * logp))
        pi_ref[...] += _lanes(_rowsum(jnp.where(keep, p * score, 0.0)))


def _grad_of_scores(keep, p, qi_ref, ki, w, lse_i_ref, gkl_ref):
    """``dI`` of a tile: ``g_kl (softmax over S of I - p)`` on the selection."""
    score = _scores(qi_ref, ki, w)
    q_of_i = jnp.where(keep, jnp.exp(score - lse_i_ref[:, :1]), 0.0)
    return gkl_ref[:, :1] * (q_of_i - p)


def _core_dq_kernel(sel_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qi_ref, ki_ref, w_ref, lse_i_ref, gkl_ref,
                    dq_ref, dqi_ref, dw_ref, dq_acc, dqi_acc, *, group, scale):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        dqi_acc[...] = jnp.zeros(dqi_acc.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        keep = _unpack(sel_ref[0, 0])
        delta, w = delta_ref[...], w_ref[...]

        def each(h, kv, p):
            dp = _dot(do_ref[h], v_ref[kv], _NT)
            ds = p * (dp - _column(delta, h))
            dq_acc[h] += _dot(ds.astype(k_ref.dtype), k_ref[kv], _NN)

        p = _head_probs(keep, q_ref, k_ref, lse_ref, group, scale, each)
        ki = ki_ref[...]
        d_score = _grad_of_scores(
            keep, p * (1.0 / q_ref.shape[0]), qi_ref, ki, w, lse_i_ref,
            gkl_ref,
        )
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 1)

        def head(h, c):
            z = _dot(qi_ref[h], ki, _NT)
            dw_ref[...] += jnp.where(
                lane == h, _rowsum(d_score * jnp.maximum(z, 0.0)), 0.0
            )
            dz = jnp.where(z > 0, d_score * _column(w, h), 0.0)
            dqi_acc[h] += _dot(dz.astype(ki.dtype), ki, _NN)
            return c

        jax.lax.fori_loop(0, qi_ref.shape[0], head, 0)

    @pl.when(j == i)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)
        dqi_ref[...] = dqi_acc[...].astype(dqi_ref.dtype)


def _core_dkv_kernel(sel_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, qi_ref, ki_ref, w_ref, lse_i_ref, gkl_ref,
                     dk_ref, dv_ref, dki_ref, dk_acc, dv_acc, dki_acc, *,
                     group, scale):
    j, i = pl.program_id(0), pl.program_id(1)  # key block, query block

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        dki_acc[...] = jnp.zeros(dki_acc.shape, jnp.float32)

    @pl.when(i >= j)
    def _():
        keep = _unpack(sel_ref[0, 0])
        delta, w = delta_ref[...], w_ref[...]

        def each(h, kv, p):
            do = do_ref[h]
            dv_acc[kv] += _dot(p.astype(do.dtype), do, _TN)
            dp = _dot(do, v_ref[kv], _NT)
            ds = p * (dp - _column(delta, h))
            dk_acc[kv] += _dot(ds.astype(q_ref.dtype), q_ref[h], _TN)

        p = _head_probs(keep, q_ref, k_ref, lse_ref, group, scale, each)
        ki = ki_ref[...]
        d_score = _grad_of_scores(
            keep, p * (1.0 / q_ref.shape[0]), qi_ref, ki, w, lse_i_ref,
            gkl_ref,
        )

        def head(h, c):
            z = _dot(qi_ref[h], ki, _NT)
            dz = jnp.where(z > 0, d_score * _column(w, h), 0.0)
            dki_acc[...] += _dot(dz.astype(ki.dtype), qi_ref[h], _TN)
            return c

        jax.lax.fori_loop(0, qi_ref.shape[0], head, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dki_ref[...] = dki_acc[...].astype(dki_ref.dtype)


def _specs(blk, row, col):
    """BlockSpecs of a (row block, column block) grid: ``row(a, b)`` and
    ``col(a, b)`` give the query and the key block of grid step ``(a, b)``."""
    sel = pl.BlockSpec(
        (1, 1, blk // 32, blk), lambda a, b: (row(a, b), col(a, b), 0, 0)
    )

    def by_query(lead, lanes):
        # fetched once a row of tiles: one buffer is room enough
        return pl.BlockSpec(
            (lead, blk, lanes), lambda a, b: (0, row(a, b), 0),
            pipeline_mode=pl.Buffered(1),
        )

    def by_key(lead, lanes):
        if lead is None:
            return pl.BlockSpec((blk, lanes), lambda a, b: (col(a, b), 0))
        return pl.BlockSpec((lead, blk, lanes), lambda a, b: (0, col(a, b), 0))

    def rows(lanes=_STAT):
        return pl.BlockSpec((blk, lanes), lambda a, b: (row(a, b), 0))

    return sel, by_query, by_key, rows


def _statics(q, k, qi):
    heads, t, d = q.shape
    return dict(
        heads=heads, t=t, d=d, kvh=k.shape[0], group=heads // k.shape[0],
        hi=qi.shape[0], di=qi.shape[2], scale=d**-0.5,
    )


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _core_forward(q, k, v, selection, *, blk, interpret):
    """-> ``(o [H, T, D], lse [H, T, 8])``."""
    heads, t, d = q.shape
    kvh, n = k.shape[0], t // blk
    sel, by_query, by_key, rows = _specs(blk, lambda i, j: i, _tile)
    return pl.pallas_call(
        functools.partial(
            _core_fwd_kernel, group=heads // kvh, scale=d**-0.5
        ),
        grid=(n, n),
        in_specs=[sel, by_query(heads, d), by_key(kvh, d), by_key(kvh, d)],
        out_specs=[by_query(heads, d), rows(heads)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((t, heads), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, blk, d), jnp.float32),
            pltpu.VMEM((heads, blk, _LANES), jnp.float32),
            pltpu.VMEM((heads, blk, _LANES), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_core_fwd",
    )(selection, q, k, v)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def kl_divergence(q, k, lse, qi, ki, w, selection, lse_i, *, blk,
                  interpret=False):
    """``kl [T]``: per query ``KL(p || softmax(I over S))``, ``p`` the mean
    of the heads' attention over the selection (from the core's ``lse``).
    A value only: its gradient is ``sparse_core``'s."""
    c = _statics(q, k, qi)
    n = c["t"] // blk
    sel, by_query, by_key, rows = _specs(blk, lambda i, j: i, _tile)
    ent, p_i = pl.pallas_call(
        functools.partial(_kl_fwd_kernel, group=c["group"], scale=c["scale"]),
        grid=(n, n),
        in_specs=[
            sel, by_query(c["heads"], c["d"]), by_key(c["kvh"], c["d"]),
            rows(c["heads"]), by_query(c["hi"], c["di"]),
            by_key(None, c["di"]), rows(c["hi"]),
        ],
        out_specs=[rows(), rows()],
        out_shape=[jax.ShapeDtypeStruct((c["t"], _STAT), jnp.float32)] * 2,
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_kl_fwd",
    )(selection, q, k, lse, qi, ki, w)
    return ent[:, 0] - p_i[:, 0] + lse_i[:, 0]


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _backward(q, k, v, qi, ki, w, selection, lse_i, o, lse, do, gkl, *, blk,
              interpret):
    c = _statics(q, k, qi)
    n = c["t"] // blk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1).T
    gkl = jnp.broadcast_to(gkl.astype(jnp.float32)[:, None], (c["t"], _STAT))
    kernel = dict(group=c["group"], scale=c["scale"])
    args = (selection, q, k, v, do, lse, delta, qi, ki, w, lse_i, gkl)

    def inputs(sel, by_query, by_key, rows):
        return [
            sel, by_query(c["heads"], c["d"]), by_key(c["kvh"], c["d"]),
            by_key(c["kvh"], c["d"]), by_query(c["heads"], c["d"]),
            rows(c["heads"]), rows(c["heads"]),
            by_query(c["hi"], c["di"]), by_key(None, c["di"]),
            rows(c["hi"]), rows(), rows(),
        ]

    sel, by_query, by_key, rows = _specs(blk, lambda i, j: i, _tile)
    dq, dqi, dw = pl.pallas_call(
        functools.partial(_core_dq_kernel, **kernel),
        grid=(n, n),
        in_specs=inputs(sel, by_query, by_key, rows),
        out_specs=[by_query(c["heads"], c["d"]), by_query(c["hi"], c["di"]),
                   rows(c["hi"])],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(qi.shape, qi.dtype),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c["heads"], blk, c["d"]), jnp.float32),
            pltpu.VMEM((c["hi"], blk, c["di"]), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_core_dq",
    )(*args)
    # grid (key block j, query block i): the query blocks before the
    # diagonal do not run and fetch nothing new
    specs = _specs(blk, lambda j, i: jnp.maximum(i, j), lambda j, i: j)
    dk, dv, dki = pl.pallas_call(
        functools.partial(_core_dkv_kernel, **kernel),
        grid=(n, n),
        in_specs=inputs(*specs),
        out_specs=[specs[2](c["kvh"], c["d"]), specs[2](c["kvh"], c["d"]),
                   specs[2](None, c["di"])],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(ki.shape, ki.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((c["kvh"], blk, c["d"]), jnp.float32),
            pltpu.VMEM((c["kvh"], blk, c["d"]), jnp.float32),
            pltpu.VMEM((blk, c["di"]), jnp.float32),
        ],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_core_dkv",
    )(*args)
    return dq, dk, dv, dqi, dki, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def sparse_core(q, k, v, qi, ki, w, selection, lse_i, blk, interpret=False):
    """``(o [H, T, D], lse [H, T, 8], zero [T])`` of one sequence over its
    ``selection`` (``sparse_select``'s, with its ``lse_i``). ``zero`` is
    zeros whose cotangent is the KL's: added to ``kl_divergence``'s value
    it carries the KL's gradient into ``qI``, ``kI`` and ``w`` through this
    function's backward, which makes every head's ``P`` anyway."""
    o, lse = _core_forward(q, k, v, selection, blk=blk, interpret=interpret)
    return o, lse, _zero(lse_i)


def _zero(lse_i):
    # made of an input, so that under ``vmap`` it is batched like the KL
    # it is added to and its cotangent stays each sequence's own
    return lse_i[:, 0] * 0.0


def _fwd(q, k, v, qi, ki, w, selection, lse_i, blk, interpret):
    o, lse = _core_forward(q, k, v, selection, blk=blk, interpret=interpret)
    o, lse = (checkpoint_name(a, RESIDUALS_NAME) for a in (o, lse))
    return (o, lse, _zero(lse_i)), (
        q, k, v, qi, ki, w, selection, lse_i, o, lse
    )


def _bwd(blk, interpret, res, g):
    q, k, v, qi, ki, w, selection, lse_i, o, lse = res
    do, _, gkl = g  # lse feeds the KL's value alone
    dq, dk, dv, dqi, dki, dw = _backward(
        q, k, v, qi, ki, w, selection, lse_i, o, lse, do.astype(o.dtype), gkl,
        blk=blk, interpret=interpret,
    )
    return dq, dk, dv, dqi, dki, dw, None, None


sparse_core.defvjp(_fwd, _bwd)


def sparse_attention(q, k, v, qi, ki, w, selection, lse_i, blk,
                     interpret=False):
    """``(o [H, T, D], kl [T])`` of one sequence over its ``selection``: the
    core and the KL's value, the KL's target held constant."""
    o, lse, zero = sparse_core(
        q, k, v, qi, ki, w, selection, lse_i, blk, interpret
    )
    kl = kl_divergence(
        *jax.lax.stop_gradient((q, k, lse, qi, ki, w)), selection, lse_i,
        blk=blk, interpret=interpret,
    )
    return o, kl + zero


def sparse_attention_layer(q, k, v, qi, ki, w, *, topk, blk, interpret=False):
    """A model's layout in, ``(o [B, T, H, D], kl [B, T], selection,
    lse_i [B, T])`` out (``selected_keys(selection)`` counts the keys):
    ``q [B, T, H, D]``, ``k``, ``v [B, T, KVH, D]``, ``qi [B, T, HI, DI]``,
    ``ki [B, T, DI]``, ``w [B, T, HI]`` (float32, the scales folded in).
    The indexer's
    inputs take no gradient through the selection, only through the KL.

    Scopes: ``indexer`` holds the selection and the KL's value;
    ``attention`` > ``sparse_attention`` the core, forward and backward,
    and with its backward the KL's gradient into the indexer, which is
    made there from the core's own ``P``."""
    blk = min(blk, q.shape[1])
    heads_first = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    w = w.astype(jnp.float32)
    q, k, v, qi = (heads_first(a) for a in (q, k, v, qi))
    with jax.named_scope("indexer"):
        selection, lse_i = jax.vmap(functools.partial(
            sparse_select, topk=topk, blk=blk, interpret=interpret
        ))(*jax.lax.stop_gradient((qi, ki, w)))
        selection, lse_i = (
            checkpoint_name(a, RESIDUALS_NAME) for a in (selection, lse_i)
        )
    with jax.named_scope("attention"), jax.named_scope("sparse_attention"):
        o, lse, zero = jax.vmap(
            lambda *a: sparse_core(*a, blk, interpret)
        )(q, k, v, qi, ki, w, selection, lse_i)
    with jax.named_scope("indexer"):
        kl = jax.vmap(functools.partial(
            kl_divergence, blk=blk, interpret=interpret
        ))(*jax.lax.stop_gradient((q, k, lse, qi, ki, w)), selection, lse_i)
    return heads_first(o), kl + zero, selection, lse_i[..., 0]
