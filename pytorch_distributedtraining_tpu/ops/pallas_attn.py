"""Pallas TPU flash attention — the framework's hot-op custom kernel.

The reference leans on cuDNN/ATen fused kernels for its hot ops (`SURVEY.md`
§2.5 native checklist item 5); the TPU-native escape hatch is Pallas. The
forward computes blockwise attention with online softmax entirely in VMEM:
one [bq, dh] query tile stays resident while K/V stream through in [bk, dh]
tiles — O(T) HBM traffic instead of the O(T^2) logits round-trip, f32
accumulators on the MXU (`/opt/skills/guides/pallas_guide.md` patterns).

The backward recomputes P from the saved per-row logsumexp in the kernel
(no O(T^2) residuals ever touch HBM, fwd or bwd), in ONE pass in both
families below: one key tile resident, Q and dO whole in VMEM, the query
blocks that see the tile streaming past it; per block pair it makes the
scores, P, dO V^T and dS = P*(dO V^T - delta) once, then dv += P^T dO,
dk += dS^T Q, and dq[block] += dS K into a float32 [T, dh] accumulator in
VMEM that lives over the key tiles of a (batch, head or lane block) and
leaves for HBM once. That costs the whole dQ again in VMEM (73 MiB at
T = 16,384, dh = 128, blocks of 512). Where it does not fit
(``backward_path``: T = 16,384 at dh = 256, or at two packed heads of 64)
the dq kernel (one query tile resident, K and V whole) and the dk/dv kernel
run apart, computing the scores and dS twice: 74 and 89 MiB at those
shapes.

``delta = rowsum(dO * O)`` is a cheap elementwise XLA pass. Causal block
skipping applies in every kernel (upper-triangular tiles never run).
``make_flash_attn_fn`` returns a drop-in ``attn_fn`` for the model zoo.
The kernels compile for the TPU or raise; CPU tests exercise the same code
by asking for ``interpret=True`` themselves.

``causal_attention_qkv`` is the core a model runs when its caller names
none (`models/gpt2.py`): it takes ``qkv [B, T, 3 * H * dh]`` as the fused
projection wrote it and reads its blocks from the shapes. Where heads pack
into 128 lanes (two heads of 64, one of 128) the kernels read q, k and v
straight out of ``qkv`` a lane block at a time and write ``out`` and
``d qkv`` the same way: no ``[B, T, H, dh] <-> [B, H, T, dh]`` transpose, no
half-filled 64-lane tile, a head taken out of its block by a lane mask
(``(q * m_h) k^T`` contracts over all 128 lanes: the MXU pass a contraction
of 64 costs), the causal mask paid on the diagonal blocks only. Its
backward is the one pass above (``flash_packed_bwd``): the heads of a lane
block add ``dS_h (k * m_h)`` into one float32 [T, 128] accumulator, each
into its own columns, and ``backward_path`` counts the statistics of all
of them. Where the
count is odd (GPT-2 XL: 25 heads, k starts at column 1,600 = 12.5 blocks)
q, k and v are first copied into thirds of their own with a head of zeros
behind. ``kernel_contract`` says which shapes it takes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -1e30
# What a kernel may ask of a TensorCore's VMEM (128 MiB on v5e/v6e, less a
# margin for Mosaic's own scratch), and the scoped limit a kernel gets
# without asking. K and V (forward, dq) or Q and dO (backward, dk/dv) ride
# whole [T, dh] in VMEM, the fused backward's dQ block and its float32
# accumulator too: past the default the limit is raised to what the blocks
# need; past the ceiling the backward runs as two kernels, and a shape
# that even those cannot hold is refused before Mosaic is.
_VMEM_CEILING = 100 * 2**20
_VMEM_DEFAULT = 16 * 2**20
# Per-row stats (lse, delta) ride in [B, H, T, _STAT_LANES] instead of
# [B, H, T]: Mosaic requires a block's last two dims divisible by (8, 128)
# or equal to the array's — a (1, 1, bq) block of a rank-3 array violates
# that on real TPUs (dim -2 is 1 != H). A broadcast 8-lane trailing dim
# makes the block (bq, 8): bq%8==0 and 8==array dim, both legal, at 8x
# the traffic of a [T] vector — noise next to the O(T*dh) tiles.
_STAT_LANES = 8
_LANES = 128
# The names the forward rules give the two residuals only the forward kernel
# can produce, ``out`` and ``lse``. Every rematerialising policy of
# `parallel/remat.py` keeps them (its ``KERNEL_RESIDUALS``), so a
# checkpointed layer's backward reads them where it would else run the whole
# forward kernel again to regain them. Not the models' ``attn_out``: in
# GPT-2 XL the kernel's ``out`` is the padded [B, T, n * 128] and
# ``attn_out`` its slice, and under one name both would be kept. Outside a
# checkpoint the tags are the identity.
RESIDUALS_NAME = "attn_kernel_residuals"
# ``lse`` again, as whole rows of 128 lanes: what a policy keeps where the
# layers are a scan's bodies and the kept residuals are stacked over them
# (`parallel/remat.py` ``kept_names(..., stacked=True)``).
DENSE_LSE_NAME = "attn_kernel_lse_rows"


def _kept(out, lse):
    """``out`` and ``lse`` under ``RESIDUALS_NAME``, and ``lse`` once more
    under ``DENSE_LSE_NAME`` as whole rows of 128 lanes. As the kernels read
    it, [.., T, 8], a tile pads its 8 lanes to 128 in HBM: 16 times the
    statistic's bytes, which stacked over GPT-2 XL's 48 scanned layers are
    2.6 GB a chip where 0.16 are meant (compile plan, PR 36). The way there
    and back is two passes over the padded buffer (0.5-0.9 ms each at the
    cells' sizes; my chip runs, PR 36), so a policy keeps the rows only where
    the bytes are stacked; where it keeps ``lse`` as it is, or nothing, the
    two reshapes meet and cancel when the program is compiled."""
    out, lse = (checkpoint_name(a, RESIDUALS_NAME) for a in (out, lse))
    rows = (-1, _LANES) if lse.size % _LANES == 0 else (-1,)
    dense = checkpoint_name(lse.reshape(rows), DENSE_LSE_NAME)
    return out, dense.reshape(lse.shape)


def _visible(s, qi, ki, window):
    """Scores ``s [bq, bk]`` of query block ``qi`` and key block ``ki`` with
    what a query may not see set to ``_BIG_NEG``: keys after it and, under a
    ``window``, keys more than ``window - 1`` before it."""
    bq, bk = s.shape
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = kpos <= qpos
    if window is not None:
        keep = keep & (kpos > qpos - window)
    return jnp.where(keep, s, _BIG_NEG)


def _band_key_blocks(qi, bq, bk, window, nk):
    """Under a window: the key blocks ``[lo, end)`` query block ``qi`` sees
    any of, and ``[a, b)`` inside them that every one of its queries sees
    whole. Only the band's edges (the window's side ``[lo, a)``, the
    diagonal's ``[b, end)``) pay for a mask; blocks outside never run."""
    row0 = qi * bq
    end = jnp.minimum(nk, (row0 + bq + bk - 1) // bk)
    lo = jnp.maximum(row0 - window + 1, 0) // bk
    a = jnp.clip(
        (jnp.maximum(row0 + bq - window, 0) + bk - 1) // bk, lo, end
    )
    b = jnp.clip((row0 + 1) // bk, a, end)
    return lo, a, b, end


def _band_query_blocks(ki, bq, bk, window, nq):
    """Under a window: the query blocks ``[first, end)`` that see any of key
    block ``ki``, and ``[a, b)`` inside them that see all of it."""
    col0 = ki * bk
    first = col0 // bq
    end = jnp.minimum(nq, (col0 + bk + window - 2) // bq + 1)
    a = jnp.clip((col0 + bk + bq - 2) // bq, first, end)
    b = jnp.clip((col0 + window) // bq, a, end)
    return first, a, b, end


def _over_band(blocks, body, init):
    """``body(i, carry, masked)`` over a band's masked edge, its whole
    blocks and its other masked edge."""
    lo, a, b, end = blocks
    carry = jax.lax.fori_loop(
        lo, a, functools.partial(body, masked=True), init
    )
    carry = jax.lax.fori_loop(
        a, b, functools.partial(body, masked=False), carry
    )
    return jax.lax.fori_loop(
        b, end, functools.partial(body, masked=True), carry
    )


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq, bk, causal, scale,
    window=None,
):
    qi = pl.program_id(2)
    # operands stay in the caller's dtype (bf16 feeds the MXU at full
    # rate; an f32 matmul takes several passes), products accumulate in f32
    q = q_ref[0, 0]  # [bq, dh]
    t = k_ref.shape[2]
    dh = q.shape[-1]
    nk = t // bk

    def body(j, carry, masked=causal):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if masked:
            s = _visible(s, qi, j, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = l * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l

    acc0 = jnp.zeros((bq, dh), jnp.float32)
    m0 = jnp.full((bq,), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # causal: blocks with j*bk > (qi+1)*bq - 1 are fully masked; skip them
    if window is None:
        nk_run = jnp.minimum(nk, (qi + 1) * bq // bk + 1) if causal else nk
        acc, m, l = jax.lax.fori_loop(0, nk_run, body, (acc0, m0, l0))
    else:
        acc, m, l = _over_band(
            _band_key_blocks(qi, bq, bk, window, nk), body, (acc0, m0, l0)
        )
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # per-row logsumexp of scaled logits, lane-broadcast (see _STAT_LANES)
    lse_ref[0, 0] = jnp.broadcast_to(
        (m + jnp.log(l_safe))[:, None], (bq, _STAT_LANES)
    )


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, bq, bk, causal, scale, window=None,
):
    qi = pl.program_id(2)
    q = q_ref[0, 0]  # [bq, dh]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, :, 0]  # [bq] (lane-broadcast stats, col 0)
    delta = delta_ref[0, 0, :, 0]  # [bq]
    t = k_ref.shape[2]
    nk = t // bk

    def body(j, dq, masked=causal):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if masked:
            s = _visible(s, qi, j, window)
        p = jnp.exp(s - lse[:, None])  # [bq, bk], masked entries -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if window is None:
        nk_run = jnp.minimum(nk, (qi + 1) * bq // bk + 1) if causal else nk
        dq = jax.lax.fori_loop(
            0, nk_run, body, jnp.zeros((bq, q.shape[-1]), jnp.float32)
        )
    else:
        dq = _over_band(
            _band_key_blocks(qi, bq, bk, window, nk), body,
            jnp.zeros((bq, q.shape[-1]), jnp.float32),
        )
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dq_ref=None, dq_acc=None, *, bq, bk, causal, scale, window=None,
):
    """dK and dV of one key block, the query blocks it is visible to
    streaming past it. Given ``dq_ref`` (the fused pass) it makes dQ too:
    each visited query block's ``dS K`` is added to its rows of ``dq_acc``,
    the whole sequence's float32 [T, dh] in VMEM, over the grid's key-block
    axis (innermost, in order), and the sum leaves for ``dq_ref``, whose
    block stays resident over that axis, once the last key block has run.
    The scores, ``exp``, ``dO V^T`` and ``dS`` are then made once a block
    pair, and a query block's dQ is still summed over its key blocks in
    ascending order."""
    ki = pl.program_id(2)
    k = k_ref[0, 0]  # [bk, dh]
    v = v_ref[0, 0]
    t = q_ref.shape[2]
    dh = k.shape[-1]
    nq = t // bq
    if dq_acc is not None:
        @pl.when(ki == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(i, carry, masked=causal):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * bq, bq), :]
        do = do_ref[0, 0, pl.ds(i * bq, bq), :]
        lse = lse_ref[0, 0, pl.ds(i * bq, bq), 0]
        delta = delta_ref[0, 0, pl.ds(i * bq, bq), 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if masked:
            s = _visible(s, i, ki, window)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, dh]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta[:, None])
        if dq_acc is not None:
            dq_acc[pl.ds(i * bq, bq), :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, dh]
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, dh]
        return dk, dv

    # causal: q tiles strictly above the diagonal band never attend this
    # key tile — start at the first row tile whose end reaches ki*bk
    if window is None:
        i0 = (ki * bk) // bq if causal else 0
        dk, dv = jax.lax.fori_loop(
            i0, nq, body,
            (jnp.zeros((bk, dh), jnp.float32), jnp.zeros((bk, dh), jnp.float32)),
        )
    else:
        dk, dv = _over_band(
            _band_query_blocks(ki, bq, bk, window, nq), body,
            (jnp.zeros((bk, dh), jnp.float32), jnp.zeros((bk, dh), jnp.float32)),
        )
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    if dq_acc is not None:
        @pl.when(ki == pl.num_programs(2) - 1)
        def _():
            dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _check_blocks(t, bq, bk):
    if t % bq or t % bk:
        raise ValueError(f"seq len {t} must divide block sizes ({bq},{bk})")


def _vmem_need(t, dh, bq, bk, dtype, *, whole, tiles, stat_rows, scratch=0):
    """Bytes of VMEM a kernel asks for that holds ``whole`` [T, dh] blocks,
    ``tiles`` [max(bq, bk), dh] blocks and per-row statistics of
    ``stat_rows`` rows, each double-buffered by the pipeline (a statistics
    block's 8 lanes pad to a 128-lane f32 tile), and ``scratch`` bytes of
    its own."""
    item = jnp.dtype(dtype).itemsize
    return 2 * (
        whole * t * dh * item
        + tiles * max(bq, bk) * dh * item
        + stat_rows * 128 * 4
    ) + scratch + 8 * bq * bk * 4  # the [bq, bk] f32 score-sized temporaries


def _vmem_params(what, t, dh, bq, bk, dtype, **blocks):
    """Compiler parameters for a kernel of ``_vmem_need(**blocks)``. Raises
    where the blocks cannot fit a TensorCore's VMEM: the sequence has to be
    streamed then, which this kernel does not do."""
    need = _vmem_need(t, dh, bq, bk, dtype, **blocks)
    if need > _VMEM_CEILING:
        raise ValueError(
            f"flash_attention {what}: T={t}, dh={dh}, {jnp.dtype(dtype).name} "
            f"needs about {need / 2**20:.0f} MiB of VMEM for its whole-"
            f"sequence blocks, over the {_VMEM_CEILING / 2**20:.0f} MiB a "
            "kernel may use: shorten the sequence per call (ring_attention "
            "splits it over chips)"
        )
    if need <= _VMEM_DEFAULT // 2:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(_VMEM_CEILING, max(_VMEM_DEFAULT, 2 * need))
    )


def _fused_backward_blocks(t, dh, hb=1):
    """The fused backward's VMEM: Q, dO and dQ whole (dQ's block stays
    resident over the key blocks), the key-value tiles and dK, dV, both
    statistics of the block's ``hb`` heads whole, and dQ's float32
    accumulator, one buffer."""
    return dict(whole=3, tiles=4, stat_rows=2 * hb * t, scratch=t * dh * 4)


def backward_path(t, dh, bq, bk, dtype, hb=1):
    """``(path, reason)`` of the backward at this shape: ``"fused"``, one
    pass, where its footprint fits under ``_VMEM_CEILING``; else
    ``"two_kernels"``, dq and dk/dv apart, which need less (T = 16,384 at
    dh = 256: 106 MiB fused, 74 apart). ``dh`` is the width of a block's
    lanes and ``hb`` the heads side by side in them: the split-head family
    runs one head of ``dh`` a block, the packed family ``hb`` heads in 128
    lanes (T = 16,384 at heads of 64: 105 MiB fused, 89 apart). The
    footprint is the only reason there are two paths."""
    need = _vmem_need(
        t, dh, bq, bk, dtype, **_fused_backward_blocks(t, dh, hb)
    )
    ceiling = _VMEM_CEILING / 2**20
    if need <= _VMEM_CEILING:
        return "fused", (
            f"one pass: {need / 2**20:.0f} MiB of VMEM, within the "
            f"{ceiling:.0f} MiB a kernel may use"
        )
    return "two_kernels", (
        f"one pass would need {need / 2**20:.0f} MiB of VMEM, over the "
        f"{ceiling:.0f} MiB a kernel may use: dq and dk/dv run apart"
    )


def _kv_head(group, whole):
    """Index map of a key / value block on a grid (batch, QUERY head, block):
    query head ``h`` reads key-value head ``h // group``, a tile a step or
    the whole sequence. No copy of k or v is made for the group; steps that
    share a key-value head find its block already in VMEM."""
    if group == 1:  # today's maps, today's program
        return (
            (lambda b_, h_, i: (b_, h_, 0, 0)) if whole
            else (lambda b_, h_, i: (b_, h_, i, 0))
        )
    return (
        (lambda b_, h_, i: (b_, h_ // group, 0, 0)) if whole
        else (lambda b_, h_, i: (b_, h_ // group, i, 0))
    )


def _check_heads(q, k, causal, window):
    h, kvh = q.shape[2], k.shape[2]
    if h % kvh:
        raise ValueError(
            f"flash_attention: {h} query heads do not divide over {kvh} "
            "key-value heads"
        )
    if window is not None and not (causal and window >= 1):
        raise ValueError(
            "flash_attention: a window (>= 1) is the causal one: query t "
            "sees keys t - window + 1 .. t"
        )
    return h // kvh


def _flash_forward(q, k, v, *, causal, bq, bk, interpret, window=None):
    """Returns (out, lse) in the caller's [B, T, H, Dh] layout for out and
    [B, H, T, _STAT_LANES] (lane-broadcast) for lse. ``k`` and ``v`` may
    have fewer heads than ``q`` (a divisor of its count)."""
    b, t, h, dh = q.shape
    group = _check_heads(q, k, causal, window)
    bq, bk = min(bq, t), min(bk, t)
    _check_blocks(t, bq, bk)
    scale = 1.0 / (dh**0.5)
    # [B, H, T, Dh] — contiguous K/V streams per (batch, head) program
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    grid = (b, h, t // bq)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, bq=bq, bk=bk, causal=causal, scale=scale,
            window=window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, t, dh), _kv_head(group, whole=True)),
            pl.BlockSpec((1, 1, t, dh), _kv_head(group, whole=True)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec(
                (1, 1, bq, _STAT_LANES), lambda b_, h_, i: (b_, h_, i, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, t, _STAT_LANES), jnp.float32),
        ],
        compiler_params=_vmem_params(
            "forward", t, dh, bq, bk, q.dtype, whole=2, tiles=2, stat_rows=bq
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _flash_backward(
    q, k, v, out, lse, do, *, causal, bq, bk, interpret, window=None
):
    """dq, dk, dv in the caller's layout: one fused pass where
    ``backward_path`` says it fits VMEM, else the dq and dk/dv kernels.
    Each trace leaves the instant ``flash.backward`` saying which."""
    from ..observe import trace

    b, t, h, dh = q.shape
    group = _check_heads(q, k, causal, window)
    bq, bk = min(bq, t), min(bk, t)
    _check_blocks(t, bq, bk)
    scale = 1.0 / (dh**0.5)
    qt, kt, vt, ot, dot_ = (
        a.transpose(0, 2, 1, 3) for a in (q, k, v, out, do)
    )
    # delta_i = dO_i . O_i — one elementwise pass, XLA fuses it; carried
    # lane-broadcast like lse (see _STAT_LANES)
    delta = jnp.broadcast_to(
        jnp.sum(
            dot_.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1
        )[..., None],
        (b, h, t, _STAT_LANES),
    )

    tile_q = pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i: (b_, h_, i, 0))
    tile_k = pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, i: (b_, h_, i, 0))
    full_seq = pl.BlockSpec((1, 1, t, dh), lambda b_, h_, i: (b_, h_, 0, 0))
    full_kv = pl.BlockSpec((1, 1, t, dh), _kv_head(group, whole=True))
    tile_kv = pl.BlockSpec((1, 1, bk, dh), _kv_head(group, whole=False))
    row_q = pl.BlockSpec(
        (1, 1, bq, _STAT_LANES), lambda b_, h_, i: (b_, h_, i, 0)
    )
    row_full = pl.BlockSpec(
        (1, 1, t, _STAT_LANES), lambda b_, h_, i: (b_, h_, 0, 0)
    )

    path, reason = backward_path(t, dh, bq, bk, q.dtype)
    trace.instant(
        "flash.backward", path=path, reason=reason, t=t, dh=dh, heads=h,
        kv_heads=k.shape[2], window=window, bq=bq, bk=bk,
    )
    kernel = dict(bq=bq, bk=bk, causal=causal, scale=scale, window=window)
    # one program a QUERY head: where a key-value head serves a group, each
    # of its query heads writes its own part of dk and dv (float32) and one
    # pass sums the group's
    part = k.dtype if group == 1 else jnp.float32
    dkv_shapes = [
        jax.ShapeDtypeStruct(qt.shape[:3] + kt.shape[3:], part),
        jax.ShapeDtypeStruct(qt.shape[:3] + vt.shape[3:], part),
    ]
    dkv_in = [full_seq, tile_kv, tile_kv, full_seq, row_full, row_full]
    if path == "fused":
        dk, dv, dq = pl.pallas_call(
            functools.partial(_dkv_kernel, **kernel),
            grid=(b, h, t // bk),
            in_specs=dkv_in,
            out_specs=[tile_k, tile_k, full_seq],
            out_shape=[*dkv_shapes, jax.ShapeDtypeStruct(qt.shape, q.dtype)],
            scratch_shapes=[pltpu.VMEM((t, dh), jnp.float32)],
            compiler_params=_vmem_params(
                "backward", t, dh, bq, bk, q.dtype,
                **_fused_backward_blocks(t, dh),
            ),
            interpret=interpret,
            name="flash_bwd",
        )(qt, kt, vt, dot_, lse, delta)
    else:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **kernel),
            grid=(b, h, t // bq),
            in_specs=[tile_q, full_kv, full_kv, tile_q, row_q, row_q],
            out_specs=tile_q,
            out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
            compiler_params=_vmem_params(
                "dq", t, dh, bq, bk, q.dtype, whole=2, tiles=3,
                stat_rows=2 * bq,
            ),
            interpret=interpret,
        )(qt, kt, vt, dot_, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **kernel),
            grid=(b, h, t // bk),
            in_specs=dkv_in,
            out_specs=[tile_k, tile_k],
            out_shape=dkv_shapes,
            compiler_params=_vmem_params(
                "dk/dv", t, dh, bq, bk, q.dtype, whole=2, tiles=4,
                stat_rows=2 * t,
            ),
            interpret=interpret,
        )(qt, kt, vt, dot_, lse, delta)
    if group > 1:
        dk, dv = (
            jnp.sum(a.reshape(b, h // group, group, t, -1), axis=2).astype(
                k.dtype
            )
            for a in (dk, dv)
        )
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q, k, v, causal: bool = True, bq: int = 128, bk: int = 128,
    interpret: bool = False, window: int | None = None,
):
    """Flash attention. q: [B, T, H, Dh], k/v: [B, T, KVH, Dh] with KVH a
    divisor of H (query head h reads key-value head h // (H / KVH)) ->
    [B, T, H, Dh]. ``window``: query t sees keys t - window + 1 .. t and
    the kernels run the band's blocks only; None is the causal triangle."""
    out, _ = _flash_forward(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret,
        window=window,
    )
    return out


def _fwd(q, k, v, causal, bq, bk, interpret, window):
    # ``out`` is named as the caller gets it, [B, T, H, Dh]: named as the
    # kernel wrote it, [B, H, T, Dh], the backward kernels read it where it
    # lies but the projection's backward transposes it, and two of three
    # cells ran 0.5-0.8% slower (my chip runs, PR 36)
    out, lse = _kept(*_flash_forward(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret,
        window=window,
    ))
    return out, (q, k, v, out, lse)


def _bwd(causal, bq, bk, interpret, window, res, g):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, g, causal=causal, bq=bq, bk=bk,
        interpret=interpret, window=window,
    )


flash_attention.defvjp(_fwd, _bwd)


def make_flash_attn_fn(
    *, bq: int = 128, bk: int = 128, interpret: bool = False
):
    """Drop-in ``attn_fn`` for models/. ``interpret=True`` is for CPU tests;
    the default compiles the kernel, and a compile error propagates."""

    def attn_fn(q, k, v, *, causal: bool = True, window: int | None = None):
        return flash_attention(q, k, v, causal, bq, bk, interpret, window)

    return attn_fn


# ---------------------------------------------------------------------------
# The core on ``qkv`` as the fused projection wrote it: heads packed into
# 128-lane blocks, causal.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _each_head(hb, dh, one_head, init):
    """``one_head(h, mask, carry) -> carry`` over the ``hb`` heads of a lane
    block, ``mask [1, 128]`` saying which columns are head ``h``'s (None for
    a block that is one head). A loop in the program, not in Python: a
    kernel's body is traced, lowered and compiled once whatever ``hb`` (its
    trace is most of what the core adds to a run's set-up)."""
    if hb == 1:
        return one_head(0, None, init)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def step(h, carry):
        return one_head(h, (lane >= h * dh) & (lane < (h + 1) * dh), carry)

    return jax.lax.fori_loop(0, hb, step, init)


def _only(mask, x):
    """``x`` with the other heads' columns zeroed: a product over all 128
    lanes is then this head's alone."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _merge(mask, x, into):
    """This head's columns of ``x`` written into ``into``."""
    return x if mask is None else jnp.where(mask, x, into)


def _folds(scale):
    """A power of two multiplies bf16 operands exactly: the scale then goes
    onto a [block, 128] operand once instead of onto every score."""
    return math.frexp(scale)[0] == 0.5


def _causal(s, row0, col0):
    bq, bk = s.shape
    qpos = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(kpos <= qpos, s, _BIG_NEG)


def _key_blocks(qi, bq, bk):
    """Key blocks a query block sees whole (no mask needed), and where its
    last visible one ends: only the blocks on the diagonal pay for a mask."""
    return (qi * bq + 1) // bk, ((qi + 1) * bq + bk - 1) // bk


def _query_blocks(ki, bq, bk, nq):
    """For a key block: the first query block that sees any of it, and the
    first that sees all of it."""
    return (ki * bk) // bq, jnp.minimum(nq, ((ki + 1) * bk + bq - 2) // bq)


def _packed_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq, bk, hb, dh, scale
):
    qi = pl.program_id(2)
    q = q_ref[0]  # [bq, 128]: hb heads side by side
    whole, end = _key_blocks(qi, bq, bk)

    def one_head(h, mask, out):
        q_h = _only(mask, q)
        if _folds(scale):
            q_h = q_h * jnp.asarray(scale, q_h.dtype)

        def body(j, carry, masked):
            acc, m, l = carry
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            k, v = k_ref[0, at, :], v_ref[0, at, :]
            s = _dot(q_h, k, _NT)  # [bq, bk]
            if not _folds(scale):
                s = s * scale
            if masked:
                s = _causal(s, qi * bq, j * bk)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, None])
            l = l * corr + jnp.sum(p, axis=1)
            # [bq, 128]: this head's columns are its product, the others'
            # are dropped at the merge
            acc = acc * corr[:, None] + _dot(p.astype(v.dtype), v, _NN)
            return acc, m_new, l

        carry = (
            jnp.zeros((bq, _LANES), jnp.float32),
            jnp.full((bq,), _BIG_NEG, jnp.float32),
            jnp.zeros((bq,), jnp.float32),
        )
        carry = jax.lax.fori_loop(
            0, whole, functools.partial(body, masked=False), carry
        )
        acc, m, l = jax.lax.fori_loop(
            whole, end, functools.partial(body, masked=True), carry
        )
        lse_ref[0, h] = jnp.broadcast_to(
            (m + jnp.log(l))[:, None], (bq, _STAT_LANES)
        )
        return _merge(mask, acc / l[:, None], out)

    out = _each_head(
        hb, dh, one_head, jnp.zeros((bq, _LANES), jnp.float32)
    )
    o_ref[0] = out.astype(o_ref.dtype)


def _packed_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, bq, bk, hb, dh, scale,
):
    qi = pl.program_id(2)
    q, do = q_ref[0], do_ref[0]  # [bq, 128]
    whole, end = _key_blocks(qi, bq, bk)

    def one_head(h, mask, out):
        q_h, do_h = _only(mask, q), _only(mask, do)
        if _folds(scale):
            q_h = q_h * jnp.asarray(scale, q_h.dtype)
        lse = lse_ref[0, h, :, 0]  # [bq]
        delta = delta_ref[0, h, :, 0]

        def body(j, dq, masked):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            k, v = k_ref[0, at, :], v_ref[0, at, :]
            s = _dot(q_h, k, _NT)
            if not _folds(scale):
                s = s * scale
            if masked:
                s = _causal(s, qi * bq, j * bk)
            p = jnp.exp(s - lse[:, None])  # masked entries -> 0
            ds = p * (_dot(do_h, v, _NT) - delta[:, None])
            return dq + _dot(ds.astype(k.dtype), k, _NN)

        dq = jax.lax.fori_loop(
            0, whole, functools.partial(body, masked=False),
            jnp.zeros((bq, _LANES), jnp.float32),
        )
        dq = jax.lax.fori_loop(
            whole, end, functools.partial(body, masked=True), dq
        )
        return _merge(mask, dq * scale, out)

    out = _each_head(
        hb, dh, one_head, jnp.zeros((bq, _LANES), jnp.float32)
    )
    dq_ref[0] = out.astype(dq_ref.dtype)


def _packed_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dq_ref=None, dq_acc=None, *, bq, bk, hb, dh, scale,
):
    """dK and dV of one key block for the ``hb`` heads of a lane block, the
    query blocks that see it streaming past. Given ``dq_ref`` (the fused
    pass) it makes dQ too, as ``_dkv_kernel`` does: each visited query
    block's ``dS_h k_h`` (``k_h`` the key tile with the other heads' lanes
    zeroed, so the heads of a block share one accumulator and need no merge)
    is added to its rows of ``dq_acc``, the lane block's float32 [T, 128]
    over the grid's key-block axis, and leaves for ``dq_ref`` once the last
    key block has run."""
    ki = pl.program_id(2)
    k, v = k_ref[0], v_ref[0]  # [bk, 128]
    nq = q_ref.shape[1] // bq
    first, whole = _query_blocks(ki, bq, bk, nq)
    if dq_acc is not None:
        @pl.when(ki == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def one_head(h, mask, outs):
        k_h, v_h = _only(mask, k), _only(mask, v)
        k_s = k_h * jnp.asarray(scale, k_h.dtype) if _folds(scale) else k_h

        def body(i, carry, masked):
            dk, dv = carry
            at = pl.ds(pl.multiple_of(i * bq, bq), bq)
            q, do = q_ref[0, at, :], do_ref[0, at, :]  # every head's columns
            s = _dot(q, k_s, _NT)  # [bq, bk]: k_s's zeros leave this head's
            if not _folds(scale):
                s = s * scale
            if masked:
                s = _causal(s, i * bq, ki * bk)
            p = jnp.exp(s - lse_ref[0, h, at, 0][:, None])
            dv = dv + _dot(p.astype(do.dtype), do, _TN)  # [bk, 128]
            ds = p * (_dot(do, v_h, _NT) - delta_ref[0, h, at, 0][:, None])
            if dq_acc is not None:  # this head's columns, scaled at the end
                dq_acc[at, :] += _dot(ds.astype(k.dtype), k_h, _NN)
            dk = dk + _dot(ds.astype(q.dtype), q, _TN)
            return dk, dv

        carry = jax.lax.fori_loop(
            first, whole, functools.partial(body, masked=True),
            (jnp.zeros((bk, _LANES), jnp.float32),) * 2,
        )
        dk, dv = jax.lax.fori_loop(
            whole, nq, functools.partial(body, masked=False), carry
        )
        return _merge(mask, dk * scale, outs[0]), _merge(mask, dv, outs[1])

    dk, dv = _each_head(
        hb, dh, one_head, (jnp.zeros((bk, _LANES), jnp.float32),) * 2
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    if dq_acc is not None:
        @pl.when(ki == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _lane_specs(first):
    """Block specs on a grid (batch, lane block, sequence block) for arrays
    ``[B, T, lanes]`` whose lane block ``first + p`` belongs to grid column
    ``p``, a tile of ``rows`` a step or the whole sequence; and for the
    per-row statistics ``[B, H, T, 8]`` of the ``hb`` heads of a block."""
    def lanes(rows, whole=False):
        return pl.BlockSpec(
            (1, rows, _LANES),
            (lambda b, p, i: (b, 0, first + p)) if whole
            else (lambda b, p, i: (b, i, first + p)),
        )
    return lanes


def _stat_specs(hb):
    def stats(rows, whole=False):
        return pl.BlockSpec(
            (1, hb, rows, _STAT_LANES),
            (lambda b, p, i: (b, p, 0, 0)) if whole
            else (lambda b, p, i: (b, p, i, 0)),
        )
    return stats


_KERNEL_STATICS = ("first", "n", "dh", "bq", "bk", "interpret")


# jitted: autodiff asks for a kernel more than once (the primal and the
# custom-vjp forward; the backward at linearisation and at transposition),
# and tracing a kernel's body is most of what the core adds to a run's set-up
@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _packed_forward(qkv, first, n, dh, bq, bk, interpret):
    """``qkv``: the arrays q, k and v are read from (one array three times
    where the fused projection wrote them side by side), ``first``: the lane
    block each starts at, ``n``: lane blocks of heads. Returns ``out
    [B, T, n * 128]`` and ``lse [B, n * hb, T, 8]``."""
    b, t, _ = qkv[0].shape
    hb = _LANES // dh
    q_at, k_at, v_at = (_lane_specs(f) for f in first)
    out_at, stats = _lane_specs(0), _stat_specs(hb)
    return pl.pallas_call(
        functools.partial(
            _packed_fwd_kernel, bq=bq, bk=bk, hb=hb, dh=dh, scale=dh**-0.5
        ),
        grid=(b, n, t // bq),
        in_specs=[q_at(bq), k_at(t, whole=True), v_at(t, whole=True)],
        out_specs=[out_at(bq), stats(bq)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, n * _LANES), qkv[0].dtype),
            jax.ShapeDtypeStruct((b, n * hb, t, _STAT_LANES), jnp.float32),
        ],
        compiler_params=_vmem_params(
            "forward", t, _LANES, bq, bk, qkv[0].dtype,
            whole=2, tiles=2, stat_rows=hb * bq,
        ),
        interpret=interpret,
        name="flash_packed_fwd",
    )(*qkv)


def _packed_backward(qkv, first, n, dh, out, lse, do, bq, bk, interpret):
    """-> ``(dq, dk, dv)``, each ``[B, T, n * 128]``: one fused pass where
    ``backward_path`` says it fits VMEM, else the dq and dk/dv kernels. Each
    trace leaves the instant ``flash.backward`` saying which."""
    from ..observe import trace

    t, hb = qkv[0].shape[1], _LANES // dh
    path, reason = backward_path(t, _LANES, bq, bk, qkv[0].dtype, hb)
    trace.instant(
        "flash.backward", path=path, reason=reason, t=t, dh=dh, heads=n * hb,
        kv_heads=n * hb, window=None, bq=bq, bk=bk, family="packed", hb=hb,
    )
    return _packed_backward_kernels(
        qkv, first, n, dh, out, lse, do, bq, bk, interpret, path
    )


@functools.partial(jax.jit, static_argnames=(*_KERNEL_STATICS, "path"))
def _packed_backward_kernels(
    qkv, first, n, dh, out, lse, do, bq, bk, interpret, path
):
    b, t, _ = qkv[0].shape
    hb = _LANES // dh
    # delta_i = dO_i . O_i per head: one elementwise pass, lane-broadcast
    # like lse (see _STAT_LANES)
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            b, t, n * hb, dh
        ),
        axis=-1,
    ).transpose(0, 2, 1)
    delta = jnp.broadcast_to(delta[..., None], (b, n * hb, t, _STAT_LANES))
    kernel = dict(bq=bq, bk=bk, hb=hb, dh=dh, scale=dh**-0.5)
    third = jax.ShapeDtypeStruct((b, t, n * _LANES), qkv[0].dtype)
    q_at, k_at, v_at = (_lane_specs(f) for f in first)
    own, stats = _lane_specs(0), _stat_specs(hb)  # out, d out, d q / k / v
    dkv_in = [
        q_at(t, whole=True), k_at(bk), v_at(bk), own(t, whole=True),
        stats(t, whole=True), stats(t, whole=True),
    ]
    if path == "fused":
        dk, dv, dq = pl.pallas_call(
            functools.partial(_packed_dkv_kernel, **kernel),
            grid=(b, n, t // bk),
            in_specs=dkv_in,
            out_specs=[own(bk), own(bk), own(t, whole=True)],
            out_shape=[third] * 3,
            scratch_shapes=[pltpu.VMEM((t, _LANES), jnp.float32)],
            compiler_params=_vmem_params(
                "backward", t, _LANES, bq, bk, qkv[0].dtype,
                **_fused_backward_blocks(t, _LANES, hb),
            ),
            interpret=interpret,
            name="flash_packed_bwd",
        )(*qkv, do, lse, delta)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_packed_dq_kernel, **kernel),
        grid=(b, n, t // bq),
        in_specs=[
            q_at(bq), k_at(t, whole=True), v_at(t, whole=True), own(bq),
            stats(bq), stats(bq),
        ],
        out_specs=own(bq),
        out_shape=third,
        compiler_params=_vmem_params(
            "dq", t, _LANES, bq, bk, qkv[0].dtype,
            whole=2, tiles=3, stat_rows=2 * hb * bq,
        ),
        interpret=interpret,
        name="flash_packed_dq",
    )(*qkv, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_packed_dkv_kernel, **kernel),
        grid=(b, n, t // bk),
        in_specs=dkv_in,
        out_specs=[own(bk), own(bk)],
        out_shape=[third, third],
        compiler_params=_vmem_params(
            "dk/dv", t, _LANES, bq, bk, qkv[0].dtype,
            whole=2, tiles=4, stat_rows=2 * hb * t,
        ),
        interpret=interpret,
        name="flash_packed_dkv",
    )(*qkv, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_qkv(qkv, heads, bq, bk, interpret=False):
    """Causal flash attention over ``qkv [B, T, 3 * H * dh]`` as the fused
    projection wrote it -> ``[B, T, H * dh]``, for heads that fill 128-lane
    blocks (``packs``): q, k and v are read where they lie."""
    return _packed_forward(*_in_place(qkv, heads), bq, bk, interpret)[0]


def _in_place(qkv, heads):
    n = qkv.shape[-1] // 3 // _LANES
    return (qkv,) * 3, (0, n, 2 * n), n, qkv.shape[-1] // 3 // heads


def _fwd_qkv(qkv, heads, bq, bk, interpret):
    out, lse = _kept(
        *_packed_forward(*_in_place(qkv, heads), bq, bk, interpret)
    )
    return out, (qkv, out, lse)


def _bwd_qkv(heads, bq, bk, interpret, res, g):
    qkv, out, lse = res
    grads = _packed_backward(
        *_in_place(qkv, heads), out, lse, g, bq, bk, interpret
    )
    return (jnp.concatenate(grads, axis=-1),)


flash_attention_qkv.defvjp(_fwd_qkv, _bwd_qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lanes(q, k, v, dh, bq, bk, interpret=False):
    """Causal flash attention over q, k, v ``[B, T, n * 128]``, heads of
    ``dh`` side by side in the lanes, -> ``[B, T, n * 128]``: the layout a
    head count that does not fill its last block is padded into."""
    n = q.shape[-1] // _LANES
    return _packed_forward((q, k, v), (0, 0, 0), n, dh, bq, bk, interpret)[0]


def _fwd_lanes(q, k, v, dh, bq, bk, interpret):
    n = q.shape[-1] // _LANES
    out, lse = _kept(
        *_packed_forward((q, k, v), (0, 0, 0), n, dh, bq, bk, interpret)
    )
    return out, (q, k, v, out, lse)


def _bwd_lanes(dh, bq, bk, interpret, res, g):
    q, k, v, out, lse = res
    n = q.shape[-1] // _LANES
    return _packed_backward(
        (q, k, v), (0, 0, 0), n, dh, out, lse, g, bq, bk, interpret
    )


flash_attention_lanes.defvjp(_fwd_lanes, _bwd_lanes)


def packs(heads, dh):
    """Whether whole heads fill 128-lane blocks of ``qkv``'s thirds: q, k
    and v then start on a block each and ``flash_attention_qkv`` reads them
    where they lie."""
    return _LANES % dh == 0 and heads % (_LANES // dh) == 0


def attention_blocks(t):
    """(bq, bk) the core runs a sequence of ``t`` in: the largest of 512,
    256, 128 that divides it, or None where none does. On the v5e at
    T = 1,024 and heads of 64, forward + backward, blocks of 512 took 2.8 ms
    against 4.0 at 256 and 6.7 at 128 (a larger block skips less of the
    causal square and still wins: fewer, fuller loop steps), 1,024 no less
    (my chip run, PR 30: `PERF.md` §6)."""
    return next(((b, b) for b in (512, 256, 128) if t % b == 0), None)


def kernel_contract(t, heads, dh, dtype):
    """None where ``causal_attention_qkv`` takes these shapes, else why it
    does not, in words."""
    name = jnp.dtype(dtype).name
    if name not in ("bfloat16", "float32"):
        return f"dtype {name} is neither bfloat16 nor float32"
    if dh not in (64, 128):
        return f"head size {dh} does not fill 128 lanes by ones or twos"
    blocks = attention_blocks(t)
    if blocks is None:
        return f"T={t} is not a multiple of the smallest block, 128"
    try:  # the dk/dv kernel's footprint: the least a backward path needs
        _vmem_params(
            "dk/dv", t, _LANES, *blocks, dtype,
            whole=2, tiles=4, stat_rows=2 * (_LANES // dh) * t,
        )
    except ValueError as e:
        return str(e)
    return None


def causal_attention_qkv(qkv, heads, interpret=False, blocks=None):
    """The causal attention core between the two projections:
    ``qkv [B, T, 3 * H * dh]`` -> ``[B, T, H, dh]``, blocks ``(bq, bk)`` read
    from the shapes unless given. Heads that fill 128-lane blocks are read
    out of ``qkv`` where they lie; an odd count of heads of 64 (GPT-2 XL's
    25: k starts half way into a block) is copied into thirds of their own
    with one head of zeros behind, which costs a pass over ``qkv``. Raises
    where ``kernel_contract`` refuses the shapes."""
    b, t, d3 = qkv.shape
    d = d3 // 3
    dh = d // heads
    why = kernel_contract(t, heads, dh, qkv.dtype)
    if why is not None:
        raise ValueError(f"causal_attention_qkv: {why}")
    bq, bk = blocks or attention_blocks(t)
    if packs(heads, dh):
        out = flash_attention_qkv(qkv, heads, bq, bk, interpret)
    else:
        # ``qkv`` is held T-minor here, the layout XLA gives it anyway where
        # ``c_attn``'s kernel is sharded (ZeRO-3: a matmul over gathered
        # column slices, each landing whole in its rows). A pad alone hands
        # the kernels' row-major layout back through to that matmul, which
        # then lands every slice at a lane offset of 1,200:
        # gpt2-xl.zero3-4chip lost in `c_attn` what the kernels won (+59 ms
        # a step of `dynamic-update-slice`; my chip runs, PR 30). The copy
        # below is then the one transpose.
        qkv = with_layout_constraint(qkv, Layout(major_to_minor=(0, 2, 1)))
        to_block = [(0, 0), (0, 0), (0, -d % _LANES)]
        q, k, v = (jnp.pad(a, to_block) for a in jnp.split(qkv, 3, axis=-1))
        out = flash_attention_lanes(q, k, v, dh, bq, bk, interpret)[..., :d]
    return out.reshape(b, t, heads, dh)
