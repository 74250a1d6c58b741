"""Pallas TPU flash attention — the framework's hot-op custom kernel.

The reference leans on cuDNN/ATen fused kernels for its hot ops (`SURVEY.md`
§2.5 native checklist item 5); the TPU-native escape hatch is Pallas. The
forward computes blockwise attention with online softmax entirely in VMEM:
one [bq, dh] query tile stays resident while K/V stream through in [bk, dh]
tiles — O(T) HBM traffic instead of the O(T^2) logits round-trip, f32
accumulators on the MXU (`/opt/skills/guides/pallas_guide.md` patterns).

The backward is the FlashAttention-2 scheme as two Pallas kernels with
in-kernel recompute from the saved per-row logsumexp (no O(T^2) residuals
ever touch HBM, fwd or bwd):

  - dq kernel: one query tile resident, K/V stream; recomputes P from lse,
    dS = P*(dO V^T - delta), dq += dS K.
  - dk/dv kernel: one key tile resident, Q/dO stream; dv += P^T dO,
    dk += dS^T Q.

``delta = rowsum(dO * O)`` is a cheap elementwise XLA pass. Causal block
skipping applies in all three kernels (upper-triangular tiles never run).
``make_flash_attn_fn`` returns a drop-in ``attn_fn`` for the model zoo.
The kernels compile for the TPU or raise; CPU tests exercise the same code
by asking for ``interpret=True`` themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG_NEG = -1e30
# What a kernel may ask of a TensorCore's VMEM (128 MiB on v5e/v6e, less a
# margin for Mosaic's own scratch), and the scoped limit a kernel gets
# without asking. K and V (forward, dq) or Q and dO (dk/dv) ride whole
# [T, dh] in VMEM: past the default the limit is raised to what the blocks
# need, and past the ceiling the shape is refused before Mosaic is.
_VMEM_CEILING = 100 * 2**20
_VMEM_DEFAULT = 16 * 2**20
# Per-row stats (lse, delta) ride in [B, H, T, _STAT_LANES] instead of
# [B, H, T]: Mosaic requires a block's last two dims divisible by (8, 128)
# or equal to the array's — a (1, 1, bq) block of a rank-3 array violates
# that on real TPUs (dim -2 is 1 != H). A broadcast 8-lane trailing dim
# makes the block (bq, 8): bq%8==0 and 8==array dim, both legal, at 8x
# the traffic of a [T] vector — noise next to the O(T*dh) tiles.
_STAT_LANES = 8


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq, bk, causal, scale):
    qi = pl.program_id(2)
    # operands stay in the caller's dtype (bf16 feeds the MXU at full
    # rate; an f32 matmul takes several passes), products accumulate in f32
    q = q_ref[0, 0]  # [bq, dh]
    t = k_ref.shape[2]
    dh = q.shape[-1]
    nk = t // bk

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _BIG_NEG)
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
    nk_run = jnp.minimum(nk, (qi + 1) * bq // bk + 1) if causal else nk
    acc, m, l = jax.lax.fori_loop(0, nk_run, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # per-row logsumexp of scaled logits, lane-broadcast (see _STAT_LANES)
    lse_ref[0, 0] = jnp.broadcast_to(
        (m + jnp.log(l_safe))[:, None], (bq, _STAT_LANES)
    )


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, bq, bk, causal, scale,
):
    qi = pl.program_id(2)
    q = q_ref[0, 0]  # [bq, dh]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, :, 0]  # [bq] (lane-broadcast stats, col 0)
    delta = delta_ref[0, 0, :, 0]  # [bq]
    t = k_ref.shape[2]
    nk = t // bk

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _BIG_NEG)
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

    nk_run = jnp.minimum(nk, (qi + 1) * bq // bk + 1) if causal else nk
    dq = jax.lax.fori_loop(
        0, nk_run, body, jnp.zeros((bq, q.shape[-1]), jnp.float32)
    )
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, bq, bk, causal, scale,
):
    ki = pl.program_id(2)
    k = k_ref[0, 0]  # [bk, dh]
    v = v_ref[0, 0]
    t = q_ref.shape[2]
    dh = k.shape[-1]
    nq = t // bq

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * bq, bq), :]
        do = do_ref[0, 0, pl.ds(i * bq, bq), :]
        lse = lse_ref[0, 0, pl.ds(i * bq, bq), 0]
        delta = delta_ref[0, 0, pl.ds(i * bq, bq), 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _BIG_NEG)
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
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, dh]
        return dk, dv

    # causal: q tiles strictly above the diagonal band never attend this
    # key tile — start at the first row tile whose end reaches ki*bk
    i0 = (ki * bk) // bq if causal else 0
    dk, dv = jax.lax.fori_loop(
        i0, nq, body,
        (jnp.zeros((bk, dh), jnp.float32), jnp.zeros((bk, dh), jnp.float32)),
    )
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _check_blocks(t, bq, bk):
    if t % bq or t % bk:
        raise ValueError(f"seq len {t} must divide block sizes ({bq},{bk})")


def _vmem_params(what, t, dh, bq, bk, dtype, *, whole, tiles, stat_rows):
    """Compiler parameters for a kernel that holds ``whole`` [T, dh]
    blocks, ``tiles`` [max(bq, bk), dh] blocks and per-row statistics of
    ``stat_rows`` rows in VMEM, each double-buffered by the pipeline (a
    statistics block's 8 lanes pad to a 128-lane f32 tile). Raises where
    the blocks cannot fit a TensorCore's VMEM: the sequence has to be
    streamed then, which this kernel does not do."""
    item = jnp.dtype(dtype).itemsize
    need = 2 * (
        whole * t * dh * item
        + tiles * max(bq, bk) * dh * item
        + stat_rows * 128 * 4
    ) + 8 * bq * bk * 4  # the [bq, bk] f32 score-sized temporaries
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


def _flash_forward(q, k, v, *, causal, bq, bk, interpret):
    """Returns (out, lse) in the caller's [B, T, H, Dh] layout for out and
    [B, H, T, _STAT_LANES] (lane-broadcast) for lse."""
    b, t, h, dh = q.shape
    bq, bk = min(bq, t), min(bk, t)
    _check_blocks(t, bq, bk)
    scale = 1.0 / (dh**0.5)
    # [B, H, T, Dh] — contiguous K/V streams per (batch, head) program
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    grid = (b, h, t // bq)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, bq=bq, bk=bk, causal=causal, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, t, dh), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, t, dh), lambda b_, h_, i: (b_, h_, 0, 0)),
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


def _flash_backward(q, k, v, out, lse, do, *, causal, bq, bk, interpret):
    b, t, h, dh = q.shape
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
    row_q = pl.BlockSpec(
        (1, 1, bq, _STAT_LANES), lambda b_, h_, i: (b_, h_, i, 0)
    )
    row_full = pl.BlockSpec(
        (1, 1, t, _STAT_LANES), lambda b_, h_, i: (b_, h_, 0, 0)
    )

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, bq=bq, bk=bk, causal=causal, scale=scale
        ),
        grid=(b, h, t // bq),
        in_specs=[tile_q, full_seq, full_seq, tile_q, row_q, row_q],
        out_specs=tile_q,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=_vmem_params(
            "dq", t, dh, bq, bk, q.dtype, whole=2, tiles=3, stat_rows=2 * bq
        ),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, bq=bq, bk=bk, causal=causal, scale=scale
        ),
        grid=(b, h, t // bk),
        in_specs=[full_seq, tile_k, tile_k, full_seq, row_full, row_full],
        out_specs=[tile_k, tile_k],
        out_shape=[
            jax.ShapeDtypeStruct(kt.shape, k.dtype),
            jax.ShapeDtypeStruct(vt.shape, v.dtype),
        ],
        compiler_params=_vmem_params(
            "dk/dv", t, dh, bq, bk, q.dtype, whole=2, tiles=4, stat_rows=2 * t
        ),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q, k, v, causal: bool = True, bq: int = 128, bk: int = 128,
    interpret: bool = False,
):
    """Flash attention. q/k/v: [B, T, H, Dh] -> [B, T, H, Dh]."""
    out, _ = _flash_forward(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret
    )
    return out


def _fwd(q, k, v, causal, bq, bk, interpret):
    out, lse = _flash_forward(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, bq, bk, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, g, causal=causal, bq=bq, bk=bk,
        interpret=interpret,
    )


flash_attention.defvjp(_fwd, _bwd)


def make_flash_attn_fn(
    *, bq: int = 128, bk: int = 128, interpret: bool = False
):
    """Drop-in ``attn_fn`` for models/. ``interpret=True`` is for CPU tests;
    the default compiles the kernel, and a compile error propagates."""

    def attn_fn(q, k, v, *, causal: bool = True):
        return flash_attention(q, k, v, causal, bq, bk, interpret)

    return attn_fn
