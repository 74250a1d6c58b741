"""Pallas TPU fused window attention for Swin-style models.

SwinIR's hot op is (shifted-)window attention over tiny 64-token windows
(`/root/reference/Stoke-DDP.py:206-208`: window_size=8, head_dim 10). The
XLA path materializes the per-window attention probabilities
``[B*nW, heads, 64, 64]`` through HBM every layer — at the flagship bench
shape that is ~113 MB per STL in f32, by far the largest activation the
model touches. This kernel keeps scores, bias, mask and
softmax entirely in VMEM: one grid step loads a block of ``wb`` windows'
q/k/v for one head, computes softmax(q·kᵀ·scale + bias + mask)·v in f32,
and writes only the [wb, n, d] output back.

The backward recomputes the probabilities in-kernel from q/k/v (the same
no-O(n²)-residuals scheme as `pallas_attn.py`, trivially exact here since
a 64x64 score tile needs no online softmax) and emits dq/dk/dv plus the
relative-position-bias gradient, accumulated across the window grid in
the revisited output block (grid iterates windows innermost per head).

``window_attention`` is a drop-in for the einsum path in
`models/swinir.py:WindowAttention` — same math, same parameters — and is
exposed there as ``attn_impl='pallas'`` (compiled, TPU) and
``attn_impl='pallas_interpret'`` (the same kernels interpreted, which is
how CPU tests exercise identical code).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest, scale, has_mask):
    if has_mask:
        mask_ref, o_ref = rest
    else:
        (o_ref,) = rest
    q = q_ref[:, 0].astype(jnp.float32) * scale  # [wb, n, d]
    k = k_ref[:, 0].astype(jnp.float32)
    v = v_ref[:, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [wb, n, n]
    s = s + bias_ref[0].astype(jnp.float32)[None]
    if has_mask:
        s = s + mask_ref[...].astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    o = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [wb, n, d]
    o_ref[:, 0] = o.astype(o_ref.dtype)


def _bwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, *rest, scale, has_mask,
):
    if has_mask:
        mask_ref, do_ref, dq_ref, dk_ref, dv_ref, dbias_ref = rest
    else:
        do_ref, dq_ref, dk_ref, dv_ref, dbias_ref = rest
    i = pl.program_id(1)  # window-block index (innermost grid dim)
    q = q_ref[:, 0].astype(jnp.float32) * scale
    k = k_ref[:, 0].astype(jnp.float32)
    v = v_ref[:, 0].astype(jnp.float32)
    do = do_ref[:, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    s = s + bias_ref[0].astype(jnp.float32)[None]
    if has_mask:
        s = s + mask_ref[...].astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)  # [wb, n, n]

    # dv = pᵀ·do (contract query rows)
    dv = jax.lax.dot_general(
        p, do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [wb, n, d]
    dp = jax.lax.dot_general(
        do, v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [wb, n, n]
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    dk = jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [wb, n(k), d] — q already carries the scale
    dq_ref[:, 0] = dq.astype(dq_ref.dtype)
    dk_ref[:, 0] = dk.astype(dk_ref.dtype)
    dv_ref[:, 0] = dv.astype(dv_ref.dtype)

    acc = jnp.sum(ds, axis=0)  # [n, n]: bias is shared across windows

    @pl.when(i == 0)
    def _init():
        dbias_ref[0] = acc

    @pl.when(i > 0)
    def _accum():
        dbias_ref[0] += acc


def _specs(bn, h, n, d, wb, nw_mask):
    """(q/k/v tile, bias tile, mask tile) BlockSpecs for grid (h, blocks)."""
    qkv = pl.BlockSpec((wb, 1, n, d), lambda h_, i: (i, h_, 0, 0))
    bias = pl.BlockSpec((1, n, n), lambda h_, i: (h_, 0, 0))
    mask = None
    if nw_mask is not None:
        nblk = nw_mask // wb
        mask = pl.BlockSpec((wb, n, n), lambda h_, i: (i % nblk, 0, 0))
    return qkv, bias, mask


def _validate(q, bias, mask):
    """Shape contract; block-size divisibility is handled by _effective_wb."""
    bn, h, n, d = q.shape
    if bias.shape != (h, n, n):
        raise ValueError(f"bias must be [heads, n, n], got {bias.shape}")
    if mask is not None and mask.shape[-2:] != (n, n):
        raise ValueError(f"mask must be [nW, {n}, {n}], got {mask.shape}")


def _effective_wb(bn, mask, wb):
    # block size must divide both the total window count and (when a shift
    # mask is present) the per-image window count so mask indexing tiles
    wb = min(wb, bn)
    while bn % wb or (mask is not None and mask.shape[0] % wb):
        wb -= 1
    return wb


def _forward(q, k, v, bias, mask, *, wb, interpret):
    bn, h, n, d = q.shape
    _validate(q, bias, mask)
    wb = _effective_wb(bn, mask, wb)
    scale = d**-0.5
    qkv_spec, bias_spec, mask_spec = _specs(
        bn, h, n, d, wb, None if mask is None else mask.shape[0]
    )
    in_specs = [qkv_spec, qkv_spec, qkv_spec, bias_spec]
    args = [q, k, v, bias]
    if mask is not None:
        in_specs.append(mask_spec)
        args.append(mask)
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, has_mask=mask is not None
        ),
        grid=(h, bn // wb),
        in_specs=in_specs,
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*args)
    return out


def _backward_impl(q, k, v, bias, mask, do, *, wb, interpret):
    bn, h, n, d = q.shape
    _validate(q, bias, mask)
    wb = _effective_wb(bn, mask, wb)
    scale = d**-0.5
    qkv_spec, bias_spec, mask_spec = _specs(
        bn, h, n, d, wb, None if mask is None else mask.shape[0]
    )
    in_specs = [qkv_spec, qkv_spec, qkv_spec, bias_spec]
    args = [q, k, v, bias]
    if mask is not None:
        in_specs.append(mask_spec)
        args.append(mask)
    in_specs.append(qkv_spec)  # do
    args.append(do)
    dq, dk, dv, dbias = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, has_mask=mask is not None
        ),
        grid=(h, bn // wb),
        in_specs=in_specs,
        out_specs=[qkv_spec, qkv_spec, qkv_spec, bias_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((h, n, n), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def window_attention(q, k, v, bias, mask, wb: int = 16,
                     interpret: bool = False):
    """Fused softmax(q·kᵀ/√d + bias [+ mask])·v over independent windows.

    q/k/v: ``[B*nW, heads, n, d]``; bias: ``[heads, n, n]`` (the gathered
    relative-position bias); mask: ``[nW, n, n]`` additive shift mask or
    None. Returns ``[B*nW, heads, n, d]``. Gradients flow to q/k/v/bias.
    """
    return _forward(q, k, v, bias, mask, wb=wb, interpret=interpret)


def _vjp_fwd(q, k, v, bias, mask, wb, interpret):
    out = _forward(q, k, v, bias, mask, wb=wb, interpret=interpret)
    return out, (q, k, v, bias, mask)


def _vjp_bwd(wb, interpret, res, g):
    q, k, v, bias, mask = res
    dq, dk, dv, dbias = _backward_impl(
        q, k, v, bias, mask, g, wb=wb, interpret=interpret
    )
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dbias.astype(bias.dtype), dmask


window_attention.defvjp(_vjp_fwd, _vjp_bwd)


def window_attention_packed(
    q, k, v, bias, mask, pack: int = 2, wb: int = 8,
    interpret: bool = False,
):
    """Window attention with ``pack`` windows fused per attention tile.

    Packs ``pack`` consecutive windows into one virtual window of
    ``pack*n`` tokens (128 for SwinIR's 64-token windows at pack=2) with a
    block-diagonal bias and a cross-window kill mask, then runs the SAME
    Pallas kernel on the packed shapes — composing the kernel's
    VMEM-resident softmax with full-height MXU tiles for the scores/AV
    matmuls (two half-empty 64-row passes become one full 128-row pass).
    Numerically identical to ``window_attention``: softmax over the packed
    axis with -1e9 cross-window logits reproduces the per-window softmax.

    Same signature semantics as :func:`window_attention`; consecutive
    windows are packed, so when ``mask`` is given its window count must be
    divisible by ``pack`` (whole pairs stay within one image).
    """
    bn, h, n, d = q.shape
    p = pack
    if p <= 1:
        return window_attention(q, k, v, bias, mask, wb, interpret)
    if bn % p:
        raise ValueError(f"window count {bn} not divisible by pack {p}")
    if mask is not None and mask.shape[0] % p:
        raise ValueError(
            f"mask window count {mask.shape[0]} not divisible by pack {p}"
        )
    _validate(q, bias, mask)
    pn = p * n
    qp, kp, vp = (a.reshape(bn // p, p, h, n, d).transpose(0, 2, 1, 3, 4)
                  .reshape(bn // p, h, pn, d) for a in (q, k, v))

    # block-diagonal bias + cross-window kill, [h, pn, pn]; tile() puts
    # bias[i%n, j%n] everywhere, the where keeps diagonal blocks only —
    # off-diagonal logits go to -1e9 so their softmax mass is exactly 0
    row_blk = jnp.arange(pn)[:, None] // n
    col_blk = jnp.arange(pn)[None, :] // n
    same = row_blk == col_blk
    bias_p = jnp.where(
        same[None], jnp.tile(bias, (1, p, p)), jnp.float32(-1e9)
    )

    mask_p = None
    if mask is not None:
        nw = mask.shape[0]
        m = jnp.asarray(mask).reshape(nw // p, p, n, n)
        eye = jnp.eye(p, dtype=m.dtype)
        mask_p = jnp.einsum("ab,wanm->wanbm", eye, m).reshape(nw // p, pn, pn)

    out = window_attention(qp, kp, vp, bias_p, mask_p, wb, interpret)
    return (out.reshape(bn // p, h, p, n, d).transpose(0, 2, 1, 3, 4)
            .reshape(bn, h, n, d))
