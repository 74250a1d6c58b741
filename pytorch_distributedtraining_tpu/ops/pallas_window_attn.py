"""Pallas TPU window attention that reads ``qkv`` as the projection wrote it.

SwinIR's hot op is (shifted-)window attention over 64-token windows with
heads of 10 channels (`/root/reference/Stoke-DDP.py:206-208`). A TPU keeps
an array's minor dimension on 128 lanes, so every ``[.., heads, n, 10]``
array is padded 12.8 x and the transposes into and out of that layout are
copies; the ``[B*nW, heads, 64, 64]`` scores are 113 MB a layer in float32.

``window_attention_qkv`` is the whole core between the two projections:
``qkv [bn, n, 3c]`` in, ``out [bn, n, c]`` out, both in the projections'
own layouts. One grid step holds a block of windows (sized by shape against
VMEM) with all heads, and works through it a window at a time:

- a head is taken out of the ``c`` lanes by a lane mask, never by a slice
  of 10: ``(q * m_h) k^T`` contracts over all ``c`` columns, which costs the
  MXU the pass a contraction of 10 would and needs no unaligned slice;
- the heads' masked queries are stacked on the sublane axis, so one window
  is two matmuls of ``heads * n`` rows (scores, then values against the
  whole ``v``, of whose product head ``h``'s rows keep head ``h``'s columns)
  whatever the number of heads, and softmax runs once over
  ``[heads * n, n]``. On the v5e at the cells' shape this beat a loop over
  the heads with the same masks by a third and static lane slices of 10 by
  nearly half (my chip runs, PR 28: `PERF.md` §6);
- scores, bias, mask and softmax live in VMEM in float32; operands reach
  the MXU in the dtype they arrived in, where Mosaic gives float32 operands
  the single bf16 pass XLA's default precision gives the einsums.

The backward keeps no residual but its inputs: it recomputes the
probabilities from ``qkv`` (a 64 x 64 tile needs no online softmax: exact)
and returns ``d qkv [bn, n, 3c]`` and ``d bias [heads, n, n]``, the latter
summed over the window grid in a block every grid step revisits.

`models/swinir.py:WindowAttention` takes this kernel by default where the
program is lowered for a TPU and the shapes meet ``kernel_contract``;
``attn_impl='pallas_interpret'`` runs the same kernel interpreted, which is
how CPU tests exercise identical code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The scoped VMEM a kernel gets without asking, and what the pipelined
# blocks of one grid step and the temporaries of the windows in flight may
# each take.
_VMEM_DEFAULT = 16 * 2**20
_BLOCK_BUDGET = 24 * 2**20
_TEMP_BUDGET = 32 * 2**20
_MAX_WINDOWS = 64  # 16, 32 and 64 ran level at the cells' shape: no reason for more


@functools.cache
def _vmem_ceiling():
    """What a kernel may ask of a TensorCore's VMEM: the capacity of the
    TPU this process sees less a margin for Mosaic's own scratch (100 of a
    v5e's 128 MiB). A process that sees none (a CPU host lowering for a
    described chip) plans for the v5e. On a TPU with less, shapes whose
    blocks do not fit fall outside ``kernel_contract`` and the default path
    computes them by the einsums."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:  # no TPU here
        capacity = 128 * 2**20
    return capacity * 25 // 32


def _lane_bytes(rows, cols, dtype):
    """Bytes of a [rows, cols] tile in VMEM: the minor dimension pads to
    128 lanes."""
    return rows * -(-cols // 128) * 128 * jnp.dtype(dtype).itemsize


def _window_bytes(n, c, dtype, has_mask, backward):
    """VMEM one window of a block takes, double-buffered by the pipeline."""
    qkv, out = _lane_bytes(n, 3 * c, dtype), _lane_bytes(n, c, dtype)
    per = qkv + out + (_lane_bytes(n, n, jnp.float32) if has_mask else 0)
    if backward:
        per += qkv  # d qkv beside qkv; d out takes out's place
    return 2 * per


def block_windows(bn, nw_mask, n, c, dtype):
    """Windows a grid step holds: the most the backward's blocks fit into
    the budget, shrunk until it divides the window count and, under a shift
    mask, the windows of one image (so a block's masks are contiguous).
    One size serves forward and backward."""
    per = _window_bytes(n, c, dtype, nw_mask is not None, backward=True)
    wb = max(1, min(bn, _MAX_WINDOWS, _BLOCK_BUDGET // per))
    while bn % wb or (nw_mask is not None and nw_mask % wb):
        wb -= 1
    return wb


def _window_temps(n, c, heads):
    """VMEM one window in flight takes beside its blocks: a dozen
    [heads * n, n] float32 score-sized arrays and the stacked operands."""
    return 16 * _lane_bytes(heads * n, max(n, c), jnp.float32)


def windows_in_flight(wb, n, c, heads):
    """Windows an iteration of the kernel's loop works through: as many of
    8, 4, 2 as divide the block and whose temporaries fit their budget."""
    per = _window_temps(n, c, heads)
    return next(
        (u for u in (8, 4, 2) if wb % u == 0 and u * per <= _TEMP_BUDGET), 1
    )


def _vmem_need(wb, n, c, heads, dtype, has_mask, backward):
    return (
        wb * _window_bytes(n, c, dtype, has_mask, backward)
        + windows_in_flight(wb, n, c, heads) * _window_temps(n, c, heads)
        + 4 * _lane_bytes(heads * n, n, jnp.float32)  # bias, d bias
    )


def kernel_contract(bn, n, c, heads, nw_mask, dtype):
    """None where the kernel takes these shapes, else why it does not."""
    if c % heads:
        return f"c={c} does not divide into {heads} heads"
    if n % 8:
        return f"n={n} is not a multiple of 8 (the sublane tile)"
    if jnp.dtype(dtype).name not in ("float32", "bfloat16"):
        return f"dtype {jnp.dtype(dtype).name} is neither float32 nor bfloat16"
    if jnp.dtype(dtype).name == "bfloat16" and n % 16:
        return f"n={n} is not a multiple of 16 (bfloat16's sublane tile)"
    if nw_mask is not None and bn % nw_mask:
        return f"{bn} windows are not whole images of {nw_mask}"
    wb = block_windows(bn, nw_mask, n, c, dtype)
    if wb < min(8, bn if nw_mask is None else nw_mask):
        # a window count with no divisor worth a block: each grid step
        # would move a few KB
        return f"window count {bn} (mask {nw_mask}) leaves blocks of {wb}"
    need = _vmem_need(wb, n, c, heads, dtype, nw_mask is not None, True)
    if need > _vmem_ceiling():
        return (
            f"blocks of {wb} windows [{n}, {3 * c}] need about "
            f"{need / 2**20:.0f} MiB of VMEM, over the "
            f"{_vmem_ceiling() / 2**20:.0f} MiB a kernel may use"
        )
    return None


def _head_masks(heads, c):
    """[1, c] lane masks, one a head: which of the c columns are its own."""
    d = c // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    return [(lane >= h * d) & (lane < (h + 1) * d) for h in range(heads)]


def _stack_heads(x, masks):
    """[n, c] -> [heads * n, c]: head h's rows keep head h's columns."""
    zero = jnp.zeros_like(x)
    return jnp.concatenate([jnp.where(m, x, zero) for m in masks], axis=0)


def _sum_heads(x_stack, masks, n):
    """[heads * n, c] -> [n, c]: head h's columns from head h's rows."""
    out = jnp.zeros((n, x_stack.shape[1]), x_stack.dtype)
    for h, m in enumerate(masks):
        out = jnp.where(m, x_stack[h * n:(h + 1) * n], out)
    return out


_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _probabilities(q_stack, k, bias, mask, heads):
    """softmax(q k^T + bias + mask) for all heads of one window, float32,
    [heads * n, n]. ``q_stack`` carries the scale."""
    s = _dot(q_stack, k, _NT) + bias
    if mask is not None:
        s = s + jnp.concatenate([mask] * heads, axis=0)
    e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    return e / jnp.sum(e, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads", "scale"))
def _window_forward(x, bias, mask, heads, scale):
    """One window: ``qkv [n, 3c]`` -> ``out [n, c]``, float32."""
    n, c = x.shape[0], x.shape[1] // 3
    masks = _head_masks(heads, c)
    q, k, v = x[:, :c], x[:, c:2 * c], x[:, 2 * c:]
    p = _probabilities(_stack_heads(q * scale, masks), k, bias, mask, heads)
    o_stack = _dot(p.astype(v.dtype), v, _NN)  # [heads * n, c]
    return _sum_heads(o_stack, masks, n)


@functools.partial(jax.jit, static_argnames=("heads", "scale"))
def _window_backward(x, bias, mask, do, heads, scale):
    """One window: ``qkv [n, 3c]``, ``d out [n, c]`` -> ``d qkv [n, 3c]``
    and this window's ``d bias [heads * n, n]``, float32."""
    n, c = x.shape[0], x.shape[1] // 3
    masks = _head_masks(heads, c)
    q, k, v = x[:, :c], x[:, c:2 * c], x[:, 2 * c:]
    q_stack = _stack_heads(q * scale, masks)
    p = _probabilities(q_stack, k, bias, mask, heads)
    do_stack = _stack_heads(do, masks)
    dp = _dot(do_stack, v, _NT)  # [heads * n, n]
    ds = p * (dp - jnp.sum(dp * p, axis=1, keepdims=True))
    ds_op = ds.astype(x.dtype)
    dv = _dot(p.astype(x.dtype), do_stack, _TN)  # [n, c]: heads fall in place
    dk = _dot(ds_op, q_stack, _TN)  # q_stack carries the scale
    dq = _sum_heads(_dot(ds_op, k, _NN), masks, n) * scale
    return jnp.concatenate([dq, dk, dv], axis=1), ds


def _for_each_window(per, wb, window):
    """``window(w)`` for every window of the block, ``per`` an iteration:
    one window's matmuls are issued while another's softmax runs. At the
    cells' shape two an iteration took 16% off `WindowAttention`'s forward
    and 8% off its forward + backward, eight another 5% off the latter (my
    chip runs, PR 28). Mosaic's own ``unroll`` of a ``fori_loop`` is all or
    nothing; the window's arithmetic is jitted, so that an iteration of
    eight traces it once."""

    def body(i, carry):
        for j in range(per):
            window(i * per + j)
        return carry

    jax.lax.fori_loop(0, wb // per, body, 0)


def _fwd_kernel(qkv_ref, bias_ref, *rest, heads, scale, has_mask, interleave):
    mask_ref, o_ref = rest if has_mask else (None, *rest)

    def window(w):
        out = _window_forward(
            qkv_ref[w], bias_ref[...],
            None if mask_ref is None else mask_ref[w], heads, scale,
        )
        o_ref[w] = out.astype(o_ref.dtype)

    _for_each_window(interleave, qkv_ref.shape[0], window)


def _bwd_kernel(qkv_ref, bias_ref, *rest, heads, scale, has_mask, interleave):
    if has_mask:
        mask_ref, do_ref, dqkv_ref, dbias_ref = rest
    else:
        mask_ref, (do_ref, dqkv_ref, dbias_ref) = None, rest

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def window(w):
        dqkv, ds = _window_backward(
            qkv_ref[w], bias_ref[...],
            None if mask_ref is None else mask_ref[w], do_ref[w], heads, scale,
        )
        dqkv_ref[w] = dqkv.astype(dqkv_ref.dtype)
        dbias_ref[...] += ds

    _for_each_window(interleave, qkv_ref.shape[0], window)


def _validate(qkv, bias, mask):
    bn, n, c3 = qkv.shape
    if c3 % 3:
        raise ValueError(f"qkv must be [bn, n, 3c], got {qkv.shape}")
    heads = bias.shape[0]
    if bias.shape != (heads, n, n):
        raise ValueError(f"bias must be [heads, {n}, {n}], got {bias.shape}")
    if mask is not None and mask.shape[-2:] != (n, n):
        raise ValueError(f"mask must be [nW, {n}, {n}], got {mask.shape}")
    why = kernel_contract(
        bn, n, c3 // 3, heads, None if mask is None else mask.shape[0],
        qkv.dtype,
    )
    if why is not None:
        raise ValueError(f"window_attention_qkv: {why}")


@functools.partial(jax.jit, static_argnames="interpret")
def _window_attention(qkv, bias, mask, do, interpret):
    """One grid over blocks of windows: the forward where ``do`` is None,
    else the backward. Jitted so that a model's layers, which call it with
    the same shapes, share one trace of the kernel and one lowering."""
    bn, n, c3 = qkv.shape
    c, heads = c3 // 3, bias.shape[0]
    nw = None if mask is None else mask.shape[0]
    wb = block_windows(bn, nw, n, c, qkv.dtype)
    need = _vmem_need(
        wb, n, c, heads, qkv.dtype, mask is not None, do is not None
    )

    def windows(last):
        return pl.BlockSpec((wb, n, last), lambda i: (i, 0, 0))

    # [heads * n, n], the heads stacked as the kernel stacks the queries;
    # every grid step sees the same block (d bias: the sum over all windows)
    stacked = pl.BlockSpec((heads * n, n), lambda i: (0, 0))
    in_specs = [windows(c3), stacked]
    args = [qkv, bias.astype(jnp.float32).reshape(heads * n, n)]
    if mask is not None:
        per_image = nw // wb
        in_specs.append(
            pl.BlockSpec((wb, n, n), lambda i: (i % per_image, 0, 0))
        )
        args.append(mask.astype(jnp.float32))
    if do is None:
        kernel, name, out_specs = _fwd_kernel, "window_attention_fwd", windows(c)
        out_shape = jax.ShapeDtypeStruct((bn, n, c), qkv.dtype)
    else:
        in_specs.append(windows(c))
        args.append(do)
        kernel, name = _bwd_kernel, "window_attention_bwd"
        out_specs = [windows(c3), stacked]
        out_shape = [
            jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
            jax.ShapeDtypeStruct((heads * n, n), jnp.float32),
        ]
    return pl.pallas_call(
        functools.partial(
            kernel, heads=heads, scale=(c // heads) ** -0.5,
            has_mask=mask is not None,
            interleave=windows_in_flight(wb, n, c, heads),
        ),
        grid=(bn // wb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # d bias sums over the grid
            vmem_limit_bytes=min(_vmem_ceiling(), max(_VMEM_DEFAULT, 2 * need)),
        ),
        interpret=interpret,
        name=name,
    )(*args)


def _forward(qkv, bias, mask, interpret):
    _validate(qkv, bias, mask)
    return _window_attention(qkv, bias, mask, None, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def window_attention_qkv(qkv, bias, mask, interpret: bool = False):
    """softmax(q k^T / sqrt(d) + bias [+ mask]) v over independent windows,
    heads split and merged inside.

    qkv: ``[B*nW, n, 3c]`` as ``nn.Dense(3c)`` writes it (q, k, v side by
    side, each head's ``d = c / heads`` columns contiguous); bias:
    ``[heads, n, n]`` (the gathered relative-position bias); mask:
    ``[nW, n, n]`` additive shift mask or None. Returns ``[B*nW, n, c]``,
    heads concatenated as ``nn.Dense(c)`` reads them. Gradients flow to qkv
    and bias. Raises where ``kernel_contract`` refuses the shapes.
    """
    return _forward(qkv, bias, mask, interpret)


def _vjp_fwd(qkv, bias, mask, interpret):
    return _forward(qkv, bias, mask, interpret), (qkv, bias, mask)


def _vjp_bwd(interpret, res, g):
    qkv, bias, mask = res
    dqkv, dbias = _window_attention(qkv, bias, mask, g, interpret)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dqkv, dbias.reshape(bias.shape).astype(bias.dtype), dmask


window_attention_qkv.defvjp(_vjp_fwd, _vjp_bwd)
