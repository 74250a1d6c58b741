"""The gated short convolution of a conv/attention hybrid's conv layers:
``bcx = [B | C | X]``, the in-projection's output ``[Bt, T, 3D]``, and the
depthwise causal taps ``w [D, L]`` give::

    z = B * X
    y_t = sum_k w[:, k] z_{t - (L - 1) + k}     (zeros before t = 0)
    out = C * y

``w[:, L - 1]`` multiplies step ``t`` itself: torch's ``Conv1d(D, D, L,
groups=D, padding=L - 1)`` weight ``[D, 1, L]`` squeezed, its first ``T``
outputs kept. Products and sums in float32, ``out`` in ``bcx``'s dtype.

The operator does almost no arithmetic and moves ``4 x [Bt, T, D]`` forward
and ``8 x`` backward, so it is bound by memory. On a TPU it is a Pallas pair
(``short_conv_kernel``, a ``custom_vjp``): the forward's grid is ``(Bt, D /
128)``, each program the whole ``T`` of one 128-lane block, reading ``B``,
``C`` and ``X`` where they lie in ``bcx`` by lane-block index maps and
writing ``out`` once; the backward reads ``d out``, ``B``, ``C`` and ``X``
once, writes ``d[B | C | X]`` into one ``[Bt, T, 3D]`` array (the layout the
in-projection's backward takes) and the taps' gradient as float32 partials a
program, summed outside. The steps before ``t = 0`` (and after ``T - 1`` in
the backward) are rows of zeros in a VMEM scratch beside the sequence, read
at a shift of a row or two.

Elsewhere, or where the whole ``T`` of a lane block does not fit VMEM, the
``jnp`` form runs: ``L`` shifted products summed, differentiated by XLA
(``short_conv_jnp``). ``short_conv`` chooses by platform and footprint only:
a program lowered for a TPU holds the kernels (``jax.lax.platform_dependent``),
one lowered for any other platform the ``jnp`` form. Each trace leaves the
instant ``short_conv.path`` (``path`` ``"kernel"`` or ``"jnp"``, ``reason``,
``b``, ``t``, ``d``, ``taps``) in the telemetry ring, and all of it (the
kernels and their glue, not the projections) is under
``jax.named_scope("short_conv")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_PAD = 8  # rows of zeros beside the sequence in the shift scratch: taps <= 8
# what a kernel may ask of a TensorCore's VMEM, and what it gets unasked
# (``ops/pallas_attn.py``'s numbers)
_VMEM_CEILING = 100 * 2**20
_VMEM_DEFAULT = 16 * 2**20


def _split(bcx):
    d = bcx.shape[-1] // 3
    return bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]


def short_conv_jnp(bcx, w):
    """The ``jnp`` form: ``L`` shifted products of ``z = B * X`` summed, in
    float32, then gated by ``C``."""
    b, c, x = (a.astype(jnp.float32) for a in _split(bcx))
    taps = w.shape[1]
    t = bcx.shape[1]
    z = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = sum(w[:, k] * z[:, k:k + t] for k in range(taps))
    return (c * y).astype(bcx.dtype)


def _vmem_need(t, item, backward):
    """Bytes of VMEM one program asks for: its double-buffered blocks of
    ``[T, 128]`` (4 forward, 5 backward), the shift scratch, the backward's
    three results held over the grid's last axis, and the float32
    ``[T, 128]`` temporaries of the arithmetic."""
    tile = t * _LANES
    blocks = (5 if backward else 4) * 2 * tile * item
    scratch = (t + 2 * _PAD) * _LANES * 4 + (3 * tile * item if backward else 0)
    return blocks + scratch + (10 if backward else 6) * tile * 4


def conv_path(t, d, taps, dtype):
    """``(path, reason)``: ``"kernel"`` where the Pallas pair takes the
    shape (a program lowered for a TPU then holds it), else ``"jnp"``."""
    if d % _LANES:
        return "jnp", f"D = {d} is no multiple of {_LANES} lanes"
    if taps > _PAD:
        return "jnp", f"{taps} taps, more than the {_PAD} rows of zeros kept"
    need = _vmem_need(t, jnp.dtype(dtype).itemsize, backward=True)
    if need > _VMEM_CEILING:
        return "jnp", (
            f"the whole T = {t} of a lane block needs {need / 2**20:.0f} MiB "
            f"of VMEM, over the {_VMEM_CEILING / 2**20:.0f} MiB a kernel may use"
        )
    return "kernel", (
        "by platform: a TPU lowering holds the Pallas pair "
        f"({need / 2**20:.0f} MiB of VMEM a program), any other the jnp form"
    )


def _params(t, item, backward, semantics):
    need = _vmem_need(t, item, backward)
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=min(_VMEM_CEILING, max(_VMEM_DEFAULT, 2 * need)),
    )


def _taps_rows(w):
    """``w [D, L]`` -> float32 ``[8, D]``: tap ``k`` in row ``k``, the rest
    zeros (a whole sublane tile for the kernels' block of it)."""
    rows = jnp.zeros((_PAD, w.shape[0]), jnp.float32)
    return rows.at[: w.shape[1]].set(w.T.astype(jnp.float32))


def _causal(pad_ref, z, taps_ref, taps):
    """``sum_k w[k] z_{t - (L - 1) + k}``: ``z`` is written below ``_PAD``
    rows of zeros and read back a row or two early."""
    t = z.shape[0]
    pad_ref[0:_PAD, :] = jnp.zeros((_PAD, _LANES), jnp.float32)
    pad_ref[_PAD:_PAD + t, :] = z
    w = taps_ref[...]
    y = w[taps - 1:taps] * z
    for k in range(taps - 1):
        start = _PAD - (taps - 1) + k
        y = y + w[k:k + 1] * pad_ref[start:start + t, :]
    return y


def _fwd_kernel(b_ref, c_ref, x_ref, taps_ref, o_ref, pad_ref, *, taps):
    z = b_ref[0].astype(jnp.float32) * x_ref[0].astype(jnp.float32)
    y = _causal(pad_ref, z, taps_ref, taps)
    o_ref[0] = (c_ref[0].astype(jnp.float32) * y).astype(o_ref.dtype)


def _bwd_kernel(
    g_ref, b_ref, c_ref, x_ref, taps_ref, d_ref, dw_ref, pad_ref, held_ref,
    *, taps,
):
    """At the grid's last axis' first step: everything, into ``held_ref``
    (``dB``, ``dC``, ``dX``) and ``dw_ref``; at each step ``k`` the ``k``-th
    third of ``d[B | C | X]`` leaves for its place."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        bb = b_ref[0].astype(jnp.float32)
        xx = x_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        t = bb.shape[0]
        z = bb * xx
        y = _causal(pad_ref, z, taps_ref, taps)
        dy = g * c_ref[0].astype(jnp.float32)
        # the taps' gradient: sum over t of dy_t z_{t - (L - 1) + k}; the
        # shifted z are still in the scratch
        dw_ref[0] = jnp.zeros((_PAD, _LANES), jnp.float32)
        dw_ref[0, taps - 1:taps, :] = jnp.sum(dy * z, 0, keepdims=True)
        for k in range(taps - 1):
            start = _PAD - (taps - 1) + k
            dw_ref[0, k:k + 1, :] = jnp.sum(
                dy * pad_ref[start:start + t, :], 0, keepdims=True
            )
        # dz_t = sum_k w[k] dy_{t + (L - 1) - k}: dy above _PAD rows of zeros
        pad_ref[0:t, :] = dy
        pad_ref[t:t + _PAD, :] = jnp.zeros((_PAD, _LANES), jnp.float32)
        w = taps_ref[...]
        dz = w[taps - 1:taps] * dy
        for k in range(taps - 1):
            shift = taps - 1 - k
            dz = dz + w[k:k + 1] * pad_ref[shift:shift + t, :]
        held_ref[0] = (dz * xx).astype(held_ref.dtype)
        held_ref[1] = (g * y).astype(held_ref.dtype)
        held_ref[2] = (dz * bb).astype(held_ref.dtype)

    d_ref[0] = held_ref[pl.program_id(2)]


def _blocks(d):
    """Index maps of ``B``, ``C`` and ``X``'s 128-lane block ``j`` of
    ``bcx [Bt, T, 3D]`` on a grid whose first two axes are (batch, j)."""
    nd = d // _LANES
    return [
        (lambda third: lambda b, j, *_: (b, 0, third * nd + j))(third)
        for third in range(3)
    ]


def _forward(bcx, w, interpret):
    bt, t, d3 = bcx.shape
    d, taps = d3 // 3, w.shape[1]
    tile = (1, t, _LANES)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        grid=(bt, d // _LANES),
        in_specs=[
            *(pl.BlockSpec(tile, index) for index in _blocks(d)),
            pl.BlockSpec((_PAD, _LANES), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec(tile, lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bt, t, d), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((t + 2 * _PAD, _LANES), jnp.float32)],
        compiler_params=_params(
            t, bcx.dtype.itemsize, False, ("parallel", "parallel")
        ),
        interpret=interpret,
        name="short_conv_fwd",
    )(bcx, bcx, bcx, _taps_rows(w))


def _backward(bcx, w, g, interpret):
    bt, t, d3 = bcx.shape
    d, taps = d3 // 3, w.shape[1]
    nd = d // _LANES
    tile = (1, t, _LANES)
    d_bcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=(bt, nd, 3),
        in_specs=[
            pl.BlockSpec(tile, lambda b, j, k: (b, 0, j)),
            *(pl.BlockSpec(tile, index) for index in _blocks(d)),
            pl.BlockSpec((_PAD, _LANES), lambda b, j, k: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec(tile, lambda b, j, k: (b, 0, k * nd + j)),
            pl.BlockSpec((1, _PAD, _LANES), lambda b, j, k: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
            jax.ShapeDtypeStruct((bt, _PAD, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t + 2 * _PAD, _LANES), jnp.float32),
            pltpu.VMEM((3, t, _LANES), bcx.dtype),
        ],
        compiler_params=_params(
            t, bcx.dtype.itemsize, True, ("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="short_conv_bwd",
    )(g, bcx, bcx, bcx, _taps_rows(w))
    return d_bcx, jnp.sum(dw, 0)[:taps].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def short_conv_kernel(bcx, w, interpret: bool = False):
    """The Pallas pair; ``interpret=True`` for CPU tests."""
    return _forward(bcx, w, interpret)


def _kernel_fwd(bcx, w, interpret):
    return _forward(bcx, w, interpret), (bcx, w)


def _kernel_bwd(interpret, res, g):
    bcx, w = res
    return _backward(bcx, w, g.astype(bcx.dtype), interpret)


short_conv_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def short_conv(bcx, w):
    """``bcx [Bt, T, 3D]``, ``w [D, L]`` -> ``C * causal_conv(B * X)``
    ``[Bt, T, D]`` in ``bcx``'s dtype (see the module's docstring)."""
    from ..observe import trace

    bt, t, d3 = bcx.shape
    taps = w.shape[1]
    with jax.named_scope("short_conv"):
        path, reason = conv_path(t, d3 // 3, taps, bcx.dtype)
        trace.instant(
            "short_conv.path", path=path, reason=reason, b=bt, t=t,
            d=d3 // 3, taps=taps,
        )
        if path == "jnp":
            return short_conv_jnp(bcx, w)
        return jax.lax.platform_dependent(
            bcx, w, tpu=short_conv_kernel, default=short_conv_jnp
        )
