"""Grouped matmul over the experts a chip holds: rows sorted by group, one
weight matrix a group, static shapes, work only for the rows that exist.

``x`` is a buffer ``[M, K]`` whose first ``sum(group_sizes)`` rows are
sorted by group (``group_sizes[g]`` rows of group ``g``, in order); rows
past them belong to no group. ``w`` is ``[G, K, N]``. Row ``i`` of the
result is ``x[i] @ w[group of i]``. **Rows past the groups are not
written**, forward or backward (the gradient of ``x`` too): they hold
whatever the buffer held, so a caller masks them out BEFORE they meet a
product (``where(valid, y, 0) * weight``, never ``where(valid, y * weight,
0)``: a product's gradient multiplies a zero cotangent by the unwritten
row), or never reads them. Nor need they be written on the way IN: the
rows of ``x`` and of the result's cotangent past the groups may hold
anything, NaN included (the kernels mask a tile's rows by its group before
the product, ``tgmm`` too). The one caller, ``models/held_experts.py``,
does both since PR 33: its dispatch writes only the rows that landed into
an allocated buffer, and its combine reads only those.

The kernel is the Pallas ``megablox.gmm`` that jax ships (with its backward:
``gmm`` for the rows' gradient, ``tgmm`` for the weights'). Its grid runs
over the row tiles that hold rows, not over the buffer: a buffer sized for
the worst case costs nothing where it is empty. Measured against
``jax.lax.ragged_dot`` at the GLM-4.7-Flash expert layer's shapes on a v5e
(``benchmarks/glm4_kernels.py``; PERF.md, PR 27): forward + backward 1.7 ms
against 3.1 ms, so ``ragged_dot`` is not wired in.
"""

from __future__ import annotations

import jax.numpy as jnp

ROW_TILE = 512  # rows a grid step; a group's last tile is partly empty


def _tile(dim: int) -> int:
    """The largest MXU-friendly tile that divides ``dim``, else all of it."""
    return next((t for t in (1024, 512, 256, 128) if dim % t == 0), dim)


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """``x`` [M, K] (M a multiple of ``ROW_TILE``, or under it), ``w``
    [G, K, N], ``group_sizes`` [G] int32 -> [M, N] in ``x.dtype``, products
    accumulated in float32. ``interpret=True`` is for CPU tests."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = x.shape
    tm = min(ROW_TILE, m)
    if m % tm:
        raise ValueError(
            f"grouped_matmul: {m} buffer rows are not a multiple of the "
            f"row tile {tm}: pad the buffer"
        )
    return gmm(
        x, w.astype(x.dtype), group_sizes.astype(jnp.int32), x.dtype,
        (tm, _tile(k), _tile(w.shape[-1])), None, None, False, interpret,
    )
