"""Named collectives over mesh axes — the framework's communication layer.

TPU-native replacement for the c10d collective surface the reference
exercises (`/root/reference/` §: all-reduce from DDP grad hooks and loss sync
`Stoke-DDP.py:86`; reduce-to-owner from ShardedDDP `Fairscale-DDP.py:89`;
fp16-compressed param broadcast from OSS `Stoke-DDP.py:197-199`; barrier at
init). Instead of hand-written ring algorithms over NCCL/gloo, these are thin
names over XLA collective HLOs (`psum`, `all_gather`, `psum_scatter`,
`ppermute`) which XLA:TPU's C++ runtime schedules onto ICI/DCN.

Two levels:

- **In-jit (SPMD)**: :func:`all_reduce` … :func:`permute` take an
  ``axis_name`` and must run inside `shard_map` (or any ctx where the axis
  is bound). These compile to single HLO collectives.
- **Host-level**: :func:`host_all_gather` / :func:`host_broadcast` /
  :func:`barrier` coordinate *processes* outside jit (checkpoint
  consolidation, rendezvous sanity) via `jax.experimental.multihost_utils`.

.. warning:: **Gradients inside shard_map are already all-reduced.**
   Under jax's varying-manual-axes (vma) tracking, differentiating a
   per-shard loss w.r.t. a *replicated* (unvarying) input auto-inserts the
   cross-shard ``psum`` (the transpose of replication is reduction). A
   per-shard-mean loss therefore yields ``axis_size × global_mean`` grads;
   scale by ``1/axis_size`` — do NOT apply :func:`tree_all_reduce` on top
   (it double-counts). The DDP engine in ``parallel/`` instead uses the
   jit+`NamedSharding` path, where XLA's SPMD partitioner inserts exactly
   one all-reduce and global-mean losses come out right with no manual
   scaling. Explicit collectives here are for shard_map interiors: ring
   attention, ZeRO ownership layouts, custom fusions.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map  # noqa: F401  (shard_map re-exported)

# -- in-jit SPMD collectives -------------------------------------------------

_REDUCERS = {
    "sum": lax.psum,
    "mean": lax.pmean,
    "max": lax.pmax,
    "min": lax.pmin,
}


def all_reduce(x, axis_name: str = "dp", op: str = "sum"):
    """All-reduce over a mesh axis. Twin of c10d all_reduce / DDP grad sync."""
    try:
        return _REDUCERS[op](x, axis_name)
    except KeyError:
        raise ValueError(f"op must be one of {sorted(_REDUCERS)}, got {op!r}")


def all_gather(x, axis_name: str = "dp", axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every member of the mesh axis.

    ``tiled=True`` concatenates (c10d semantics: [n*s, ...]); ``tiled=False``
    stacks a new leading dim ([n, s, ...]).
    """
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str = "dp", scatter_axis: int = 0, op: str = "sum"):
    """Reduce across the axis, scatter result shards along ``scatter_axis``.

    The ShardedDDP "reduce each grad to its owning rank" pattern
    (`Fairscale-DDP.py:89`) expressed as one fused HLO instead of per-bucket
    point-to-point reduces.
    """
    out = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=True)
    if op == "mean":
        out = out / lax.axis_size(axis_name)
    elif op != "sum":
        raise ValueError(f"reduce_scatter supports sum|mean, got {op!r}")
    return out


def broadcast(x, axis_name: str = "dp", src: int = 0):
    """Broadcast ``src``'s shard to every member of the axis.

    Twin of OSS's post-step param fan-out (`Fairscale-DDP.py:86` step
    semantics). Implemented as a masked psum — one collective, no gather of
    non-src data.
    """
    idx = lax.axis_index(axis_name)
    # select (not multiply-by-mask) so NaN/Inf in non-src shards — e.g. stale
    # non-owner param state in the OSS fan-out — cannot leak through 0*NaN
    return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), axis_name)


def compressed_broadcast(x, axis_name: str = "dp", src: int = 0, dtype=jnp.bfloat16):
    """Broadcast through a lower-precision wire format.

    Parity with ``FairscaleOSSConfig(broadcast_fp16=True)``
    (`Stoke-DDP.py:197-199`): the payload crosses the interconnect in
    ``dtype`` (default bf16 — the TPU-native choice) and is cast back.
    """
    orig = x.dtype
    return broadcast(x.astype(dtype), axis_name, src).astype(orig)


def permute(x, axis_name: str, perm: list[tuple[int, int]]):
    """Point-to-point ring shift: ``perm`` is [(src, dst), ...] pairs.

    Building block for ring attention / pipeline transfers.
    """
    return lax.ppermute(x, axis_name, perm)


def ring_shift(x, axis_name: str, offset: int = 1):
    """Shift shards by ``offset`` around the axis ring (wraps)."""
    n = int(lax.axis_size(axis_name))
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str = "dp"):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str = "dp"):
    return lax.axis_size(axis_name)


# -- host-level (outside jit) ------------------------------------------------


def barrier(name: str = "barrier", timeout_s: float = 1800.0) -> None:
    """Block until every process reaches this point.

    Twin of ``dist.barrier()`` — a PROCESS barrier, like torch's. Rides
    the coordination service (pure gRPC) when the distributed client is
    up, so it is safe even before the first device collective (Gloo's
    context bootstrap has a fixed ~30 s timeout that pre-collective
    process skew can blow; see ``runtime.dist.coordination_barrier``).
    Falls back to a device-collective sync when no client exists (e.g.
    single-process multi-device test harnesses) — note that fallback has
    no timeout mechanism, so ``timeout_s`` only bounds the
    coordination-service path. No-op single-process.
    """
    if jax.process_count() == 1:
        return
    from ..runtime import dist as _dist

    if _dist.has_coordination_client():
        # default matches torch dist.barrier's 30-min patience (a rank can
        # legitimately spend minutes in a cold compile before arriving)
        _dist.coordination_barrier(name, timeout_s=timeout_s)
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def host_all_gather(x):
    """Gather a host-local (numpy/pytree) value from all processes."""
    if jax.process_count() == 1:
        return jax.tree.map(lambda a: np.asarray(a)[None], x)
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x)


def host_broadcast(x, src: int = 0):
    """Broadcast a host-local value from process ``src`` to all processes."""
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(x, is_source=jax.process_index() == src)


def sync_scalar(x, op: str = "mean"):
    """Cross-device scalar sync for reporting — `detach_and_sync_loss` twin
    (`Stoke-DDP.py:86`).

    Accepts a replicated/sharded jax scalar OR a per-device array; returns a
    python float. Outside jit: a fully-replicated scalar (the common case —
    the compiled step already psum'd it) is just pulled to host; otherwise we
    mean over shards. Blocks the host; for hot loops use
    ``sync_scalar_device`` and convert at log points only.
    """
    return float(sync_scalar_device(x, op))


def sync_scalar_device(x, op: str = "mean"):
    """Like ``sync_scalar`` but stays on device (returns a 0-d jax array).

    The reference's ``detach_and_sync_loss`` returns a *tensor*
    (`Stoke-DDP.py:86`) that the driver accumulates and only ``float()``s
    at log points — so the loop never blocks the host per step. This is
    the faithful twin; ``float()``/formatting of the result syncs.
    """
    reducers = {"mean": jnp.mean, "sum": jnp.sum}
    if op not in reducers:
        raise ValueError(f"op must be one of {sorted(reducers)}, got {op!r}")
    arr = jnp.asarray(x)
    if arr.ndim == 0:
        return arr
    return reducers[op](arr)


def tree_all_reduce(tree, axis_name: str = "dp", op: str = "mean"):
    """All-reduce every leaf of a pytree (grad-sync twin of DDP's bucketed
    all-reduce — XLA fuses/schedules, no bucket loop; cf. C++ Reducer,
    `torch/nn/parallel/distributed.py:1298`)."""
    fn = functools.partial(all_reduce, axis_name=axis_name, op=op)
    return jax.tree.map(fn, tree)


# -- two-level (hierarchical) forms ------------------------------------------
#
# On a hybrid ICI x DCN mesh (runtime.mesh.make_hybrid_mesh) a flat ring
# over the data axes ships FULL gradient payloads across the slow DCN
# links. The two-level form reduce-scatters within the slice first (fast
# ICI, each device ends up owning 1/ici_size of the payload), all-reduces
# only that owned shard across slices (the DCN hop carries 1/ici_size of
# the bytes), then all-gathers within the slice. Same result, DCN volume
# divided by the within-slice axis size. parallel/hierarchy.py builds the
# bucketed grad-sync strategy on these primitives.


def hier_all_reduce(
    x, *, ici_axis: str | None, dcn_axis: str, op: str = "sum"
):
    """Two-level all-reduce for shard_map interiors.

    ``reduce-scatter(ici) -> all-reduce(dcn) -> all-gather(ici)`` on a
    flattened view of ``x`` (the scatter needs an even split, so the
    payload is zero-padded to a multiple of the ICI axis size and the
    pad is stripped after the gather). ``ici_axis=None`` — a pure-DCN
    mesh, nothing to scatter within — degenerates to the flat
    single-axis reduce, which IS the hierarchical form at ici size 1.
    """
    if op not in ("sum", "mean"):
        raise ValueError(f"hier_all_reduce supports sum|mean, got {op!r}")
    if ici_axis is None:
        out = lax.psum(x, dcn_axis)
        if op == "mean":
            out = out / lax.axis_size(dcn_axis)
        return out
    n_ici = int(lax.axis_size(ici_axis))
    flat = x.reshape(-1)
    pad = (-flat.size) % n_ici
    flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, dcn_axis)  # 1/ici_size payload on the DCN hop
    full = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    out = full.reshape(x.shape)
    if op == "mean":
        out = out / (n_ici * lax.axis_size(dcn_axis))
    return out


def tree_hier_all_reduce(
    tree, *, ici_axis: str | None, dcn_axis: str, op: str = "mean"
):
    """Two-level :func:`tree_all_reduce`: every leaf through
    :func:`hier_all_reduce`. Leaf-at-a-time (unbucketed) — the bucketed
    strategy that coalesces small leaves lives in parallel/hierarchy.py."""
    fn = functools.partial(
        hier_all_reduce, ici_axis=ici_axis, dcn_axis=dcn_axis, op=op
    )
    return jax.tree.map(fn, tree)
