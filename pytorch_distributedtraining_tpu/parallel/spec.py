"""Per-leaf sharding rules: how a tensor is split over the ZeRO axis.

Fairscale shards by partitioning the *parameter list* across ranks (each
rank owns whole tensors). TPU-native we shard *within* tensors along one
dimension — XLA then slices/gathers with zero-copy tiling and the layout is
identical on every rank, which keeps checkpoints portable across world
sizes (a known Fairscale OSS pain point).
"""

from __future__ import annotations

import contextlib
import contextvars
import math

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.mesh import data_axes


def shard_axis(mesh: Mesh) -> str | None:
    """The mesh axis ZeRO state shards over: "fsdp" if sized, else "dp"."""
    if mesh.shape.get("fsdp", 1) > 1:
        return "fsdp"
    if mesh.shape.get("dp", 1) > 1:
        return "dp"
    return None


def leaf_spec(shape, axis_name: str, axis_size: int, min_size: int = 1024) -> P:
    """PartitionSpec sharding the largest divisible dim of ``shape``.

    Leaves smaller than ``min_size`` elements (biases, norm scales) stay
    replicated — sharding them buys nothing and costs a gather each.
    """
    if axis_size <= 1 or int(np.prod(shape, dtype=np.int64)) < min_size:
        return P()
    divisible = [i for i, d in enumerate(shape) if d % axis_size == 0 and d > 0]
    if not divisible:
        return P()
    dim = max(divisible, key=lambda i: shape[i])
    spec = [None] * len(shape)
    spec[dim] = axis_name
    return P(*spec)


def tree_specs(tree, axis_name: str | None, axis_size: int, min_size: int = 1024):
    """Map :func:`leaf_spec` over a pytree of arrays/ShapeDtypeStructs."""
    if axis_name is None or axis_size <= 1:
        return jax.tree.map(lambda _: P(), tree)
    return jax.tree.map(
        lambda x: leaf_spec(x.shape, axis_name, axis_size, min_size), tree
    )


def tree_shardings(tree_of_specs, mesh: Mesh, *, memory_kind: str | None = None):
    """Bind a tree of PartitionSpecs to ``mesh``.

    ``memory_kind="pinned_host"`` places the leaves in host memory (the
    DeepSpeed optimizer-offload twin): XLA:TPU streams them over PCIe
    during the update instead of holding them in HBM.
    """
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s, memory_kind=memory_kind),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def host_offload_supported(mesh: Mesh) -> bool:
    """Can this backend run jitted programs with pinned_host operands?

    TPU (and GPU) register the device-placement custom call; the CPU
    backend does not (as of jax 0.9: ``annotate_device_placement`` is
    unimplemented for Host) — so offload configs fall back to device
    memory there rather than failing multichip dryruns and tests.
    Probe-compiles a trivial program once per backend platform.
    """
    platform = mesh.devices.flat[0].platform
    if platform in _HOST_OFFLOAD_SUPPORT:
        return _HOST_OFFLOAD_SUPPORT[platform]
    try:
        s = NamedSharding(mesh, P(), memory_kind="pinned_host")
        import jax.numpy as jnp

        jax.jit(lambda x: x * 2, in_shardings=s, out_shardings=s).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)
        ).compile()
        ok = True
    except Exception:
        ok = False
    _HOST_OFFLOAD_SUPPORT[platform] = ok
    return ok


_HOST_OFFLOAD_SUPPORT: dict = {}


def stream_to_device(tree, shardings):
    """Inside-jit: copy pinned-host leaves into device memory.

    Offloaded state (``Policy.offload_opt_state`` / ``offload_params``)
    lives in pinned host memory between steps; TPU programs cannot mix
    host- and device-placed operands in one op, so every program that
    computes on possibly-offloaded trees streams them in first (an async
    DMA XLA overlaps with compute). Device-resident leaves pass through
    untouched; ``shardings=None`` is a no-op. The matching write-back is
    the program's ``out_shardings``, which keep the host memory kind.
    """
    if shardings is None:
        return tree

    def one(x, s):
        if getattr(s, "memory_kind", None) == "pinned_host":
            return jax.device_put(x, s.with_memory_kind("device"))
        return x

    return jax.tree.map(one, tree, shardings)


def constrain(tree, tree_of_specs, mesh: Mesh):
    """`with_sharding_constraint` applied leaf-wise (in-jit).

    Specs are bound to ``mesh`` here — raw PartitionSpecs would require an
    ambient `jax.set_mesh` context.
    """
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree,
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# The mesh whose data axes the batch of the step being traced is split over.
_BATCH_LAYOUT = contextvars.ContextVar("batch_layout", default=None)


@contextlib.contextmanager
def batch_layout(mesh: Mesh):
    """Publish, while a step is traced, that its batch is split over
    ``mesh``'s data axes: what :func:`pin_batch` holds activations to.

    Whoever owns the mesh says it (``TrainStep`` around its loss function,
    the stoke facade around every application of its model); a model never
    asks jax for an ambient mesh.
    """
    token = _BATCH_LAYOUT.set(mesh)
    try:
        yield
    finally:
        _BATCH_LAYOUT.reset(token)


def published_batch_mesh() -> Mesh | None:
    """The mesh of the step being traced, or None where no step has said:
    for code that has to place a computation itself (a kernel the
    partitioner cannot split, wrapped in ``shard_map`` over the data axes)."""
    return _BATCH_LAYOUT.get()


def pin_batch(x):
    """Hold ``x`` ([batch, ...]) to the published batch layout (in-jit).

    Only the leading dimension is constrained, to the mesh's data axes;
    every other dimension stays the partitioner's, so the hidden and
    sequence splits of "tp" / "sp" meshes survive. The identity when no
    layout is published (serving, eval, a bare ``model.apply``), when the
    data axes hold one device, or when they do not divide the batch.

    Why it exists: with sharded parameters and free activations GSPMD may
    keep the weights where they are and gather the batch instead (ZeRO-3
    on a 2x2 became hidden-sharded tensor parallelism, every chip computing
    every sequence's attention); pinned, the parameters move.
    """
    mesh = _BATCH_LAYOUT.get()
    if mesh is None:
        return x
    axes = data_axes(mesh)
    n = math.prod(mesh.shape[a] for a in axes)
    if n <= 1 or x.shape[0] % n:
        return x
    spec = P(axes, *([P.UNCONSTRAINED] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
