"""Device-mesh construction: the substrate every parallelism engine rides.

The reference's parallelism is a flat ranks-in-a-process-group world
(`/root/reference/Fairscale-DDP.py:27`; DDP/OSS/ShardedDDP all address "rank
r of world W"). TPU-native, the equivalent structure is a named
`jax.sharding.Mesh` whose axes map onto the ICI torus (and DCN across pods);
parallelism engines then become PartitionSpec rules over these axes and XLA
lowers the collectives onto the right links.

Canonical axis names used across the framework:

    "dp"    data parallel (DDP twin; grads psum over it)
    "fsdp"  sharded-data-parallel axis (OSS/ShardedDDP/FSDP state sharding)
    "tp"    tensor parallel
    "sp"    sequence/context parallel (ring attention)
    "ep"    expert parallel

A plain DDP run is ``make_mesh(dp=N)``; ZeRO engines reuse the SAME physical
axis under the "fsdp" name via :func:`MeshSpec.zero` so state shards over the
data-parallel group exactly like Fairscale partitions optimizer state over
the DDP world (`Fairscale-DDP.py:86`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401  (re-export)

from ..observe import trace as telemetry

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "tp", "ep")

# id(mesh) -> (mesh, name of its DCN/slice axis). Populated by
# make_hybrid_mesh; queried through slice_axis() so callers never
# string-match "dp". NOTE: jax interns Mesh — constructing an equal
# (devices, axis_names) layout returns the SAME object — so the
# registration is effectively per physical layout, which is the right
# semantics: the slice structure is a property of the devices, not of
# which builder you called. Consumers that must distinguish "this step
# MEANT to be hierarchical" (e.g. the dcn-flat-ring rule) gate on a
# step-level claim, not on this registry alone. The stored mesh ref
# keeps the id live; bounded FIFO (meshes are tiny, tests build
# hundreds).
_SLICE_AXES: dict = {}
_SLICE_AXES_CAP = 128


def _register_slice_axis(mesh: "Mesh", axis: str) -> None:
    while len(_SLICE_AXES) >= _SLICE_AXES_CAP:
        _SLICE_AXES.pop(next(iter(_SLICE_AXES)))
    _SLICE_AXES[id(mesh)] = (mesh, axis)


def slice_axis(mesh: "Mesh") -> str | None:
    """The mesh axis that crosses slice (DCN) boundaries, or None.

    Only hybrid meshes built by :func:`make_hybrid_mesh` with more than
    one slice have a slice axis; a single-slice mesh (every link is ICI)
    returns None. This is the one sanctioned way to ask "which axis is
    the slow hop" — parallel/hierarchy.py, the dcn-flat-ring graftcheck
    rule and the facade all route through it instead of assuming "dp".
    Because jax interns Mesh, an equal layout rebuilt by hand IS the
    registered object and inherits the slice axis — the slice structure
    belongs to the physical devices, not to the builder call.
    """
    entry = _SLICE_AXES.get(id(mesh))
    return entry[1] if entry is not None else None


def ici_data_axes(mesh: "Mesh") -> tuple:
    """Data axes that stay within a slice (the fast, within-ICI hops)."""
    dcn = slice_axis(mesh)
    return tuple(a for a in data_axes(mesh) if a != dcn)


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Axes of size 1 are kept (named, free to resize)."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    axis_order: tuple = field(default=AXIS_ORDER)

    @property
    def size(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp * self.ep * self.pp

    def shape(self) -> dict:
        return {name: getattr(self, name) for name in self.axis_order}

    @staticmethod
    def ddp(n: int | None = None) -> "MeshSpec":
        """All devices on the data axis — the DDP twin layout."""
        return MeshSpec(dp=n if n is not None else jax.device_count())

    @staticmethod
    def zero(n: int | None = None) -> "MeshSpec":
        """All devices on the sharded-DP axis — OSS/ShardedDDP/FSDP layout.

        Fairscale shards state over the same ranks DDP replicates over
        (`Fairscale-DDP.py:86-89`); here that is one physical axis named
        "fsdp" so PartitionSpecs can shard state AND batches over it.
        """
        return MeshSpec(fsdp=n if n is not None else jax.device_count())


def make_mesh(spec: MeshSpec | None = None, *, devices=None, **axes) -> Mesh:
    """Build a Mesh from a spec or kwargs: ``make_mesh(dp=4, tp=2)``.

    Uses ``mesh_utils.create_device_mesh`` so the axis order maps well onto
    the ICI torus (innermost axes get the fastest links); falls back to a
    plain reshape for virtual/CPU devices. Its span in the start-up ledger
    (``mesh.make``) holds the backend's start where this is the first call
    that asks for devices.
    """
    if spec is None:
        spec = MeshSpec(**axes)
    with telemetry.span("mesh.make", "startup", **spec.shape()):
        devices = list(jax.devices()) if devices is None else list(devices)
        if spec.size != len(devices):
            raise ValueError(
                f"MeshSpec wants {spec.size} devices ({spec.shape()}), "
                f"got {len(devices)}"
            )
        shape = tuple(spec.shape().values())
        names = tuple(spec.shape().keys())
        if devices[0].platform == "tpu":
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        else:
            dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, names)


def make_hybrid_mesh(
    spec: MeshSpec | None = None,
    *,
    dcn_dp: int | None = None,
    devices=None,
    **axes,
) -> Mesh:
    """Multi-slice mesh: data parallelism over DCN, everything else on ICI.

    The scaling recipe for TPU multi-pod ("ride ICI, not DCN"): put ONLY the
    gradient all-reduce on the slow inter-slice DCN links — its volume is
    amortized over a whole step — and keep the chatty model axes
    (fsdp/tp/sp/ep) inside a slice on the ICI torus. ``dcn_dp`` is the
    number of slices (defaults to ``jax.process_count()`` under one process
    per slice); the remaining ``spec`` axes must multiply to the per-slice
    device count.

    Uses ``mesh_utils.create_hybrid_device_mesh`` on real TPU so device
    order respects slice boundaries; on CPU/virtual devices a plain reshape
    stands in (processes are contiguous in ``jax.devices()`` order).
    """
    import dataclasses

    if spec is None:
        spec = MeshSpec(**axes)
    devices = list(jax.devices()) if devices is None else list(devices)
    if dcn_dp is None:
        dcn_dp = max(1, jax.process_count())
    if spec.dp != 1:
        raise ValueError(
            "make_hybrid_mesh owns the dp axis (it becomes the DCN axis); "
            "size the per-slice axes (fsdp/tp/sp/ep/pp) in the spec instead"
        )
    if dcn_dp * spec.size != len(devices):
        raise ValueError(
            f"dcn_dp={dcn_dp} x per-slice {spec.size} != {len(devices)} devices"
        )
    full = dataclasses.replace(spec, dp=dcn_dp)
    if dcn_dp == 1:
        # single slice: no DCN axis to place — delegate to the torus-aware
        # builder (naive reshape would lose ICI ring ordering on TPU)
        return make_mesh(full, devices=devices)

    names = tuple(full.shape().keys())
    if devices[0].platform == "tpu":
        ici_shape = tuple(1 if n == "dp" else getattr(spec, n) for n in names)
        dcn_shape = tuple(dcn_dp if n == "dp" else 1 for n in names)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
        mesh = Mesh(dev_array, names)
        _register_slice_axis(mesh, "dp")
        return mesh
    # reshape with the DCN axis OUTERMOST (slices are contiguous in device
    # order), then move it into the "dp" slot — a straight reshape would
    # hand contiguous slices to whatever axis precedes dp (e.g. pp)
    rest = tuple(getattr(spec, n) for n in names if n != "dp")
    arr = np.asarray(devices).reshape((dcn_dp,) + rest)
    arr = np.moveaxis(arr, 0, names.index("dp"))
    mesh = Mesh(arr, names)
    _register_slice_axis(mesh, "dp")
    return mesh


def best_mesh(n: int | None = None, *, zero: bool = False) -> Mesh:
    """The sensible default mesh: everything on one data axis."""
    spec = MeshSpec.zero(n) if zero else MeshSpec.ddp(n)
    return make_mesh(spec)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def current_mesh() -> Mesh | None:
    """The mesh of the innermost active `with mesh:` context, if any."""
    try:  # no public accessor for the active mesh context yet
        phys = jax._src.mesh.thread_resources.env.physical_mesh
        return None if phys.empty else phys
    except AttributeError:  # pragma: no cover - jax internals moved
        return None


def data_axes(mesh: Mesh) -> tuple:
    """Axes a global batch is sharded over.

    Only dp/fsdp — NOT "pp": pipeline stages hold different layers and must
    see the same microbatches, so the batch is never split over pp. On a
    mesh with no data axis at all (e.g. pure-pp) the batch is replicated.
    """
    axes = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    if axes:
        return axes
    return ("dp",) if "dp" in mesh.axis_names else ()


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec for a [batch, ...] array on this mesh."""
    return P(data_axes(mesh))


def stacked_batch_spec(mesh: Mesh) -> P:
    """PartitionSpec for a ``[k, batch, ...]`` stacked-window array.

    The scan axis is replicated (every device runs all k microbatch
    steps); everything after it shards like the single-step batch. This
    is the layout ``MultiStep`` expects and ``stack_windows`` over a
    ``DataLoader.device_iter`` produces.
    """
    return P(None, *batch_spec(mesh))


def divisors_check(n: int, by: int, what: str) -> None:
    if n % by:
        raise ValueError(f"{what}={n} not divisible by mesh axis size {by}")


def balanced_factors(n: int) -> tuple:
    """Split n into (a, b), a*b == n and a <= b, as square as possible."""
    a = int(math.isqrt(n))
    while n % a:
        a -= 1
    return a, n // a
