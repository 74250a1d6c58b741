"""Sharding policies: DDP, ZeRO-1 (OSS), ZeRO-2 (ShardedDDP), ZeRO-3 (FSDP).

Each policy answers three questions about the train state
(params / optimizer state / grads):

  1. how are **params** laid out across the ZeRO axis?
  2. how is **optimizer state** laid out?
  3. are **grads** constrained to a sharded layout in-step (forcing XLA to
     emit reduce-scatter instead of all-reduce)?

A policy lays out state only. Activations are the step's: ``TrainStep``
publishes its batch layout while the loss is traced and the model pins its
residual stream to it (``spec.batch_layout`` / ``spec.pin_batch``), because
with sharded parameters and free activations GSPMD chose, on a 2x2, to keep
the weights in place and gather the batch: hidden-sharded tensor parallelism.

Aliases keep the reference's vocabulary: ``OSS`` == ZeRO-1
(`/root/reference/Fairscale-DDP.py:86`), ``ShardedDDP`` == ZeRO-2
(`Fairscale-DDP.py:89`), ``FSDP`` == ZeRO-3 (Stoke's ``fairscale_fsdp``
flag surface). ``policy_from_flags`` maps Stoke's flag combination
(`Stoke-DDP.py:248-250`) to a policy.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .spec import leaf_spec, shard_axis, tree_specs


@dataclass(frozen=True)
class Policy:
    """Base sharding policy (DDP semantics: everything replicated)."""

    shard_params: bool = False
    shard_opt_state: bool = False
    shard_grads: bool = False
    min_shard_size: int = 1024
    # Activation rematerialization in backward (FSDP memory). Accepts a bool
    # (True == "full") or a named policy from parallel.remat:
    # "none" | "full" | "dots" | "names" | "offload".
    remat: bool | str = False
    # DeepSpeed optimizer-offload twin (`Stoke-DDP.py:18` config surface):
    # optimizer state lives in pinned host memory, streamed to the chip for
    # the update. Falls back to HBM on backends without host-placement
    # support (see spec.host_offload_supported).
    offload_opt_state: bool = False
    # DeepSpeed offload_param twin: params resident in pinned host memory,
    # streamed to the chip per step (fwd/bwd read them, the update writes
    # back host-side). Same fallback rule as offload_opt_state.
    offload_params: bool = False

    def __post_init__(self):
        from .remat import resolve_remat

        resolve_remat(self.remat)  # fail at construction, not first step

    @property
    def remat_policy(self) -> str:
        """Canonical remat policy name ("none"/"full"/"dots"/...)."""
        from .remat import resolve_remat

        return resolve_remat(self.remat)

    # -- spec builders (trees of PartitionSpec) ----------------------------

    def params_specs(self, params, mesh: Mesh):
        ax = shard_axis(mesh)
        if not self.shard_params or ax is None:
            return jax.tree.map(lambda _: P(), params)
        return tree_specs(params, ax, mesh.shape[ax], self.min_shard_size)

    def opt_specs(self, opt_state, mesh: Mesh):
        ax = shard_axis(mesh)
        if not self.shard_opt_state or ax is None:
            return jax.tree.map(lambda _: P(), opt_state)
        return tree_specs(opt_state, ax, mesh.shape[ax], self.min_shard_size)

    def grads_specs(self, params, mesh: Mesh):
        ax = shard_axis(mesh)
        if not self.shard_grads or ax is None:
            return None  # no constraint: XLA free-chooses (all-reduce)
        return tree_specs(params, ax, mesh.shape[ax], self.min_shard_size)

    @property
    def name(self) -> str:
        return type(self).__name__


@dataclass(frozen=True)
class DDP(Policy):
    """Replicated params+state, grad all-reduce — the DDP twin
    (`Stoke-DDP.py:248`; C++ Reducer subsumed by one XLA all-reduce)."""


@dataclass(frozen=True)
class ZeRO1(Policy):
    """Optimizer-state sharding — Fairscale OSS twin (`Fairscale-DDP.py:86`,
    ``fairscale_oss=True`` `Stoke-DDP.py:249`)."""

    shard_opt_state: bool = True


@dataclass(frozen=True)
class ZeRO2(ZeRO1):
    """+ grad reduce-scatter — ShardedDDP twin (`Fairscale-DDP.py:89`,
    ``fairscale_sddp=True`` `Stoke-DDP.py:250`)."""

    shard_grads: bool = True


@dataclass(frozen=True)
class ZeRO3(ZeRO2):
    """+ param sharding — FSDP twin (Stoke ``fairscale_fsdp`` surface;
    BASELINE.json config 4). ``remat=True`` trades FLOPs for HBM like
    FSDP's activation checkpointing. The parameters are gathered for their
    use only because the step pins activations to the batch layout
    (``spec.pin_batch``); left free, GSPMD gathers the batch instead."""

    shard_params: bool = True


# reference vocabulary
OSS = ZeRO1
ShardedDDP = ZeRO2
FSDP = ZeRO3


def policy_from_flags(
    distributed: str | None = None,
    fairscale_oss: bool = False,
    fairscale_sddp: bool = False,
    fairscale_fsdp: bool = False,
    **kwargs,
) -> Policy:
    """Map Stoke's flag surface (`Stoke-DDP.py:248-250`) onto a policy."""
    if fairscale_fsdp:
        return ZeRO3(**kwargs)
    if fairscale_sddp:
        return ZeRO2(**kwargs)
    if fairscale_oss:
        return ZeRO1(**kwargs)
    return DDP(**kwargs)
