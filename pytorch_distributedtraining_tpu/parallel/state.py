"""TrainState: the complete training-run state as one sharded pytree.

Holds what the reference scatters across objects — model params (DDP
module), optimizer+state (OSS), AMP scaler state, step counter, RNG — in a
single `flax.struct` pytree so the whole update is one compiled function and
checkpointing is one tree serialization (SURVEY §5 checkpoint gap: the
reference never saves optimizer/RNG state; this does).

``create_train_state`` initializes **directly into the policy's sharded
layout**: the init runs under jit with sharded ``out_shardings``, so a
ZeRO-3 model never materializes unsharded anywhere — params larger than one
device's HBM work from step zero.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import logging

from ..observe import trace as telemetry
from ..precision import ScalerState
from .policy import Policy
from .spec import host_offload_supported, tree_shardings

logger = logging.getLogger(__name__)


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray  # i32 scalar
    params: Any
    opt_state: Any
    model_state: Any  # mutable collections (e.g. BN stats); {} if none
    rng: jnp.ndarray  # PRNG key, folded per step (dropout etc.)
    scaler: ScalerState | None = None  # fp16 loss-scale state, None for bf16/f32


def create_train_state(
    *,
    model=None,
    sample_input=None,
    init_fn: Callable | None = None,
    tx,
    mesh: Mesh,
    policy: Policy,
    rng=None,
    scaler_state: ScalerState | None = None,
    init_kwargs: dict | None = None,
) -> tuple[TrainState, TrainState]:
    """Build a sharded TrainState; returns ``(state, sharding_tree)``.

    Either pass a Flax ``model`` + ``sample_input`` (``model.init`` is used)
    or a custom ``init_fn(rng) -> (params, model_state)``. In the start-up
    ledger this is ``state.create``: init's two traces (shapes, then the
    program), its executable and its dispatch; the run itself is
    asynchronous and ends wherever the caller next waits.
    """
    with telemetry.span("state.create", "startup", policy=policy.name):
        return _create_train_state(
            model, sample_input, init_fn, tx, mesh, policy, rng,
            scaler_state, init_kwargs,
        )


def _create_train_state(
    model, sample_input, init_fn, tx, mesh, policy, rng, scaler_state,
    init_kwargs,
):
    rng = jax.random.PRNGKey(0) if rng is None else rng

    def build(rng):
        if init_fn is not None:
            params, model_state = init_fn(rng)
        else:
            variables = model.init(rng, sample_input, **(init_kwargs or {}))
            variables = dict(variables)
            params = variables.pop("params")
            model_state = variables  # batch_stats etc.
        opt_state = tx.init(params)
        return TrainState(
            step=jnp.int32(0),
            params=params,
            opt_state=opt_state,
            model_state=model_state,
            rng=rng,
            scaler=scaler_state,
        )

    shapes = jax.eval_shape(build, rng)
    specs = TrainState(
        step=P(),
        params=policy.params_specs(shapes.params, mesh),
        opt_state=policy.opt_specs(shapes.opt_state, mesh),
        model_state=jax.tree.map(lambda _: P(), shapes.model_state),
        rng=P(),
        scaler=jax.tree.map(lambda _: P(), shapes.scaler),
    )
    shardings = tree_shardings(specs, mesh)

    def offload(field: str, what: str):
        """Place one TrainState field in pinned host memory, or fall back
        to device memory with a warning on backends without host
        placement (one rule for every offload knob)."""
        nonlocal shardings
        if host_offload_supported(mesh):
            shardings = shardings.replace(**{
                field: tree_shardings(
                    getattr(specs, field), mesh, memory_kind="pinned_host"
                )
            })
        else:
            logger.warning(
                "%s host offload requested but the %s backend has no "
                "host-placement support; keeping %s in device memory",
                what, mesh.devices.flat[0].platform, what,
            )

    if policy.offload_opt_state:
        offload("opt_state", "optimizer-state")
    if policy.offload_params:
        offload("params", "parameter")
    state = jax.jit(build, out_shardings=shardings)(rng)
    return state, shardings
