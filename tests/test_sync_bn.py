"""Sync-BN parity: BN under a dp mesh == single-device big-batch BN.

The reference requests sync-BN via ``DDPConfig(convert_to_sync_batch_norm=
True)`` (`/root/reference/Stoke-DDP.py:190-193`), whose torch contract
(`torch/nn/modules/batchnorm.py:890` convert_sync_batchnorm) is: batch
statistics are computed over the GLOBAL batch across all ranks, not each
rank's local slice. In this framework that contract is met structurally —
under global-view ``jit`` a dp-sharded batch is one logical array, so
``nn.BatchNorm``'s mean/var reductions are global and XLA inserts the
collective (see ``models/resnet.py`` docstring). These tests *prove* it
rather than argue it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.models.resnet import BasicBlock, ResNet
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    TrainStep,
    create_train_state,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def _tiny_resnet():
    # one stage is enough: BN cross-replica stats are per-layer semantics
    return ResNet(
        stage_sizes=(1,),
        block_cls=BasicBlock,
        num_classes=4,
        num_filters=8,
        small_inputs=True,
    )


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 4, size=(n,))
    return x, y


def _loss_and_stats(model, params, stats, batch):
    x, y = batch
    logits, mutated = model.apply(
        {"params": params, "batch_stats": stats}, x, train=True,
        mutable=["batch_stats"],
    )
    onehot = jax.nn.one_hot(y, logits.shape[-1])
    loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1))
    return loss, mutated["batch_stats"]


def test_bn_stats_and_grads_match_single_device(devices8):
    """dp=8 sharded batch vs 1 device, same global batch: identical BN
    batch_stats and identical grads (the convert_sync_batchnorm contract)."""
    model = _tiny_resnet()
    batch = _batch(16)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    params, stats = variables["params"], variables["batch_stats"]

    grad_fn = jax.jit(
        jax.grad(
            lambda p, s, b: _loss_and_stats(model, p, s, b),
            has_aux=True,
        )
    )

    # single device, full batch
    g1, stats1 = grad_fn(params, stats, batch)

    # dp=8: batch sharded over the mesh's data axis
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    shard = NamedSharding(mesh, P("dp"))
    x8 = jax.device_put(batch[0], shard)
    y8 = jax.device_put(batch[1], shard)
    with mesh:
        g8, stats8 = grad_fn(params, stats, (x8, y8))

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        stats1, stats8,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        g1, g8,
    )


def test_global_stats_differ_from_local_shard_stats():
    """Control: stats over one rank's local half differ from global stats —
    i.e. the parity above is meaningful, not vacuous."""
    model = _tiny_resnet()
    x, y = _batch(16, seed=1)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    params, stats = variables["params"], variables["batch_stats"]

    _, stats_global = _loss_and_stats(model, params, stats, (x, y))
    _, stats_local = _loss_and_stats(model, params, stats, (x[:8], y[:8]))
    diffs = jax.tree.leaves(
        jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), stats_global, stats_local
        )
    )
    assert max(diffs) > 1e-4, "local-half stats should differ from global"


def test_bn_training_through_trainstep_on_dp_mesh(devices8):
    """End-to-end: TrainStep threads mutated batch_stats through
    TrainState.model_state on a dp mesh and the running stats move."""
    model = _tiny_resnet()
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    tx = optim.adamw(lr=1e-3)

    def loss_fn(params, batch, rng, model_state):
        loss, new_stats = _loss_and_stats(
            model, params, model_state["batch_stats"], batch
        )
        return loss, {"model_state": {"batch_stats": new_stats}}

    def init_fn(rng):
        v = model.init(rng, jnp.zeros((1, 8, 8, 3)))
        return v["params"], {"batch_stats": v["batch_stats"]}

    state, shardings = create_train_state(
        init_fn=init_fn, tx=tx, mesh=mesh, policy=DDP()
    )
    step = TrainStep(
        loss_fn, tx, mesh, DDP(), state_shardings=shardings, donate=False
    )
    before = jax.tree.map(np.asarray, state.model_state)
    batch = _batch(16)
    with mesh:
        for _ in range(3):
            state, metrics = step(state, batch)
    after = state.model_state
    moved = jax.tree.leaves(
        jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))),
            before, after,
        )
    )
    assert max(moved) > 1e-6, "running BN stats did not update through the step"
    assert np.isfinite(float(metrics["loss"]))
