"""FusedAdamW (flat fused update) == per-leaf optax chain, step for step.

The fused path exists for TPU step-time (the per-leaf chain is one small
fusion per parameter leaf); these
tests pin its numerics to the chain it replaces (`optim.adamw`), its
GradScaler overflow-skip semantics, and its replicated-layout-only guard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    TrainStep,
    ZeRO2,
    create_train_state,
)
from pytorch_distributedtraining_tpu.precision import DynamicLossScaler
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def _make(mesh, tx, scaler=None, accum=1):
    model = Net(upscale_factor=2)

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        out = model.apply({"params": params}, lr_img)
        return mse_loss(out, hr_img), {}

    scaler_state = scaler.init() if scaler else None
    state, shardings = create_train_state(
        init_fn=lambda rng: (
            model.init(rng, jnp.zeros((1, 8, 8, 3)))["params"],
            {},
        ),
        tx=tx,
        mesh=mesh,
        policy=DDP(),
        scaler_state=scaler_state,
    )
    step = TrainStep(
        loss_fn, tx, mesh, DDP(),
        grad_accum_steps=accum, loss_scaler=scaler,
        state_shardings=shardings, donate=False,
    )
    return state, step


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    hr = rng.random((n, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(n, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    return lr, hr


def test_fused_matches_chain_5_steps(mesh8):
    batch = _batch(16)
    kw = dict(lr=3e-3, clip_grad_norm=0.1, weight_decay=0.01)
    s_c, step_c = _make(mesh8, optim.adamw(**kw))
    s_f, step_f = _make(mesh8, optim.FusedAdamW(**kw))
    for _ in range(5):
        s_c, m_c = step_c(s_c, batch)
        s_f, m_f = step_f(s_f, batch)
        np.testing.assert_allclose(
            float(m_c["loss"]), float(m_f["loss"]), rtol=2e-5
        )
        # pre-clip global norm metric agrees (flat vs per-leaf reduction)
        np.testing.assert_allclose(
            float(m_c["grad_norm"]), float(m_f["grad_norm"]), rtol=2e-5
        )
    for a, b in zip(jax.tree.leaves(s_c.params), jax.tree.leaves(s_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fused_matches_chain_with_schedule_and_accum(mesh8):
    batch = _batch(16, seed=3)
    sched = optim.onecycle(max_lr=3e-3, total_steps=50)
    s_c, step_c = _make(mesh8, optim.adamw(lr=sched), accum=2)
    s_f, step_f = _make(mesh8, optim.FusedAdamW(lr=sched), accum=2)
    for _ in range(4):
        s_c, _ = step_c(s_c, batch)
        s_f, _ = step_f(s_f, batch)
    for a, b in zip(jax.tree.leaves(s_c.params), jax.tree.leaves(s_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fused_scaler_skips_overflow(mesh8):
    scaler = DynamicLossScaler(init_scale=2.0**14, growth_interval=3)
    state, step = _make(mesh8, optim.FusedAdamW(lr=0.01), scaler=scaler)
    state, m = step(state, _batch(16))
    assert float(m["loss_scale"]) == 2.0**14
    lr_img, hr = _batch(16)
    bad = (lr_img, np.full_like(hr, np.inf))
    p_before = np.asarray(jax.tree.leaves(state.params)[0])
    count_before = int(state.opt_state.count)
    state, m = step(state, bad)
    assert float(m["loss_scale"]) == 2.0**13
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(state.params)[0]), p_before
    )
    # GradScaler parity: the skipped step advances no optimizer state
    assert int(state.opt_state.count) == count_before


def test_fused_lr_factor_freezes_update(mesh8):
    state, step = _make(mesh8, optim.FusedAdamW(lr=0.01))
    p0 = np.asarray(jax.tree.leaves(state.params)[0])
    s2, _ = step(state, _batch(16), lr_factor=0.0)
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(s2.params)[0]), p0)


def test_fused_rejects_grad_sharded_policy(mesh8):
    tx = optim.FusedAdamW(lr=0.01)

    def loss_fn(params, batch, rng, model_state):
        return 0.0, {}

    with pytest.raises(ValueError, match="ZeRO-1"):
        TrainStep(loss_fn, tx, mesh8, ZeRO2())


def test_fused_zero1_shards_flat_moments_and_matches_ddp(devices8):
    """ZeRO-1 + FusedAdamW: the flat [N] mu/nu shard over dp (the
    DeepSpeed flat-partition scheme as shardings) and numerics match the
    replicated fused run."""
    from pytorch_distributedtraining_tpu.parallel import ZeRO1
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )

    batch = _batch(16)
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    mesh1 = make_mesh(MeshSpec(dp=1), devices=devices8[:1])

    def build(mesh_, policy):
        model = Net(upscale_factor=2)
        tx = optim.FusedAdamW(lr=3e-3, clip_grad_norm=0.1)

        def loss_fn(params, b, rng, model_state):
            lr_img, hr_img = b
            out = model.apply({"params": params}, lr_img)
            from pytorch_distributedtraining_tpu.losses import mse_loss

            return mse_loss(out, hr_img), {}

        state, shardings = create_train_state(
            init_fn=lambda r: (
                model.init(r, jnp.zeros((1, 8, 8, 3)))["params"],
                {},
            ),
            tx=tx, mesh=mesh_, policy=policy,
        )
        step = TrainStep(
            loss_fn, tx, mesh_, policy,
            state_shardings=shardings, donate=False,
        )
        return state, step

    s_z, step_z = build(mesh, ZeRO1(min_shard_size=1))
    s_d, step_d = build(mesh1, DDP())
    # the flat moments are actually sharded: each device holds 1/8
    mu = s_z.opt_state.mu
    assert mu.addressable_shards[0].data.shape[0] == mu.shape[0] // 8
    for _ in range(3):
        s_z, m_z = step_z(s_z, batch)
        s_d, m_d = step_d(s_d, batch)
        np.testing.assert_allclose(
            float(m_z["loss"]), float(m_d["loss"]), rtol=2e-5
        )
    for a, b in zip(
        jax.tree.leaves(s_z.params), jax.tree.leaves(s_d.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_fused_update_wire_dtype_bounds_error():
    """The bf16 update wire (OSS broadcast_fp16 twin) stays within bf16
    rounding of the full-precision update."""
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(16)(x)

    model = M()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    g = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1, params)
    from jax.flatten_util import ravel_pytree

    gflat = ravel_pytree(g)[0].astype(jnp.float32)
    tx = optim.FusedAdamW(lr=1e-2)
    tx_w = optim.FusedAdamW(lr=1e-2, update_wire_dtype=jnp.bfloat16)
    p1, _, _ = tx.apply(gflat, tx.init(params), params)
    p2, _, _ = tx_w.apply(gflat, tx_w.init(params), params)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        # close to the exact update, but not bit-identical (the wire
        # narrowing must actually be in effect)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))
    )
