"""int8-wire gradient all-reduce with error feedback (EQuARX direction)."""

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    CompressedGradStep,
    TrainStep,
    create_train_state,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def _loss_fn(model):
    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        return mse_loss(model.apply({"params": params}, lr_img), hr_img), {}

    return loss_fn


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    hr = rng.random((n, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(n, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    return lr, hr


def _build(devices8, compressed: bool):
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)
    loss_fn = _loss_fn(model)
    state, shardings = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=DDP(),
    )
    if not compressed:
        return state, TrainStep(
            loss_fn, tx, mesh, DDP(), state_shardings=shardings, donate=False
        )
    step = CompressedGradStep(loss_fn, tx, mesh)
    state = state.replace(
        model_state={"grad_residual": step.init_residuals(state.params)}
    )
    return state, step


def test_compressed_grads_converge(devices8):
    state, step = _build(devices8, compressed=True)
    batch = _batch(16)
    losses = []
    with step.mesh:
        for _ in range(15):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < 0.3 * losses[0], losses


def test_compressed_tracks_exact_ddp(devices8):
    """int8 wire + error feedback stays close to the exact-DDP trajectory."""
    batch = _batch(16)
    s_c, step_c = _build(devices8, compressed=True)
    s_e, step_e = _build(devices8, compressed=False)
    with step_c.mesh:
        for _ in range(10):
            s_c, m_c = step_c(s_c, batch)
            s_e, m_e = step_e(s_e, batch)
    # same init + same data: trajectories agree to quantization tolerance
    np.testing.assert_allclose(
        float(m_c["loss"]), float(m_e["loss"]), rtol=0.15
    )
    # error-feedback residuals are live (quantization actually happened),
    # carry a true per-shard layout, and survive materialization round trips
    res = jax.tree.leaves(s_c.model_state["grad_residual"])
    assert any(float(jnp.max(jnp.abs(r))) > 0 for r in res)
    r0 = res[0]
    assert r0.shape[0] == 8  # leading dp axis
    assert r0.sharding.spec[0] == "dp"
    host = np.asarray(r0)  # materialize: per-shard values must be distinct
    assert host.shape == r0.shape


def test_quantize_roundtrip_unbiased_over_steps():
    """Repeated quantization with error feedback recovers the true mean:
    the cumulative dequantized sum approaches sum(g) as residual carries."""
    from pytorch_distributedtraining_tpu.parallel.compressed import _quantize

    def run(axis_name="dp"):
        g = jnp.asarray(
            np.random.default_rng(3).normal(size=(64,)).astype(np.float32)
        ) * 1e-3

        def body(carry, _):
            r, acc = carry
            q, scale, r = _quantize(g, r, axis_name)
            return (r, acc + q.astype(jnp.float32) * scale), None

        (r, acc), _ = jax.lax.scan(
            body, (jnp.zeros_like(g), jnp.zeros_like(g)), None, length=20
        )
        return acc / 20.0, g

    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    from jax.sharding import PartitionSpec as P

    from pytorch_distributedtraining_tpu.ops.collectives import shard_map

    acc, g = jax.jit(shard_map(
        lambda: run(), mesh=mesh, in_specs=(), out_specs=(P(), P()),
        check_vma=False,
    ))()
    np.testing.assert_allclose(np.asarray(acc), np.asarray(g), atol=1e-6)


def test_compressed_zero2_scatter_matches_exact_sgd(devices8):
    """The ZeRO-2 composition — int8 psum_scatter to
    the owning shard — must take the same SGD step as exact DDP, with the
    opt state actually sharded (reduce-to-owner, not all-reduce)."""
    import optax

    from pytorch_distributedtraining_tpu.parallel import ZeRO2

    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optax.sgd(learning_rate=0.5)
    loss_fn = _loss_fn(model)
    batch = _batch(16)
    policy = ZeRO2(min_shard_size=1)

    state_e, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=DDP(),
    )
    step_e = TrainStep(
        loss_fn, tx, mesh, DDP(), state_shardings=sh, donate=False
    )
    state_c, _ = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step_c = CompressedGradStep(loss_fn, tx, mesh, policy)
    with mesh:
        state_e, _ = step_e(state_e, batch)
        state_c, m = step_c(state_c, batch)
    assert np.isfinite(float(m["loss"]))
    for a, b in zip(
        jax.tree.leaves(state_e.params), jax.tree.leaves(state_c.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg="compressed ZeRO2 step diverges from exact DDP step",
        )


def test_compressed_zero2_converges_with_sharded_opt(devices8):
    """ZeRO-2 composition end to end: adamw converges and the optimizer
    moments live sharded (the OSS memory win survives the int8 wire)."""
    from pytorch_distributedtraining_tpu.parallel import ZeRO2

    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)
    policy = ZeRO2(min_shard_size=1)
    state, _ = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = CompressedGradStep(_loss_fn(model), tx, mesh, policy)
    batch = _batch(16)
    losses = []
    with mesh:
        for _ in range(15):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < 0.3 * losses[0], losses
    # some adam moment leaf is genuinely sharded over dp
    sharded = [
        x for x in jax.tree.leaves(state.opt_state)
        if hasattr(x, "sharding")
        and x.ndim > 0
        and x.addressable_shards[0].data.shape != x.shape
    ]
    assert sharded, "ZeRO2 opt state ended up fully replicated"


def test_compressed_hybrid_dcn_mesh(devices8):
    """Hybrid ICI x DCN composition: fsdp reduces in f32 on the fast
    links, only the dp (DCN) hop is quantized — converges and tracks the
    exact-DDP loss."""
    from pytorch_distributedtraining_tpu.parallel import ZeRO2
    from pytorch_distributedtraining_tpu.runtime.mesh import make_hybrid_mesh

    mesh = make_hybrid_mesh(MeshSpec(fsdp=4), dcn_dp=2, devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)
    policy = ZeRO2(min_shard_size=1)
    state, _ = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = CompressedGradStep(_loss_fn(model), tx, mesh, policy)
    assert step.ici_axis == "fsdp" and step.n_data_shards == 8
    batch = _batch(16)
    losses = []
    with mesh:
        for _ in range(15):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < 0.3 * losses[0], losses
    # residuals carry the hybrid [dp, fsdp, ...] per-shard layout
    res = jax.tree.leaves(state.model_state["grad_residual"])
    assert res[0].shape[:2] == (2, 4), res[0].shape
    assert tuple(res[0].sharding.spec[:2]) == ("dp", "fsdp")


def test_compressed_rejects_zero3_and_bad_axis(devices8):
    from pytorch_distributedtraining_tpu.parallel import ZeRO3
    import pytest

    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=1e-3)
    with pytest.raises(ValueError, match="ZeRO3"):
        CompressedGradStep(_loss_fn(model), tx, mesh, ZeRO3())
    with pytest.raises(ValueError, match="not a data axis"):
        CompressedGradStep(_loss_fn(model), tx, mesh, axis_name="tp")


def test_compressed_grad_scale_matches_exact_sgd(devices8):
    """SGD is scale-sensitive: one compressed step must move params by the
    same amount as exact DDP (catches any n-fold reduction-scale error)."""
    import optax

    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optax.sgd(learning_rate=0.5)
    loss_fn = _loss_fn(model)
    batch = _batch(16)

    state_e, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=DDP(),
    )
    step_e = TrainStep(
        loss_fn, tx, mesh, DDP(), state_shardings=sh, donate=False
    )
    step_c = CompressedGradStep(loss_fn, tx, mesh)
    state_c = state_e.replace(
        model_state={"grad_residual": step_c.init_residuals(state_e.params)}
    )
    with mesh:
        state_e, _ = step_e(state_e, batch)
        state_c, _ = step_c(state_c, batch)
    for a, b in zip(
        jax.tree.leaves(state_e.params), jax.tree.leaves(state_c.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg="compressed SGD step diverges from exact DDP step",
        )
