"""MultiStep: K steps per dispatch == K sequential step() calls.

The wrapper exists for dispatch-bound hosts/links;
its contract is that rolling steps into one `lax.scan` program changes
dispatch count only — math, rng folding, and state evolution identical.
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
    ZeRO2,
    MultiStep,
    TrainStep,
    create_train_state,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

K, B = 4, 16


def _build(devices, policy, **step_kw):
    mesh = make_mesh(
        MeshSpec.zero(8) if policy.shard_opt_state else MeshSpec.ddp(8),
        devices=devices,
    )
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3, clip_grad_norm=1.0)

    def loss_fn(params, batch, rng, ms):
        lo, hr = batch
        return mse_loss(model.apply({"params": params}, lo), hr), {}

    state, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = TrainStep(
        loss_fn, tx, mesh, policy,
        state_shardings=sh, donate=False, **step_kw,
    )
    return mesh, state, step


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    hr = rng.random((n, B, 16, 16, 3)).astype(np.float32)
    lo = hr.reshape(n, B, 8, 2, 8, 2, 3).mean(axis=(3, 5))
    return lo, hr


@pytest.mark.parametrize("policy", [DDP(), ZeRO2(min_shard_size=1)])
def test_multi_matches_sequential(devices8, policy):
    lo, hr = _batches(2 * K)

    # sequential reference
    mesh, state_a, step = _build(devices8, policy)
    with mesh:
        for i in range(2 * K):
            state_a, m_a = step(state_a, (lo[i], hr[i]))

    # two K-windows through MultiStep
    mesh, state_b, step_b = _build(devices8, policy)
    multi = MultiStep(step_b, k=K)
    for w in range(2):
        sl = slice(w * K, (w + 1) * K)
        state_b, m_b = multi(state_b, (lo[sl], hr[sl]))

    assert int(state_b.step) == int(state_a.step) == 2 * K
    assert m_b["loss"].shape == (K,)
    np.testing.assert_allclose(
        float(m_b["loss"][-1]), float(m_a["loss"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
        )


def test_window_mismatch_raises(devices8):
    mesh, state, step = _build(devices8, DDP())
    multi = MultiStep(step, k=K)
    lo, hr = _batches(K - 1)
    with pytest.raises(ValueError, match="window"):
        multi(state, (lo, hr))


def test_grad_accum_composes(devices8):
    """scan-in-scan: microbatch accumulation inside each scanned step."""
    lo, hr = _batches(K)
    mesh, state, step = _build(devices8, DDP(), grad_accum_steps=2)
    multi = MultiStep(step, k=K)
    state, m = multi(state, (lo, hr))
    assert int(state.step) == K
    assert np.isfinite(float(m["loss"][-1]))


def test_stack_windows_feeds_multi(devices8):
    from pytorch_distributedtraining_tpu.data import stack_windows

    lo, hr = _batches(2 * K + 1)  # odd tail must be dropped
    batches = [(lo[i], hr[i]) for i in range(2 * K + 1)]
    mesh, state, step = _build(devices8, DDP())
    multi = MultiStep(step, k=K)
    n = 0
    for stacked in stack_windows(batches, K):
        assert stacked[0].shape == (K, B, 8, 8, 3)
        state, m = multi(state, stacked)
        n += 1
    assert n == 2 and int(state.step) == 2 * K


def test_stack_windows_device_batches(devices8):
    """Mesh-equipped loader batches are jax Arrays: stacking must stay an
    XLA op (no host round-trip / non-addressable crash), and the stacks
    must feed MultiStep."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributedtraining_tpu.data import (
        DataLoader,
        SyntheticSRDataset,
        stack_windows,
    )

    mesh = make_mesh(MeshSpec.ddp(8), devices=devices8)
    ds = SyntheticSRDataset(n=32, lr_size=8, scale=2)
    loader = DataLoader(
        ds, batch_size=16, mesh=mesh, spec=P("dp"), drop_last=True
    )
    mesh2, state, step = _build(devices8, DDP())
    multi = MultiStep(step, k=2)
    n = 0
    for stacked in stack_windows(loader, 2):
        assert hasattr(stacked[0], "sharding"), "left device unexpectedly"
        state, m = multi(state, stacked)
        n += 1
    assert n == 1 and int(state.step) == 2


def test_tune_multi_step_k(devices8):
    """The tuner measures each candidate k on the live backend, returns
    finite rates for all arms, a best_k among the candidates, and a
    still-trainable advanced state (don't guess whether K-per-dispatch
    pays — the r4 on-chip anomaly showed guessing wrong costs 90x)."""
    from pytorch_distributedtraining_tpu.parallel import tune_multi_step_k

    mesh, state, step = _build(devices8, DDP())
    lo, hr = _batches(1)
    batch = (lo[0], hr[0])
    best_k, rates, state2 = tune_multi_step_k(
        step, state, batch, ks=(1, 2), steps_per_arm=4
    )
    assert set(rates) == {1, 2}
    assert all(r > 0 and np.isfinite(r) for r in rates.values())
    assert best_k in (1, 2) and rates[best_k] == max(rates.values())
    # the returned state advanced by every tuning step and keeps training
    assert int(state2.step) == 4 + 1 + 4 + 2  # per arm: warm + timed calls
    state3, metrics = step(state2, batch)
    assert np.isfinite(float(metrics["loss"]))
