"""Stoke facade: the reference's exact call sequence against the twin API."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import losses, metrics
from pytorch_distributedtraining_tpu.data import DistributedSampler, SyntheticSRDataset
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.optim import OneCycleLR, ReduceLROnPlateau
from pytorch_distributedtraining_tpu.stoke import (
    AMPConfig,
    ClipGradNormConfig,
    DDPConfig,
    DistributedOptions,
    FairscaleOSSConfig,
    FP16Options,
    Stoke,
    StokeOptimizer,
)


def _stoke(**over):
    """Construct the facade exactly like Stoke-DDP.py:240-254 does."""
    kwargs = dict(
        model=Net(upscale_factor=2),
        verbose=False,
        optimizer=StokeOptimizer(
            optimizer="AdamW",
            optimizer_kwargs={
                "lr": 1e-3, "betas": (0.9, 0.99), "eps": 1e-8,
                "weight_decay": 1e-4,
            },
        ),
        loss=losses.mse_loss,
        batch_size_per_device=2,
        gpu=True,
        fp16=None,
        distributed=DistributedOptions.ddp.value,
        fairscale_oss=True,
        fairscale_sddp=True,
        grad_accum_steps=2,
        configs=[
            AMPConfig(init_scale=2.0**14),
            DDPConfig(local_rank=int(os.getenv("LOCAL_RANK", 0)),
                      convert_to_sync_batch_norm=True),
            FairscaleOSSConfig(broadcast_fp16=True),
        ],
        grad_clip=ClipGradNormConfig(max_norm=0.1, norm_type=2.0),
    )
    kwargs.update(over)
    return Stoke(**kwargs)


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    hr = rng.random((n, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(n, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    return lr, hr


def test_reference_train_loop_shape():
    """The exact loop of Stoke-DDP.py:70-86 runs and learns."""
    stoke_model = _stoke()
    inputs, targets = _batch()
    stoke_model.model_access.train()
    first = last = None
    for idx in range(8):
        outputs = stoke_model.model(inputs)
        train_loss = stoke_model.loss(outputs, targets)
        stoke_model.print_ema_loss(prepend_msg=f"Step {idx+1} -- EMA Loss")
        stoke_model.backward(loss=train_loss)
        stoke_model.step()
        synced = stoke_model.detach_and_sync_loss(loss=train_loss)
        # device scalar (the reference returns a detached *tensor*,
        # Stoke-DDP.py:86): float-coercible, but no implicit host sync
        assert jnp.ndim(synced) == 0
        assert isinstance(float(synced), float)
        first = synced if first is None else first
        last = synced
    assert float(last) < float(first)
    # accum=2 -> 8 backwards = 4 optimizer steps
    assert stoke_model.step_count == 4


def test_world_size_rank_properties():
    s = _stoke()
    assert s.world_size == jax.device_count()
    assert 0 <= s.rank < s.world_size


def test_grad_accum_boundary_semantics():
    s = _stoke(grad_accum_steps=2)
    x, y = _batch()
    out = s.model(x)
    s.loss(out, y)
    s.backward()
    s.step()  # 1 backward: no optimizer step yet
    assert s.step_count == 0
    out = s.model(x)
    s.loss(out, y)
    s.backward()
    s.step()
    assert s.step_count == 1


def test_schedulers_drive_handle_lr():
    s = _stoke()
    sched1 = OneCycleLR(s.optimizer, max_lr=0.01, steps_per_epoch=10, epochs=2,
                        pct_start=0.9)
    lr0 = s.optimizer.lr
    for _ in range(18):
        sched1.step()
    assert s.optimizer.lr != lr0
    sched2 = ReduceLROnPlateau(s.optimizer, mode="min", factor=0.2, patience=0,
                               min_lr=5e-5)
    sched2.step(1.0)
    before = s.optimizer.lr
    sched2.step(2.0)  # worse -> patience 0 -> cut
    assert s.optimizer.lr == pytest.approx(max(before * 0.2, 5e-5))


def test_fused_step_matches_eager_path():
    x, y = _batch(seed=3)
    s1 = _stoke(grad_accum_steps=1)
    s2 = _stoke(grad_accum_steps=1)
    for _ in range(3):
        out = s1.model(x)
        l = s1.loss(out, y)
        s1.backward(l)
        s1.step()
        s2.fused_step(x, y)
    for a, b in zip(jax.tree.leaves(s1.state.params), jax.tree.leaves(s2.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert s1.step_count == s2.step_count == 3


def test_hot_loop_runs_single_fused_program():
    """The reference loop must not pay a separate forward: `.model()` defers,
    `.backward()` runs the one compiled fwd+bwd program."""
    s = _stoke(grad_accum_steps=1)
    x, y = _batch(seed=5)
    s.init(x)
    fwd_calls = {"n": 0}
    real_fwd = s._jit_fwd

    def counting_fwd(*a, **k):
        fwd_calls["n"] += 1
        return real_fwd(*a, **k)

    s._jit_fwd = counting_fwd
    for _ in range(3):
        out = s.model(x)
        l = s.loss(out, y)
        s.backward(l)
        s.step()
        assert jnp.ndim(s.detach_and_sync_loss(l)) == 0
    assert fwd_calls["n"] == 0, "eager forward ran inside the fused hot loop"


def test_hot_loop_never_blocks_host(monkeypatch):
    """The reference-shaped loop must not host-sync per step:
    loss bookkeeping stays on device; ``print_ema_loss`` rides
    an async background fetch, so only ``_last_loss`` /
    ``detach_and_sync_loss`` / explicit float() block the host."""
    s = _stoke(grad_accum_steps=1, verbose=True)
    x, y = _batch(seed=11)
    s.init(x)
    pulls = {"n": 0}
    real_get = jax.device_get

    def counting_get(*a, **k):
        pulls["n"] += 1
        return real_get(*a, **k)

    monkeypatch.setattr(jax, "device_get", counting_get)
    sum_loss = 0.0
    for _ in range(3):
        out = s.model(x)
        l = s.loss(out, y)
        s.backward(l)
        s.step()
        sum_loss += s.detach_and_sync_loss(l)
    assert pulls["n"] == 0, "hot loop host-synced via device_get"
    # verbose printing rides the async fetcher (np.asarray in a daemon
    # thread) — no blocking device_get even at the log points
    s.print_ema_loss()
    assert pulls["n"] == 0
    assert s._ema_async.flush() is not None  # a real value was fetched
    # exact reads are the only blocking points, by design
    lv = float(l)  # explicit materialization of the lazy loss
    n0 = pulls["n"]
    assert s._last_loss == pytest.approx(lv)
    assert pulls["n"] == n0 + 1  # _last_loss: exactly one blocking read
    assert float(sum_loss) > 0


def test_deferred_output_materializes_correctly():
    """Using the `.model()` output directly still gives the real forward,
    both before backward (fresh params) and after (from the grad program)."""
    s = _stoke(grad_accum_steps=1)
    x, y = _batch(seed=6)
    s.init(x)

    # before backward: materialization == explicit compiled forward
    out = s.model(x)
    expect = s._run_forward(s._shard_batch(x), train=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), atol=1e-6
    )

    # after backward: handle resolves from the grad program's own forward
    out2 = s.model(x)
    l = s.loss(out2, y)
    s.backward(l)
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(expect), atol=1e-6
    )
    # deferred loss resolves to the fused program's loss
    assert float(l) == pytest.approx(float(s._last_loss))


def test_deferred_handles_behave_like_arrays():
    """Operators, comparisons, bookkeeping idioms must all work on the
    deferred handles (code-review r2 finding #1)."""
    s = _stoke(grad_accum_steps=1)
    x, y = _batch(seed=8)
    s.init(x)
    out = s.model(x)
    assert out.shape == (16, 16, 16, 3)  # served from eval_shape, no exec
    l = s.loss(out, y)
    running = 0.0
    running += l  # float.__radd__ path
    assert float(running) > 0
    assert bool(l > 0.0)
    assert (l < 1e9) and (l >= 0.0)
    comp = out == out  # elementwise, not identity bool
    assert hasattr(comp, "shape") and comp.shape == (16, 16, 16, 3)
    s.backward(l)
    s.step()


def test_unresolved_handle_survives_step_donation():
    """A monitoring forward that never goes through backward() must
    materialize the pre-step values even though step() donates the params
    it captured (code-review r2 finding #2)."""
    s = _stoke(grad_accum_steps=1)
    x, y = _batch(seed=9)
    s.init(x)
    monitor = s.model(x)  # deferred, never passed to backward
    out = s.model(x)
    expect = np.asarray(s._run_forward(s._shard_batch(x), train=True))
    s.backward(s.loss(out, y))
    s.step()  # donates the old params; must force-materialize `monitor`
    np.testing.assert_allclose(np.asarray(monitor), expect, atol=1e-6)


def test_eval_mode_forward_is_eager():
    s = _stoke()
    x, _ = _batch(seed=7)
    s.init(x)
    s.model_access.eval()
    out = s.model(x)
    assert hasattr(out, "shape") and not type(out).__name__.startswith("_Lazy")


def test_checkpoint_save_load_roundtrip(tmp_path):
    s = _stoke()
    x, y = _batch()
    for _ in range(4):
        s.fused_step(x, y)
    path, tag = s.save(path=str(tmp_path), name="model_0_0.10_0.20")
    assert tag == "model_0_0.10_0.20.npz"
    assert os.path.exists(path)

    s2 = _stoke()
    s2.init(x)
    s2.load(path)
    assert s2.step_count == s.step_count
    for a, b in zip(jax.tree.leaves(s.state.params), jax.tree.leaves(s2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues identically after resume
    m1 = s.fused_step(x, y)
    m2 = s2.fused_step(x, y)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)


def test_load_model_state_nested_and_strict(tmp_path):
    s = _stoke()
    x, y = _batch()
    s.init(x)
    raw = jax.device_get(s.state.params)
    # nested under 'params' key (Stoke-DDP.py:209-213)
    s.load_model_state({"params": raw}, strict=True)
    with pytest.raises(ValueError, match="strict load failed"):
        s.load_model_state({"params": {"bogus": np.zeros(3)}}, strict=True)


def test_validation_loop_shape():
    """validate() of Stoke-DDP.py:101-128 shape: eval mode, metrics math."""
    s = _stoke()
    ds = SyntheticSRDataset(n=16, lr_size=8, scale=2)
    sampler = DistributedSampler(ds, num_replicas=1, rank=0, shuffle=False)
    val_loader = s.DataLoader(ds, sampler=sampler, num_workers=0)
    s.model_access.eval()
    val_loss, n = 0.0, 0
    mae_sum, psnr_sum = 0.0, 0.0
    for inputs, targets in val_loader:
        outputs = s.model(inputs)
        val_loss += float(s.loss(outputs, targets))
        mae_sum += float(metrics.mae(outputs, targets))
        psnr_sum += float(metrics.psnr(outputs, targets))
        n += 1
    assert n == len(val_loader) > 0
    assert np.isfinite(val_loss) and np.isfinite(psnr_sum)


def test_eval_step_matches_eager_validation():
    """facade.eval_step: one compiled program per
    batch, device-scalar totals, numerically equal to the eager loop."""
    s = _stoke()
    ds = SyntheticSRDataset(n=16, lr_size=8, scale=2)
    sampler = DistributedSampler(ds, num_replicas=1, rank=0, shuffle=False)
    val_loader = s.DataLoader(ds, sampler=sampler, num_workers=0)
    x0, _ = _batch()
    s.init(x0)
    s.model_access.eval()

    step = s.eval_step({"mae": metrics.mae, "psnr": metrics.psnr})
    assert s.eval_step({"mae": metrics.mae, "psnr": metrics.psnr}) is step

    totals, n = None, 0
    eager = {"loss": 0.0, "mae": 0.0, "psnr": 0.0}
    for inputs, targets in val_loader:
        m = step(inputs, targets)
        assert set(m) == {"loss", "mae", "psnr"}
        assert all(hasattr(v, "device") for v in m.values())  # stays on device
        totals = m if totals is None else jax.tree.map(jnp.add, totals, m)
        out = s.model(inputs)
        eager["loss"] += float(s.loss(out, targets))
        eager["mae"] += float(metrics.mae(out, targets))
        eager["psnr"] += float(metrics.psnr(out, targets))
        n += 1
    host = jax.device_get(totals)
    for k in eager:
        np.testing.assert_allclose(float(host[k]), eager[k], rtol=2e-5)


def test_eval_step_honors_sharded_policy(zero_mesh8):
    """eval_step under ZeRO-3 (fairscale_fsdp): params keep their sharded
    placement — no implicit all-gather onto one device — and the metrics
    match the eager forward."""
    s = _stoke(
        fairscale_fsdp=True,
        fairscale_oss=False,
        fairscale_sddp=False,
        grad_accum_steps=1,
        mesh=zero_mesh8,
    )
    x, y = _batch()
    s.init(x)
    assert s.policy.shard_params
    # at least one param leaf is genuinely sharded before eval
    kernels = [p for p in jax.tree.leaves(s.state.params) if p.ndim == 4]
    assert any(
        k.addressable_shards[0].data.shape != k.shape for k in kernels
    )
    s.model_access.eval()
    step = s.eval_step({"mae": metrics.mae})
    m = jax.device_get(step(x, y))
    out = s.model(x)
    np.testing.assert_allclose(
        float(m["loss"]), float(s.loss(out, y)), rtol=2e-5
    )
    np.testing.assert_allclose(
        float(m["mae"]), float(metrics.mae(out, y)), rtol=2e-5
    )
    # params untouched and still sharded after the compiled eval
    assert any(
        k.addressable_shards[0].data.shape != k.shape
        for k in jax.tree.leaves(s.state.params) if k.ndim == 4
    )


def test_fp16_amp_option():
    s = _stoke(fp16=FP16Options.amp.value, grad_accum_steps=1)
    x, y = _batch()
    m = s.fused_step(x, y)
    assert float(m["loss_scale"]) == 2.0**14  # AMPConfig(init_scale=2.**14)


def test_bf16_option():
    s = _stoke(fp16="bf16", grad_accum_steps=1)
    x, y = _batch()
    m = s.fused_step(x, y)
    assert np.isfinite(float(m["loss"]))


def test_uninitialized_save_raises():
    s = _stoke()
    with pytest.raises(RuntimeError, match="not initialized"):
        s.save()


def test_grad_clip_value_config():
    """stoke's second clip twin: ClipGradConfig (elementwise value clip)
    is accepted by the facade and actually bounds the update."""
    from pytorch_distributedtraining_tpu.stoke import ClipGradConfig

    s = _stoke(
        grad_clip=ClipGradConfig(clip=1e-4), grad_accum_steps=1,
        # keep the ZeRO-2 path but silence broadcast_fp16: the wire
        # narrowing would round the clipped update by up to ~0.4% and blur
        # the exact bound asserted below
        configs=[FairscaleOSSConfig(broadcast_fp16=False)],
        optimizer=StokeOptimizer(
            optimizer="SGD", optimizer_kwargs={"lr": 1.0},
        ),
    )
    x, y = _batch()
    s.init(x)
    before = jax.tree.map(np.asarray, jax.device_get(s.state.params))
    s.fused_step(x, y)
    after = jax.device_get(s.state.params)
    deltas = [
        np.max(np.abs(np.asarray(a) - b))
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
    ]
    assert max(deltas) <= 1e-4 + 1e-7, max(deltas)  # |update| <= lr*clip
    assert max(deltas) > 0  # but training still moves

    class Bogus:
        pass

    with pytest.raises(TypeError, match="grad_clip"):
        _stoke(grad_clip=Bogus())


def test_deepspeed_config_precision_and_clip_wiring():
    """DeepspeedConfig's own switches are honored when the ctor doesn't
    already decide: bf16_enabled/fp16_enabled pick the precision,
    gradient_clipping feeds the global-norm clip, and
    AMPConfig(enabled=False) disables the scaler like torch's
    GradScaler(enabled=False)."""
    from pytorch_distributedtraining_tpu.stoke import DeepspeedConfig

    s = _stoke(configs=[DeepspeedConfig(bf16_enabled=True)],
               grad_clip=None, fp16=None)
    assert s.fp16 == "bf16" and s.loss_scaler is None

    s = _stoke(configs=[DeepspeedConfig(fp16_enabled=True,
                                        gradient_clipping=0.5)],
               grad_clip=None, fp16=None)
    assert s.fp16 == "amp" and s.loss_scaler is not None

    # explicit ctor fp16 wins over the DeepSpeed switch
    s = _stoke(configs=[DeepspeedConfig(fp16_enabled=True)],
               grad_clip=None, fp16=FP16Options.bf16.value)
    assert s.fp16 == "bf16"

    # scaler disabled but fp16 compute kept
    s = _stoke(configs=[AMPConfig(init_scale=2.0**14, enabled=False)],
               fp16=FP16Options.amp.value, grad_accum_steps=1)
    assert s.loss_scaler is None
    x, y = _batch()
    m = s.fused_step(x, y)
    assert np.isfinite(float(m["loss"]))


def test_remat_applies_to_eager_backward_path():
    """TPUConfig(remat=True) must not be inert on the reference-shaped
    eager loop: the .backward() program carries a remat region, and the
    trajectory matches the non-remat facade exactly."""
    from pytorch_distributedtraining_tpu.stoke import TPUConfig

    x, y = _batch(seed=13)
    # broadcast_fp16 off: bf16 update rounding would amplify remat's
    # bitwise-different grad reassociation past the exactness tolerance
    s_rm = _stoke(
        configs=[TPUConfig(remat=True), FairscaleOSSConfig()],
        grad_accum_steps=1,
    )
    s_nr = _stoke(configs=[FairscaleOSSConfig()], grad_accum_steps=1)
    for s in (s_rm, s_nr):
        out = s.model(x)
        l = s.loss(out, y)
        s.backward(l)
        s.step()
    assert s_rm.policy.remat and not s_nr.policy.remat
    for a, b in zip(
        jax.tree.leaves(s_rm.state.params), jax.tree.leaves(s_nr.state.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def grad_jaxpr(s):
        return str(jax.make_jaxpr(
            lambda p: s._jit_loss_grad.__wrapped__(
                p, s._state.model_state, s._shard_batch(x),
                s._shard_batch(y), s._state.rng, s._state.scaler,
            )
        )(s._state.params).jaxpr)

    assert "remat" in grad_jaxpr(s_rm)
    assert "remat" not in grad_jaxpr(s_nr)


def test_oss_broadcast_fp16_narrows_update_wire():
    """FairscaleOSSConfig(broadcast_fp16=True) under a ZeRO policy casts
    the post-step update fan-out to bf16 — params move by bf16-rounded
    updates (the reference's lossy fp16 broadcast twin); with the flag
    off, updates apply at full f32."""
    x, y = _batch(seed=17)
    kw = dict(
        grad_accum_steps=1, grad_clip=None,
        optimizer=StokeOptimizer(optimizer="SGD",
                                 optimizer_kwargs={"lr": 0.25}),
    )
    s_on = _stoke(configs=[FairscaleOSSConfig(broadcast_fp16=True)], **kw)
    s_off = _stoke(configs=[FairscaleOSSConfig(broadcast_fp16=False)], **kw)
    assert s_on._update_wire_dtype() == jnp.bfloat16
    assert s_off._update_wire_dtype() is None
    for s in (s_on, s_off):
        s.init(x)
        s.fused_step(x, y)
    # same seed/init: the two runs differ exactly by bf16 rounding of the
    # update (absolute error <= one bf16 ulp of the update magnitude) —
    # close in absolute terms, but not bitwise equal
    close = all(
        np.allclose(np.asarray(a), np.asarray(b), atol=5e-4)
        for a, b in zip(jax.tree.leaves(s_on.state.params),
                        jax.tree.leaves(s_off.state.params))
    )
    identical = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s_on.state.params),
                        jax.tree.leaves(s_off.state.params))
    )
    assert close and not identical


# -- pipeline knobs ($GRAFT_PP family) ------------------------------------


def test_pp_env_knobs_resolution(monkeypatch):
    from pytorch_distributedtraining_tpu.stoke.config import TPUConfig
    from pytorch_distributedtraining_tpu.stoke.facade import _pp_from_env

    for var in ("GRAFT_PP", "GRAFT_PP_SCHEDULE", "GRAFT_PP_MICRO"):
        monkeypatch.delenv(var, raising=False)
    assert _pp_from_env(TPUConfig()) == (1, "1f1b", 0)
    assert _pp_from_env(
        TPUConfig(pp=2, pp_schedule="interleaved", pp_micro=6)
    ) == (2, "interleaved", 6)
    # env twins override the config fields (deploy-time, like GRAFT_REMAT)
    monkeypatch.setenv("GRAFT_PP", "4")
    monkeypatch.setenv("GRAFT_PP_SCHEDULE", "gpipe")
    monkeypatch.setenv("GRAFT_PP_MICRO", "8")
    assert _pp_from_env(TPUConfig(pp=2)) == (4, "gpipe", 8)


def test_pp_env_shapes_facade_mesh(monkeypatch):
    monkeypatch.setenv("GRAFT_PP", "2")
    monkeypatch.delenv("GRAFT_PP_SCHEDULE", raising=False)
    s = _stoke()
    # $GRAFT_PP alone: remaining devices fill the data axis
    assert s.mesh.shape["pp"] == 2
    assert s.mesh.shape["dp"] == jax.device_count() // 2
    assert s.pp == 2 and s.pp_schedule == "1f1b"


def test_explicit_mesh_overrides_pp_env(monkeypatch, mesh8):
    monkeypatch.setenv("GRAFT_PP", "4")
    s = _stoke(mesh=mesh8)
    # a caller-supplied mesh wins; pp reflects ITS shape, not the env
    assert s.pp == mesh8.shape.get("pp", 1) == 1


def test_pipeline_step_requires_initialized_state(monkeypatch):
    monkeypatch.setenv("GRAFT_PP", "2")
    s = _stoke()
    with pytest.raises(RuntimeError, match="init"):
        s.pipeline_step(
            lambda p, x: x, lambda o, y, mb, rng: jnp.mean(y**2)
        )


def test_facade_publishes_its_mesh_while_the_model_is_traced():
    """The facade owns the mesh and places every batch on its data axes, so
    it says so (``spec.batch_layout``) wherever it applies the model. The
    driver's SwinIR names no attention: on the eight devices it can then
    place its window kernel itself, each device over its own windows
    (``by_platform``: the einsums on this CPU), in the training programs
    and in the forward alike. Without a published mesh it would have to
    take the einsums (the partitioner cannot split a Mosaic kernel)."""
    from pytorch_distributedtraining_tpu.models import SwinIR
    from pytorch_distributedtraining_tpu.observe import trace

    stoke_model = _stoke(model=SwinIR(
        upscale=2, window_size=8, depths=[2], embed_dim=12, num_heads=[2],
        mlp_ratio=2,
    ))
    rng = np.random.default_rng(0)
    hr = rng.random((16, 32, 32, 3)).astype(np.float32)
    lr = hr.reshape(16, 16, 2, 16, 2, 3).mean(axis=(2, 4))
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        stoke_model.model_access.train()
        for _ in range(2):  # one accumulation window
            loss = stoke_model.loss(stoke_model.model(lr), hr)
            stoke_model.backward(loss=loss)
            stoke_model.step()
        stoke_model.model_access.eval()
        np.asarray(stoke_model.model(lr))
        said = [r["attrs"] for r in trace.records()
                if r["name"] == "window_attention.path"]
    finally:
        trace.clear()
        tracer.enabled = was
    assert stoke_model.mesh.size == 8
    # the parameters' init sees one image and no mesh: the einsums
    assert {s["path"] for s in said if s["bn"] == 4} == {"einsum"}
    batches = [s for s in said if s["bn"] == 16 * 4]
    assert len(batches) >= 4 and len(batches) + 2 == len(said), said
    assert {s["path"] for s in batches} == {"by_platform"}, batches
    assert all("8 devices its own windows" in s["reason"] for s in batches)
