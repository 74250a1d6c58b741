"""SwinIR-S: shapes, param budget, window ops, shift masks, training."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import SwinIR
from pytorch_distributedtraining_tpu.models.swinir import (
    _relative_position_index,
    _shift_attn_mask,
    window_partition,
    window_reverse,
)


def _model():
    # the exact reference config (Stoke-DDP.py:206-208)
    return SwinIR(
        upscale=2, in_chans=3, img_size=64, window_size=8, img_range=1.0,
        depths=[6, 6, 6, 6], embed_dim=60, num_heads=[6, 6, 6, 6],
        mlp_ratio=2, upsampler="pixelshuffledirect", resi_connection="1conv",
    )


def _tiny():
    # same code paths (2 layers = one W-MSA + one SW-MSA, conv, upsample)
    # at a fraction of the 1-core compile time of the full SwinIR-S
    return SwinIR(
        upscale=2, window_size=8, depths=[2], embed_dim=12, num_heads=[2],
        mlp_ratio=2,
    )


def test_window_partition_roundtrip():
    x = jnp.arange(2 * 16 * 16 * 3, dtype=jnp.float32).reshape(2, 16, 16, 3)
    wins = window_partition(x, 8)
    assert wins.shape == (2 * 4, 64, 3)
    back = window_reverse(wins, 8, 16, 16)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_relative_position_index_bounds():
    idx = _relative_position_index(8)
    assert idx.shape == (64, 64)
    assert idx.min() == 0 and idx.max() == 15 * 15 - 1
    assert idx[0, 0] == idx[5, 5]  # self-offset always the same bucket


def test_shift_mask_blocks_cross_region():
    mask = _shift_attn_mask(16, 16, 8, 4)
    assert mask.shape == (4, 64, 64)
    assert np.all(np.diagonal(mask, axis1=1, axis2=2) == 0)  # self visible
    assert (mask == -100.0).any()  # some pairs blocked


def test_forward_shape_and_param_count():
    model = _model()
    x = jnp.zeros((1, 64, 64, 3))
    # param budget of the exact reference config, via eval_shape (no compile)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"]
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes))
    # SwinIR-S is ~0.9M params
    assert 0.7e6 < n < 1.2e6, f"param count {n}"
    # output geometry on the tiny twin (same pad/upsample code path)
    tiny = _tiny()
    xt = jnp.zeros((1, 16, 16, 3))
    params = tiny.init(jax.random.PRNGKey(0), xt)["params"]
    y = jax.jit(tiny.apply)({"params": params}, xt)
    assert y.shape == (1, 32, 32, 3)


def test_forward_non_multiple_of_window():
    model = _tiny()
    x = jnp.zeros((1, 20, 28, 3))  # not multiples of 8 -> pad+crop
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    y = model.apply({"params": params}, x)
    assert y.shape == (1, 40, 56, 3)


def test_shift_changes_output():
    """Shifted layers must actually mix across window borders."""
    model = _tiny()
    key = jax.random.PRNGKey(1)
    x = jax.random.uniform(key, (1, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    y = model.apply({"params": params}, x)
    # perturb one pixel inside window (0,0); effect must reach a pixel in a
    # different window (possible only through shifted attention / convs)
    x2 = x.at[0, 1, 1, 0].add(0.5)
    y2 = model.apply({"params": params}, x2)
    far = np.abs(np.asarray(y2 - y))[0, 24:, 24:, :]
    assert far.max() > 1e-6


def test_swinir_trains(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.losses import l1_loss
    from pytorch_distributedtraining_tpu.parallel import DDP, TrainStep, create_train_state

    model = SwinIR(
        upscale=2, window_size=8, depths=[2], embed_dim=24, num_heads=[4],
        mlp_ratio=2,
    )

    def loss_fn(params, batch, rng, model_state):
        x, y = batch
        return l1_loss(model.apply({"params": params}, x), y), {}

    tx = optim.adamw(lr=2e-3)
    state, sh = create_train_state(
        init_fn=lambda r: (model.init(r, jnp.zeros((1, 16, 16, 3)))["params"], {}),
        tx=tx, mesh=mesh8, policy=DDP(),
    )
    step = TrainStep(loss_fn, tx, mesh8, DDP(), state_shardings=sh)
    rng = np.random.default_rng(0)
    hr = rng.random((8, 32, 32, 3)).astype(np.float32)
    lr = hr.reshape(8, 16, 2, 16, 2, 3).mean(axis=(2, 4))
    batch = (lr, hr)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("impl", ["winograd", "paired", "blockdiag"])
def test_attn_impl_rejects_unknown(impl):
    """A config that names an implementation the module does not have
    fails; it does not silently take another path."""
    with pytest.raises(ValueError, match="attn_impl"):
        SwinIR(depths=[1], embed_dim=12, num_heads=[2],
               attn_impl=impl).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3))
        )


@pytest.mark.parametrize("layout", ["none", "one_device", "dp4"])
def test_default_path_on_cpu_is_the_einsum_path(layout):
    """The default SwinIR takes no argument to choose its attention. Where
    the shapes meet the kernel's contract and the program cannot span
    devices unseen (a step has published its mesh, ``spec.batch_layout``),
    the traced program holds both the fused kernel (for a TPU lowering) and
    the einsums, and a CPU lowers the einsums: loss and every gradient
    equal ``attn_impl='xla'`` to the bit, on unshifted and shifted layers.
    On a mesh of four the core runs under ``shard_map``, each device over
    its own windows (the partitioner cannot split a Mosaic kernel); the
    bias's gradient is then summed in another order. With eight devices
    visible and no layout published the einsums are all there is. The path
    is said at each trace."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributedtraining_tpu.observe import trace
    from pytorch_distributedtraining_tpu.parallel.spec import batch_layout
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    kw = dict(upscale=2, window_size=8, depths=[2], embed_dim=12,
              num_heads=[2], mlp_ratio=2)
    x = jnp.asarray(
        np.random.default_rng(4).random((8, 16, 16, 3)), jnp.float32
    )
    auto, ref = SwinIR(**kw), SwinIR(**kw, attn_impl="xla")
    assert auto.attn_impl == "auto"
    params = ref.init(jax.random.PRNGKey(1), x)["params"]
    n_dev = {"none": 0, "one_device": 1, "dp4": 4}[layout]
    mesh = n_dev and make_mesh(MeshSpec(dp=n_dev), devices=jax.devices()[:n_dev])

    def loss(model):
        def fn(p, x):
            with batch_layout(mesh) if mesh else contextlib.nullcontext():
                return jnp.mean(model.apply({"params": p}, x) ** 2)
        return fn

    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        jaxpr = str(jax.make_jaxpr(loss(auto))(params, x))
        said = [r["attrs"] for r in trace.records()
                if r["name"] == "window_attention.path"]
    finally:
        trace.clear()
        tracer.enabled = was
    assert (said[0]["bn"], said[0]["n"], said[0]["c"]) == (32, 64, 12)
    if layout == "none":
        assert jax.device_count() > 1  # conftest's eight
        assert [s["path"] for s in said] == ["einsum", "einsum"]  # two layers
        assert all("no step has published" in s["reason"] for s in said)
        assert "pallas_call" not in jaxpr
    else:
        assert [s["path"] for s in said] == ["by_platform", "by_platform"]
        assert "platform_index" in jaxpr and "pallas_call" in jaxpr
        assert ("shard_map" in jaxpr) == (layout == "dp4")

    if layout == "dp4":
        x = jax.device_put(x, NamedSharding(mesh, P("dp")))
    got = jax.jit(jax.value_and_grad(loss(auto)))(params, x)
    want = jax.jit(jax.value_and_grad(loss(ref)))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if layout == "dp4":
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
