"""Runtime layer: mesh construction, dist bootstrap, port probe."""

import socket

import jax
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import runtime
from pytorch_distributedtraining_tpu.runtime.mesh import (
    MeshSpec,
    batch_spec,
    make_mesh,
    mesh_axis_size,
)


def test_find_free_port_is_bindable():
    port = runtime.find_free_port()
    with socket.socket() as s:
        s.bind(("127.0.0.1", port))


def test_initialize_single_process_noop(monkeypatch):
    monkeypatch.delenv("MASTER_ADDR", raising=False)
    monkeypatch.delenv("WORLD_SIZE", raising=False)
    runtime.initialize()
    assert runtime.is_initialized()
    assert runtime.process_count() == 1
    assert runtime.world_size() == jax.device_count()
    assert 0 <= runtime.rank() < runtime.world_size()


def test_mesh_shapes(devices8):
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    assert mesh_axis_size(mesh, "dp") == 8
    assert mesh_axis_size(mesh, "tp") == 1
    mesh2 = make_mesh(MeshSpec(dp=4, tp=2), devices=devices8)
    assert mesh2.shape["dp"] == 4 and mesh2.shape["tp"] == 2


def test_mesh_size_mismatch_raises(devices8):
    with pytest.raises(ValueError, match="devices"):
        make_mesh(MeshSpec(dp=3), devices=devices8)


def test_mesh_kwargs_form(devices8):
    mesh = make_mesh(dp=2, fsdp=4, devices=devices8)
    assert mesh.shape["dp"] == 2 and mesh.shape["fsdp"] == 4


def test_batch_spec_covers_data_axes(devices8):
    from jax.sharding import NamedSharding

    mesh = make_mesh(MeshSpec(dp=2, fsdp=4), devices=devices8)
    spec = batch_spec(mesh)
    x = np.zeros((16, 3))
    sharded = jax.device_put(x, NamedSharding(mesh, spec))
    # batch dim is split over dp*fsdp = 8 devices
    assert sharded.addressable_shards[0].data.shape == (2, 3)


def test_hybrid_mesh_dp_over_dcn(devices8):
    """2 'slices' x 4-device FSDP: batch shards over dp x fsdp, state over
    fsdp only, and a train step runs on the hybrid layout."""
    import numpy as np
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.losses import mse_loss
    from pytorch_distributedtraining_tpu.models import Net
    from pytorch_distributedtraining_tpu.parallel import (
        TrainStep, ZeRO3, create_train_state,
    )
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec, data_axes, make_hybrid_mesh,
    )

    mesh = make_hybrid_mesh(
        MeshSpec(fsdp=4), dcn_dp=2, devices=devices8
    )
    assert mesh.shape["dp"] == 2 and mesh.shape["fsdp"] == 4
    assert data_axes(mesh) == ("dp", "fsdp")

    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        return mse_loss(model.apply({"params": params}, lr_img), hr_img), {}

    state, shardings = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=ZeRO3(),
    )
    step = TrainStep(
        loss_fn, tx, mesh, ZeRO3(), state_shardings=shardings, donate=False
    )
    rng = np.random.default_rng(0)
    hr = rng.random((16, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(16, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    losses = []
    with mesh:
        for _ in range(4):
            state, m = step(state, (lr, hr))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    # params sharded over fsdp only (replicated across the DCN dp axis)
    kernels = [x for x in jax.tree.leaves(state.params) if x.ndim == 4]
    assert any(
        x.addressable_shards[0].data.shape != x.shape for x in kernels
    )


def test_hybrid_mesh_rejects_dp_in_spec(devices8):
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec, make_hybrid_mesh,
    )

    with pytest.raises(ValueError, match="owns the dp axis"):
        make_hybrid_mesh(MeshSpec(dp=2, fsdp=4), dcn_dp=1, devices=devices8)


_CACHE_PROBE = """
import json, os, sys
import jax
set_dirs = []
_update = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        set_dirs.append(value)
    return _update(name, value)
jax.config.update = spy
from pytorch_distributedtraining_tpu.runtime.cache import enable_compile_cache
path = enable_compile_cache()
print(json.dumps({"path": path, "set_dirs": set_dirs,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", ["set", "unset"])
def test_compile_cache_placement(env_dir, tmp_path):
    """One function places the cache: where JAX_COMPILATION_CACHE_DIR says
    (and then repo code sets no directory itself), else one fixed
    in-checkout path that every process of the checkout agrees on."""
    import json
    import os
    import subprocess
    import sys

    from pytorch_distributedtraining_tpu.runtime import cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != cache.ENV_VAR}
    env["PYTHONPATH"] = repo
    if env_dir == "set":
        env[cache.ENV_VAR] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    if env_dir == "set":
        assert got["path"] == got["config"] == str(tmp_path)
        assert got["set_dirs"] == []
    else:
        # the other process: this one, asked from a different cwd
        assert got["path"] == got["config"] == cache.DEFAULT_DIR
        assert cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
        for salt in (str(os.getuid()), str(os.getpid()), "/tmp"):
            assert salt not in os.path.relpath(got["path"], repo)


def test_hybrid_mesh_fallback_keeps_slices_on_dp(devices8):
    """Non-TPU fallback: contiguous device groups (slices) land on the dp
    axis even when pp>1 precedes it in AXIS_ORDER."""
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec, make_hybrid_mesh,
    )

    mesh = make_hybrid_mesh(
        MeshSpec(pp=2, fsdp=2), dcn_dp=2, devices=devices8
    )
    arr = mesh.devices  # [pp, dp, fsdp, sp, tp, ep]
    ids = np.vectorize(lambda d: d.id)(arr).squeeze()
    # dp is axis 1 after squeeze -> [pp, dp, fsdp]; slice 0 = devices 0..3
    first_slice = {int(i) for i in ids[:, 0, :].ravel()}
    assert first_slice == {devices8[i].id for i in range(4)}, ids
