"""Optimizer-state host offload (DeepSpeed offload twin).

The reference imports the DeepSpeed config surface (`/root/reference/
Stoke-DDP.py:18`); its ``offload_optimizer.device='cpu'`` semantics map here
to optimizer state placed in pinned host memory via sharding memory kinds
(streamed over PCIe for the update). The CPU test backend cannot *execute*
host-placed jit programs (no annotate_device_placement registration), so on
CPU the policy must fall back to device memory with a warning — proven here;
the TPU path is exercised by ``benchmarks/offload_smoke.py`` on hardware.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import (
    TrainStep,
    ZeRO1,
    create_train_state,
)
from pytorch_distributedtraining_tpu.parallel.spec import (
    host_offload_supported,
    tree_shardings,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def test_memory_kind_shardings_constructed(devices8):
    mesh = make_mesh(MeshSpec(fsdp=8), devices=devices8)
    specs = {"m": P("fsdp"), "v": P()}
    sh = tree_shardings(specs, mesh, memory_kind="pinned_host")
    assert sh["m"].memory_kind == "pinned_host"
    assert sh["v"].memory_kind == "pinned_host"
    default = tree_shardings(specs, mesh)
    assert default["m"].memory_kind != "pinned_host"


def test_cpu_backend_reports_no_host_offload(devices8):
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    assert host_offload_supported(mesh) is False  # jax 0.9 CPU limitation


def test_offload_policy_falls_back_and_trains_on_cpu(devices8, caplog):
    mesh = make_mesh(MeshSpec(fsdp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        return mse_loss(model.apply({"params": params}, lr_img), hr_img), {}

    policy = ZeRO1(offload_opt_state=True)
    with caplog.at_level(logging.WARNING):
        state, shardings = create_train_state(
            init_fn=lambda rng: (
                model.init(rng, jnp.zeros((1, 8, 8, 3)))["params"],
                {},
            ),
            tx=tx, mesh=mesh, policy=policy,
        )
    assert any("host offload" in r.message for r in caplog.records)
    # fell back: opt state in default device memory, training still works
    opt_sh = jax.tree.leaves(
        jax.tree.map(lambda x: x.sharding.memory_kind, state.opt_state)
    )
    assert all(k != "pinned_host" for k in opt_sh)

    step = TrainStep(
        loss_fn, tx, mesh, policy, state_shardings=shardings, donate=False
    )
    rng = np.random.default_rng(0)
    hr = rng.random((16, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(16, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    with mesh:
        for _ in range(2):
            state, m = step(state, (lr, hr))
    assert np.isfinite(float(m["loss"]))


def test_param_offload_falls_back_and_trains_on_cpu(devices8, caplog):
    """DeepspeedOffloadParamConfig twin: params in
    pinned host memory where supported; on the CPU backend the policy must
    fall back with a warning and training must still run."""
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=3e-3)

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        return mse_loss(model.apply({"params": params}, lr_img), hr_img), {}

    from pytorch_distributedtraining_tpu.parallel import DDP

    policy = DDP(offload_params=True)
    with caplog.at_level(logging.WARNING):
        state, shardings = create_train_state(
            init_fn=lambda rng: (
                model.init(rng, jnp.zeros((1, 8, 8, 3)))["params"],
                {},
            ),
            tx=tx, mesh=mesh, policy=policy,
        )
    assert any("parameter host offload" in r.message for r in caplog.records)
    par_kinds = jax.tree.leaves(
        jax.tree.map(lambda x: x.sharding.memory_kind, state.params)
    )
    assert all(k != "pinned_host" for k in par_kinds)

    step = TrainStep(
        loss_fn, tx, mesh, policy, state_shardings=shardings, donate=False
    )
    rng = np.random.default_rng(0)
    hr = rng.random((16, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(16, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    with mesh:
        for _ in range(2):
            state, m = step(state, (lr, hr))
    assert np.isfinite(float(m["loss"]))


def test_facade_wires_offload_knobs():
    from pytorch_distributedtraining_tpu.stoke.config import (
        DeepspeedConfig,
        DeepspeedOffloadOptimizerConfig,
        DeepspeedZeROConfig,
        FairscaleFSDPConfig,
    )
    from pytorch_distributedtraining_tpu.stoke.facade import Stoke

    def make(configs):
        from pytorch_distributedtraining_tpu.stoke.optimizer import (
            StokeOptimizer,
        )

        return Stoke(
            model=Net(upscale_factor=2),
            sample_input=jnp.zeros((1, 8, 8, 3)),
            optimizer=StokeOptimizer(
                optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}
            ),
            loss=mse_loss,
            batch_size_per_device=4,
            configs=configs,
        )

    s = make([DeepspeedConfig(
        zero_optimization=DeepspeedZeROConfig(stage=1),
        offload_optimizer=DeepspeedOffloadOptimizerConfig(device="cpu"),
    )])
    assert s.policy.offload_opt_state is True

    s2 = make([FairscaleFSDPConfig(cpu_offload=True)])
    assert s2.policy.offload_opt_state is True

    s3 = make([])
    assert s3.policy.offload_opt_state is False
    assert s3.policy.offload_params is False

    from pytorch_distributedtraining_tpu.stoke.config import (
        DeepspeedOffloadParamConfig,
    )

    s4 = make([DeepspeedConfig(
        zero_optimization=DeepspeedZeROConfig(stage=2),
        offload_param=DeepspeedOffloadParamConfig(device="cpu"),
    )])
    assert s4.policy.offload_params is True
    s5 = make([DeepspeedConfig(
        offload_param=DeepspeedOffloadParamConfig(device="nvme"),
    )])
    assert s5.policy.offload_params is False  # only the cpu tier maps


def test_facade_warns_on_inert_offload_knobs(recwarn):
    """Surface-parity knobs with no TPU effect warn instead of silently
    dropping: AIO config and non-cpu offload tiers."""
    import warnings

    from pytorch_distributedtraining_tpu.stoke.config import (
        DeepspeedAIOConfig,
        DeepspeedConfig,
        DeepspeedOffloadOptimizerConfig,
    )
    from pytorch_distributedtraining_tpu.stoke.facade import Stoke
    from pytorch_distributedtraining_tpu.stoke.optimizer import StokeOptimizer

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Stoke(
            model=Net(upscale_factor=2),
            sample_input=jnp.zeros((1, 8, 8, 3)),
            optimizer=StokeOptimizer(
                optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}
            ),
            loss=lambda o, t: jnp.mean((o - t) ** 2),
            batch_size_per_device=1,
            configs=[DeepspeedConfig(
                aio=DeepspeedAIOConfig(),
                offload_optimizer=DeepspeedOffloadOptimizerConfig(
                    device="nvme"
                ),
            )],
        )
        msgs = [str(x.message) for x in w]
    assert any("inert on TPU" in m for m in msgs), msgs
    assert any("nvme" in m for m in msgs), msgs
