"""The reference training loop, line for line, on the Stoke-twin facade.

This is the loop of `/root/reference/Stoke-DDP.py:70-86` — forward via
``.model``, loss via ``.loss``, ``.backward()``, ``.step()``, synced-loss
reporting — with the same declarative knobs (grad accumulation x2, grad-norm
clip 0.1, AdamW + OneCycle). Under the eager-feeling surface each
backward()+step() accumulation window runs as ONE compiled XLA program
(``fuse_eager_step``).

Runs on host CPU by default (seconds); ``EXAMPLE_PLATFORM=tpu`` uses real
hardware.
"""

import _bootstrap

_bootstrap.setup()

import numpy as np

from pytorch_distributedtraining_tpu import losses
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.optim import OneCycleLR
from pytorch_distributedtraining_tpu.stoke import (
    ClipGradNormConfig,
    DistributedOptions,
    Stoke,
    StokeOptimizer,
)

EPOCHS, STEPS_PER_EPOCH, BATCH = 2, 8, 16


def synthetic_sr_batch(rng, n=BATCH, size=16):
    """Paired LR/HR patches: HR random, LR = 2x2 box downsample."""
    hr = rng.random((n, size, size, 3)).astype(np.float32)
    lr = hr.reshape(n, size // 2, 2, size // 2, 2, 3).mean(axis=(2, 4))
    return lr, hr


def main():
    stoke_model = Stoke(
        model=Net(upscale_factor=2),          # ESPCN twin (Fairscale-DDP.py:74)
        verbose=True,
        optimizer=StokeOptimizer(
            optimizer="AdamW",
            optimizer_kwargs={
                "lr": 1e-3, "betas": (0.9, 0.99), "eps": 1e-8,
                "weight_decay": 1e-4,
            },
        ),
        loss=losses.mse_loss,
        batch_size_per_device=BATCH,
        gpu=True,                              # accelerator if present
        fp16=None,                             # bf16 is the TPU default path
        distributed=DistributedOptions.ddp.value,
        grad_accum_steps=2,                    # Stoke-DDP.py:251
        grad_clip=ClipGradNormConfig(max_norm=0.1, norm_type=2.0),
    )
    scheduler = OneCycleLR(
        stoke_model.optimizer, max_lr=1e-3,
        steps_per_epoch=STEPS_PER_EPOCH, epochs=EPOCHS,
    )

    rng = np.random.default_rng(0)
    stoke_model.model_access.train()
    for epoch in range(EPOCHS):
        for idx in range(STEPS_PER_EPOCH):
            inputs, targets = synthetic_sr_batch(rng)
            outputs = stoke_model.model(inputs)           # Stoke-DDP.py:73
            train_loss = stoke_model.loss(outputs, targets)  # :74
            stoke_model.print_ema_loss(
                prepend_msg=f"E{epoch} S{idx} -- EMA Loss")  # :76
            stoke_model.backward(loss=train_loss)         # :79
            stoke_model.step()                            # :82
            scheduler.step()                              # :83
            synced = stoke_model.detach_and_sync_loss(loss=train_loss)  # :86
        stoke_model.print_on_devices(
            f"epoch {epoch}: loss {float(synced):.5f}")

    print("done: loss decreased to", float(synced))


if __name__ == "__main__":
    main()
