"""SwinIR (``models/swinir.py``) for image super-resolution: the model a
configuration file describes, its FLOPs and its float32 reference loss."""

from __future__ import annotations

TRAIN_MULT = 3.0  # forward + backward = 3 x the forward's matmul FLOPs


def model(config: dict):
    """The module ``drivers/stoke_ddp.main`` builds, sizes from the file."""
    from pytorch_distributedtraining_tpu.models import SwinIR

    return SwinIR(
        upscale=config["upscale"], in_chans=config["in_chans"],
        img_size=config["img_size"], window_size=config["window_size"],
        img_range=config["img_range"], depths=list(config["depths"]),
        embed_dim=config["embed_dim"], num_heads=list(config["num_heads"]),
        mlp_ratio=config["mlp_ratio"], upsampler=config["upsampler"],
        resi_connection=config["resi_connection"],
        remat="none", scan_layers=False,
    )


def train_flops_per_image(config: dict, lr_size: int) -> float:
    """Matmul and convolution FLOPs the forward and backward passes require
    for one low-resolution ``lr_size`` x ``lr_size`` patch (2mnk, backward
    twice the forward; copied from ``observe.goodput.swinir_train_flops``).
    Convention, stated: window attention over ``window_size``^2-long
    sequences; the four convolutions outside the blocks and one per RSTB;
    norms, softmax, the loss network and the optimizer are not counted."""
    tokens, c = lr_size * lr_size, config["embed_dim"]
    per_layer = (
        2 * tokens * 4 * c * c  # qkv + proj
        + 2 * 2 * tokens * config["window_size"] ** 2 * c  # qk^T, att.v
        + 2 * tokens * 2 * config["mlp_ratio"] * c * c  # mlp
    )
    chans, up = config["in_chans"], config["upscale"]
    conv = (
        2 * 9 * chans * c * tokens  # shallow 3x3 conv
        + len(config["depths"]) * 2 * 9 * c * c * tokens  # one per RSTB
        + 2 * 9 * c * c * tokens  # after the body
        + 2 * 9 * c * (chans * up**2) * tokens  # upsample conv
    )
    return TRAIN_MULT * (sum(config["depths"]) * per_layer + conv)


def reference_loss(module, loss, params, inputs, targets) -> float:
    """The module's own forward in float32 at 'highest' matmul precision,
    and the loss, on the given weights. (The module is tied to torch's
    SwinIR by ``tests/test_interop_swinir*.py``; an independent
    ``jax.numpy`` SwinIR is an open question of PERF.md.)"""
    import jax
    import jax.numpy as jnp

    def fwd(p, x, y):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return loss(module.apply({"params": p}, x.astype(jnp.float32)), y)

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(fwd)(params, inputs, targets))
