"""The plain GPT-2 reference against ``models/gpt2.py`` at a tiny size, in
both parameter layouts (float32 on the CPU: they must agree closely)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import gpt2 as reference
from pytorch_distributedtraining_tpu.models import (
    GPT2, GPT2Config, cross_entropy_loss,
)


@pytest.mark.parametrize(
    "layout", [{}, {"scan_layers": True, "remat": True}], ids=["loop", "scan"]
)
def test_reference_matches_the_model(layout):
    cfg = GPT2Config.tiny(**layout)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    tok = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 33))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    loss, grads = jax.value_and_grad(
        lambda p: cross_entropy_loss(model.apply({"params": p}, x), y)
    )(params)
    ref_loss, ref_norm = jax.jit(
        lambda p, a, b: reference.loss_and_grad_norm(
            p, a, b, n_layer=cfg.n_layer, n_head=cfg.n_head, chunks=2
        )
    )(params, x, y)
    assert float(ref_loss) == pytest.approx(float(loss), rel=1e-5)
    assert float(ref_norm) == pytest.approx(
        float(optax.global_norm(grads)), rel=1e-4
    )
