"""Pallas flash attention vs XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
from pytorch_distributedtraining_tpu.ops.pallas_attn import (
    flash_attention,
    make_flash_attn_fn,
)

B, T, H, DH = 2, 128, 2, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda: rng.normal(size=(B, T, H, DH)).astype(np.float32)  # noqa
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (128, 128)])
def test_matches_xla_attention(qkv, causal, bq, bk):
    q, k, v = qkv
    ref = default_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, bq, bk, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bf16_inputs(qkv):
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    ref = default_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 64, 64, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_gradients_match(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 64, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(default_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_blocks(qkv, causal):
    """Pallas dq/dk/dv kernels vs XLA AD across block shapes (bwd is
    in-kernel recompute, not an XLA fallback)."""
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 64, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(default_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_attn_fn_in_gpt2(qkv):
    """Pluggable attn_fn contract: GPT-2 forward with the Pallas kernel."""
    from pytorch_distributedtraining_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config.tiny(n_embd=32, n_head=2, n_positions=128)
    tok = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 128)),
        jnp.int32,
    )
    dense = GPT2(cfg)
    params = dense.init(jax.random.PRNGKey(0), tok)["params"]
    ref = dense.apply({"params": params}, tok)
    flash_model = GPT2(cfg, attn_fn=make_flash_attn_fn(bq=64, bk=64,
                                                       interpret=True))
    out = flash_model.apply({"params": params}, tok)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_indivisible_seq_raises(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="must divide"):
        flash_attention(q[:, :100], k[:, :100], v[:, :100], True, 64, 64, True)


@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("window", [None, 20, 32, 75, 128])
def test_band_and_grouped_heads_match_the_masked_einsum(window, group):
    """A causal window (shorter than a block of 32, one block, several
    blocks, the whole sequence; None = the causal kernel) and ``group``
    query heads reading one key-value head where it lies: forward and the
    three gradients (dk and dv summed over the group) against the masked
    einsum, at blocks that divide the band unevenly too."""
    from pytorch_distributedtraining_tpu.models.smallthinker import (
        banded_attention,
    )

    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q, do = (jax.random.normal(k, (2, T, 2 * group, DH)) for k in keys[:2])
    k, v = (jax.random.normal(k, (2, T, 2, DH)) for k in keys[2:])
    for bq, bk in ((32, 32), (64, 32)):
        out, vjp = jax.vjp(
            lambda *a: flash_attention(*a, True, bq, bk, True, window), q, k, v
        )
        want, want_vjp = jax.vjp(
            lambda *a: banded_attention(*a, window=window), q, k, v
        )
        np.testing.assert_allclose(out, want, atol=2e-5)
        for g, w in zip(vjp(do), want_vjp(do)):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=1e-4)


def test_a_window_is_causal_and_heads_must_divide(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, False, 32, 32, True, 16)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(
            jnp.concatenate([q, q[:, :, :1]], 2), k, v, True, 32, 32, True
        )
