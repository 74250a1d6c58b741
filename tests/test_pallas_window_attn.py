"""Fused Pallas window attention vs the XLA einsum path (interpret mode).

The kernel must be a drop-in for `models/swinir.py:WindowAttention`
(`attn_impl='pallas_interpret'` on CPU): same parameters, same outputs,
same gradients — including the relative-position-bias gradient the
backward kernel accumulates across the window grid.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pytorch_distributedtraining_tpu.models.swinir import (
    SwinIR,
    WindowAttention,
    _shift_attn_mask,
)
from pytorch_distributedtraining_tpu.ops import pallas_window_attn as pwa


def _qkv(bn=8, h=3, n=16, d=6, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.standard_normal((bn, h, n, d)), jnp.float32)
    return mk(), mk(), mk()


def _ref(q, k, v, bias, mask):
    scale = q.shape[-1] ** -0.5
    s = (q * scale) @ k.transpose(0, 1, 3, 2) + bias[None]
    if mask is not None:
        bn, h, n, _ = q.shape
        nw = mask.shape[0]
        s = s.reshape(bn // nw, nw, h, n, n) + mask[None, :, None]
        s = s.reshape(bn, h, n, n)
    p = jax.nn.softmax(s, axis=-1)
    return p @ v


@pytest.mark.parametrize("with_mask", [False, True])
def test_kernel_matches_einsum_fwd_and_grads(with_mask):
    q, k, v = _qkv()
    bn, h, n, d = q.shape
    r = np.random.default_rng(1)
    bias = jnp.asarray(r.standard_normal((h, n, n)), jnp.float32)
    mask = None
    if with_mask:
        nw = 4  # bn=8 windows -> 2 images x 4 windows
        mask = jnp.asarray(
            np.where(r.random((nw, n, n)) > 0.8, -100.0, 0.0), jnp.float32
        )

    def loss_pallas(q, k, v, bias):
        out = pwa.window_attention(q, k, v, bias, mask, 4, True)
        return jnp.sum(out * jnp.cos(out)), out

    def loss_ref(q, k, v, bias):
        out = _ref(q, k, v, bias, mask)
        return jnp.sum(out * jnp.cos(out)), out

    (l1, o1), g1 = jax.value_and_grad(loss_pallas, argnums=(0, 1, 2, 3),
                                      has_aux=True)(q, k, v, bias)
    (l2, o2), g2 = jax.value_and_grad(loss_ref, argnums=(0, 1, 2, 3),
                                      has_aux=True)(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b, name in zip(g1, g2, ["dq", "dk", "dv", "dbias"]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
        )


def test_module_pallas_impl_matches_xla():
    """Same Flax params, both impls, identical outputs + parameter grads."""
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((8, 16, 12)), jnp.float32)
    mask = None  # module-level mask parity is covered by the SwinIR test
    mods = {
        impl: WindowAttention(12, 3, 4, attn_impl=impl)
        for impl in ("xla", "pallas_interpret")
    }
    params = mods["xla"].init(jax.random.key(0), x, mask)["params"]

    def loss(impl, p):
        out = mods[impl].apply({"params": p}, x, mask)
        return jnp.mean(out**2)

    lx, gx = jax.value_and_grad(lambda p: loss("xla", p))(params)
    lp, gp = jax.value_and_grad(lambda p: loss("pallas_interpret", p))(params)
    np.testing.assert_allclose(float(lx), float(lp), rtol=1e-5)
    for (ka, a), (kb, b) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(gx), key=lambda t: str(t[0])),
        sorted(jax.tree_util.tree_leaves_with_path(gp), key=lambda t: str(t[0])),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=str(ka)
        )


def test_swinir_attn_impl_parity_with_shift():
    """Tiny SwinIR (includes shifted layers -> mask path) end to end."""
    r = np.random.default_rng(3)
    x = jnp.asarray(r.random((2, 16, 16, 3)), jnp.float32)
    kw = dict(depths=[2], embed_dim=12, num_heads=[2], window_size=4)
    m_x = SwinIR(attn_impl="xla", **kw)
    m_p = SwinIR(attn_impl="pallas_interpret", **kw)
    params = m_x.init(jax.random.key(0), x)["params"]

    def loss(m, p):
        return jnp.mean((m.apply({"params": p}, x) - 2.0 * x.repeat(2, 1).repeat(2, 2)) ** 2)

    lx, gx = jax.value_and_grad(lambda p: loss(m_x, p))(params)
    lp, gp = jax.value_and_grad(lambda p: loss(m_p, p))(params)
    np.testing.assert_allclose(float(lx), float(lp), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4
        )


def test_kernel_flagship_shape_parity():
    """Exact bench-config attention shape (n=64 tokens, 6 heads, d=10,
    wb=16) — the shape the chip will run; interpret mode, fwd + grads.
    bn=32 windows = two grid blocks, so the backward's cross-block dbias
    accumulation is exercised at this geometry too."""
    q, k, v = _qkv(bn=32, h=6, n=64, d=10, seed=4)
    r = np.random.default_rng(5)
    bias = jnp.asarray(r.standard_normal((6, 64, 64)), jnp.float32)

    def loss_p(q, k, v, bias):
        return jnp.sum(pwa.window_attention(q, k, v, bias, None, 16, True) ** 2)

    def loss_r(q, k, v, bias):
        return jnp.sum(_ref(q, k, v, bias, None) ** 2)

    lp, gp = jax.value_and_grad(loss_p, argnums=(0, 1, 2, 3))(q, k, v, bias)
    lr_, gr = jax.value_and_grad(loss_r, argnums=(0, 1, 2, 3))(q, k, v, bias)
    np.testing.assert_allclose(float(lp), float(lr_), rtol=1e-5)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("with_mask", [False, True])
def test_packed_matches_unpacked(with_mask):
    """pack=2 fuses window pairs into one 2n-token attention; outputs and
    every gradient (incl. the bias table path) must match pack=1."""
    q, k, v = _qkv(bn=8, h=3, n=16, d=6, seed=6)
    r = np.random.default_rng(7)
    bias = jnp.asarray(r.standard_normal((3, 16, 16)), jnp.float32)
    mask = None
    if with_mask:
        mask = jnp.asarray(
            np.where(r.random((4, 16, 16)) > 0.8, -100.0, 0.0), jnp.float32
        )

    def loss(fn):
        def wrapped(q, k, v, bias):
            return jnp.sum(fn(q, k, v, bias) ** 2)
        return wrapped

    f1 = loss(lambda q, k, v, b: pwa.window_attention(q, k, v, b, mask, 4, True))
    f2 = loss(
        lambda q, k, v, b: pwa.window_attention_packed(q, k, v, b, mask, 2, 2, True)
    )
    l1, g1 = jax.value_and_grad(f1, argnums=(0, 1, 2, 3))(q, k, v, bias)
    l2, g2 = jax.value_and_grad(f2, argnums=(0, 1, 2, 3))(q, k, v, bias)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b, name in zip(g1, g2, ["dq", "dk", "dv", "dbias"]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, err_msg=name
        )


def test_swinir_attn_pack_parity():
    """SwinIR(attn_impl='pallas_interpret', attn_pack=2) vs xla impl,
    including shifted layers (mask path)."""
    r = np.random.default_rng(8)
    x = jnp.asarray(r.random((2, 16, 16, 3)), jnp.float32)
    kw = dict(depths=[2], embed_dim=12, num_heads=[2], window_size=4)
    m_x = SwinIR(attn_impl="xla", **kw)
    m_p = SwinIR(attn_impl="pallas_interpret", attn_pack=2, **kw)
    params = m_x.init(jax.random.key(0), x)["params"]
    ox = m_x.apply({"params": params}, x)
    op = m_p.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op), atol=1e-4)
