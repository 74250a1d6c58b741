"""``ops/short_conv.py``: the gated short convolution of a conv mixer, the
Pallas pair (interpreted) and the ``jnp`` form, against a per-step loop and
against torch's ``Conv1d(groups=D, padding=L - 1)``, forward and backward;
causality; and the path it takes by platform and footprint."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.ops import short_conv as sc

FORMS = {
    "kernel": functools.partial(sc.short_conv_kernel, interpret=True),
    "jnp": sc.short_conv_jnp,
}
# (batch, T, D, taps): one and several 128-lane blocks, T no multiple of 8
SHAPES = [(1, 8, 128, 3), (2, 24, 256, 3), (3, 13, 128, 3), (2, 16, 384, 2)]


def draw(b, t, d, taps, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(keys[0], (b, t, 3 * d)),
        jax.random.normal(keys[1], (d, taps)),
        jax.random.normal(keys[2], (b, t, d)),
    )


def loop(bcx, w, g):
    """Step by step in float64: ``out`` and the vector-Jacobian product of
    ``sum(out * g)`` by hand (``dC``, ``dz`` from the taps read forward,
    ``dB``, ``dX``, the taps' gradient)."""
    bcx, w, g = (np.asarray(a, np.float64) for a in (bcx, w, g))
    d, taps = w.shape
    bb, cc, xx = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    t = bcx.shape[1]
    z = bb * xx
    y = np.zeros_like(z)
    for s in range(t):
        for k in range(taps):
            back = s - (taps - 1) + k
            if back >= 0:
                y[:, s] += w[:, k] * z[:, back]
    dy = g * cc
    dz = np.zeros_like(z)
    dw = np.zeros_like(w)
    for s in range(t):
        for k in range(taps):
            back = s - (taps - 1) + k
            if back >= 0:
                dz[:, back] += w[:, k] * dy[:, s]
                dw[:, k] += np.sum(dy[:, s] * z[:, back], 0)
    d_bcx = np.concatenate([dz * xx, g * y, dz * bb], -1)
    return cc * y, d_bcx, dw


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_forward_and_vjp_match_a_per_step_loop(form, shape):
    bcx, w, g = draw(*shape)
    fn = FORMS[form]
    out, vjp = jax.vjp(fn, bcx, w)
    d_bcx, dw = vjp(g)
    want, want_d_bcx, want_dw = loop(bcx, w, g)
    assert out.shape == g.shape and out.dtype == bcx.dtype
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(d_bcx, want_d_bcx, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(dw, want_dw, atol=2e-4, rtol=1e-5)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_bf16_operands_keep_float32_products(form):
    """bf16 in and out, the products and sums in float32: the result is the
    float32 form's rounded once."""
    bcx, w, _ = draw(2, 16, 256, 3, seed=4)
    low = FORMS[form](bcx.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    want = sc.short_conv_jnp(
        bcx.astype(jnp.bfloat16).astype(jnp.float32),
        w.astype(jnp.bfloat16).astype(jnp.float32),
    )
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(low, want.astype(jnp.bfloat16))


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("at", [0, 5, 11])
def test_a_step_moves_no_output_before_it(form, at):
    """Changing ``z_t`` (through ``B`` and ``X`` at step t) leaves every
    output before t as it was and moves step t and the two after it."""
    bcx, w, _ = draw(1, 16, 128, 3, seed=at)
    d = 128
    moved = bcx.at[:, at, :d].add(1.0).at[:, at, 2 * d:].add(-0.5)
    a, b = FORMS[form](bcx, w), FORMS[form](moved, w)
    np.testing.assert_array_equal(a[:, :at], b[:, :at])
    changed = np.abs(np.asarray(a - b)).max(-1)[0]
    assert all(changed[at:at + 3] > 1e-3)
    np.testing.assert_allclose(a[:, at + 3:], b[:, at + 3:], atol=1e-6)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_taps_are_torchs_conv1d(form):
    """``w`` is torch's ``Conv1d(D, D, 3, groups=D, padding=2)`` weight
    ``[D, 1, 3]`` squeezed, its first T outputs kept (``Lfm2ShortConv``):
    forward and both gradients through torch's autograd."""
    torch = pytest.importorskip("torch")
    b, t, d = 2, 12, 128
    bcx, w, g = draw(b, t, d, 3, seed=9)
    conv = torch.nn.Conv1d(d, d, 3, groups=d, padding=2, bias=False).double()
    with torch.no_grad():
        conv.weight.copy_(torch.from_numpy(np.asarray(w, np.float64))[:, None])
    tb = torch.tensor(np.asarray(bcx, np.float64), requires_grad=True)
    pb, pc, px = tb.transpose(-1, -2).chunk(3, dim=-2)
    out = (pc * conv(pb * px)[..., :t]).transpose(-1, -2)
    (out * torch.from_numpy(np.asarray(g, np.float64))).sum().backward()
    got, vjp = jax.vjp(FORMS[form], bcx, w)
    d_bcx, dw = vjp(g)
    np.testing.assert_allclose(got, out.detach().numpy(), atol=2e-5)
    np.testing.assert_allclose(d_bcx, tb.grad.numpy(), atol=2e-5)
    np.testing.assert_allclose(
        dw, conv.weight.grad.numpy()[:, 0], atol=2e-4, rtol=1e-5
    )


@pytest.mark.parametrize("t, d, taps, path", [
    (4096, 2048, 3, "kernel"), (64, 256, 3, "kernel"),
    (64, 96, 3, "jnp"), (64, 256, 9, "jnp"), (65536, 2048, 3, "jnp"),
])
def test_the_path_by_shape_and_footprint(t, d, taps, path):
    got, reason = sc.conv_path(t, d, taps, jnp.bfloat16)
    assert got == path and reason


def test_on_a_cpu_it_is_the_jnp_form_and_says_its_path(monkeypatch):
    """``short_conv`` stages the kernels for a TPU lowering and the ``jnp``
    form for any other: the CPU gets the latter to the bit; steered as a TPU
    lowering would be, the kernels (interpreted). Every trace leaves one
    ``short_conv.path`` instant."""
    from pytorch_distributedtraining_tpu.observe import trace

    bcx, w, g = draw(2, 16, 256, 3, seed=2)
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        cpu = jax.jit(sc.short_conv)(bcx, w)
        said = [r["attrs"] for r in trace.records()
                if r["name"] == "short_conv.path"]
    finally:
        trace.clear()
        tracer.enabled = was
    np.testing.assert_array_equal(cpu, sc.short_conv_jnp(bcx, w))
    assert said == [{
        "path": "kernel", "reason": said[0]["reason"], "b": 2, "t": 16,
        "d": 256, "taps": 3,
    }]
    assert "by platform" in said[0]["reason"]
    text = jax.jit(sc.short_conv).lower(bcx, w).as_text()
    assert "short_conv" in text
    monkeypatch.setattr(
        jax.lax, "platform_dependent", lambda *a, tpu, default: tpu(*a)
    )
    monkeypatch.setattr(sc, "short_conv_kernel", FORMS["kernel"])
    steered = jax.vjp(sc.short_conv, bcx, w)
    np.testing.assert_allclose(steered[0], cpu, atol=2e-5)
    for got, want in zip(steered[1](g), jax.vjp(sc.short_conv_jnp, bcx, w)[1](g)):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-5)
