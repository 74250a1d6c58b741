"""The fused window-attention kernel vs the einsum path (interpret mode).

``window_attention_qkv`` is the whole core between `models/swinir.py:
WindowAttention`'s projections: ``qkv [bn, n, 3c]`` in, ``[bn, n, c]`` out.
Same parameters, same outputs, same gradients as the einsums — including
the relative-position-bias gradient the backward kernel accumulates across
the window grid — and a contract that says which shapes it takes.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pytorch_distributedtraining_tpu.models.swinir import (
    SwinIR,
    WindowAttention,
    _einsum_core,
)
from pytorch_distributedtraining_tpu.observe import trace
from pytorch_distributedtraining_tpu.ops import pallas_window_attn as pwa


def _inputs(bn, n, c, heads, nw, dtype, seed=0):
    r = np.random.default_rng(seed)
    qkv = jnp.asarray(r.standard_normal((bn, n, 3 * c)), dtype)
    bias = jnp.asarray(0.5 * r.standard_normal((heads, n, n)), jnp.float32)
    mask = None
    if nw is not None:
        mask = jnp.asarray(
            np.where(r.random((nw, n, n)) > 0.8, -100.0, 0.0), jnp.float32
        )
    weight = jnp.asarray(r.standard_normal((bn, n, c)), jnp.float32)
    return qkv, bias, mask, weight


def _fwd_and_grads(fn, qkv, bias, mask, weight):
    """Output, d qkv and d bias of ``fn`` under a fixed random cotangent."""

    def loss(qkv, bias):
        out = fn(qkv, bias, mask)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    )(qkv, bias)
    return (out, *grads)


def _kernel(qkv, bias, mask):
    return pwa.window_attention_qkv(qkv, bias, mask, True)


def _assert_matches_einsum(bn, n, c, heads, nw, dtype, seed=0):
    qkv, bias, mask, weight = _inputs(bn, n, c, heads, nw, dtype, seed)
    einsum = lambda a, b, m: _einsum_core(a, b, m, dtype)  # noqa: E731
    got = _fwd_and_grads(_kernel, qkv, bias, mask, weight)
    ref = _fwd_and_grads(einsum, qkv, bias, mask, weight)
    # bf16: both paths round the same products to 8 bits at different
    # points; two ulps of the largest value is the room between them
    tol = 2e-5 if dtype == jnp.float32 else 2**-6
    for a, b, name in zip(got, ref, ["out", "dqkv", "dbias"]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(
            a, b, atol=tol * max(1.0, np.abs(b).max()), err_msg=name
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("nw", [None, 8], ids=["no_mask", "shift_mask"])
def test_kernel_matches_einsum_fwd_and_grads(nw, dtype):
    """Forward, d qkv and d bias at a small shape, two images of 8 windows
    (so a block's masks are taken modulo the image)."""
    _assert_matches_einsum(16, 16, 12, 3, nw, dtype)


@pytest.mark.parametrize("nw", [None, 64], ids=["no_mask", "shift_mask"])
def test_kernel_cell_shape_two_blocks(nw):
    """The benchmark cells' shape ([1152, 64, 180] float32: 64-token
    windows, 6 heads of 10, 64 windows an image) cut to its smallest
    block-complete size: 128 windows are two grid steps of 64, so the
    backward's d bias is summed across blocks and the second block's masks
    start again at the image's first window."""
    assert pwa.block_windows(1152, nw, 64, 60, jnp.float32) == 64
    assert pwa.block_windows(128, nw, 64, 60, jnp.float32) == 64
    _assert_matches_einsum(128, 64, 60, 6, nw, jnp.float32, seed=4)


def test_kernel_head_size_30():
    """Classical SwinIR-M: embed 180, 6 heads of 30, with the shift mask."""
    _assert_matches_einsum(16, 64, 180, 6, 16, jnp.float32, seed=5)


@pytest.mark.parametrize(
    "bn, n, c, heads, nw, dtype, why",
    [
        (67, 64, 60, 6, None, jnp.float32, "leaves blocks of 1"),
        (2 * 127, 64, 60, 6, 127, jnp.float32, "leaves blocks of 1"),
        (64, 49, 60, 6, None, jnp.float32, "not a multiple of 8"),
        (64, 72, 60, 6, None, jnp.bfloat16, "not a multiple of 16"),
        (96, 64, 60, 6, 64, jnp.float32, "not whole images"),
        (64, 64, 60, 6, None, jnp.float16, "neither float32 nor bfloat16"),
        (64, 128, 192, 48, None, jnp.float32, "MiB of VMEM"),
    ],
)
def test_contract_refuses(bn, n, c, heads, nw, dtype, why):
    """Shapes the kernel does not take are named by the contract, and the
    kernel raises on them rather than computing something else."""
    assert why in pwa.kernel_contract(bn, n, c, heads, nw, dtype)
    qkv, bias, mask, _ = _inputs(bn, n, c, heads, nw, dtype)
    with pytest.raises(ValueError, match=why):
        pwa.window_attention_qkv(qkv, bias, mask, True)


@pytest.mark.parametrize("vmem_mib, takes", [(128, True), (64, True), (16, False)])
def test_contract_follows_the_vmem_of_the_tpu_it_sees(monkeypatch, vmem_mib, takes):
    """The ceiling is the visible TPU's VMEM less a margin, a v5e's where
    the process sees none: the cells' shape (49 MiB of blocks and
    temporaries) fits 128 and 64 MiB, and on a TPU with 16 falls outside
    the contract, so the default path computes it by the einsums."""
    import types

    monkeypatch.setattr(
        pwa.pltpu, "get_tpu_info",
        lambda: types.SimpleNamespace(vmem_capacity_bytes=vmem_mib * 2**20),
    )
    pwa._vmem_ceiling.cache_clear()
    try:
        why = pwa.kernel_contract(1152, 64, 60, 6, 64, jnp.float32)
    finally:
        monkeypatch.undo()
        pwa._vmem_ceiling.cache_clear()
    assert (why is None) == takes, why
    assert takes or "MiB of VMEM" in why
    assert pwa._vmem_ceiling() == 100 * 2**20  # no TPU here: the v5e's


@pytest.mark.parametrize(
    "bn, nw, want",
    [(1152, 64, 64), (1152, None, 64), (36, 36, 36), (18, 9, 9), (50, 25, 25),
     (8, 4, 4), (3 * 121, 121, 11)],
)
def test_block_shrinks_to_a_divisor(bn, nw, want):
    """Window counts that do not divide the block shrink the block: to a
    divisor of the window count and, under a mask, of one image."""
    assert pwa.kernel_contract(bn, 64, 60, 6, nw, jnp.float32) is None
    assert pwa.block_windows(bn, nw, 64, 60, jnp.float32) == want


@pytest.mark.parametrize(
    "wb, c, want", [(64, 60, 8), (36, 60, 4), (18, 60, 2), (9, 60, 1), (16, 180, 4)]
)
def test_windows_in_flight_follow_the_block_and_vmem(wb, c, want):
    """The kernel interleaves as many windows an iteration as divide the
    block and fit VMEM: eight at the cells' shape, four at head size 30."""
    assert pwa.windows_in_flight(wb, 64, c, 6) == want


def _instants():
    return [
        r["attrs"] for r in trace.records()
        if r["name"] == "window_attention.path"
    ]


@pytest.fixture
def telemetry():
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    yield
    trace.clear()
    tracer.enabled = was


def test_default_falls_back_on_a_window_count_with_no_block(telemetry):
    """67 windows (a prime past the largest block) are legal input: the
    default path computes them by the einsums and says so; 'pallas' is an
    arm that must run the kernel, so it raises."""
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((67, 16, 12)), jnp.float32
    )
    auto = WindowAttention(12, 3, 4)
    params = auto.init(jax.random.key(0), x)["params"]
    trace.clear()
    got = auto.apply({"params": params}, x)
    (said,) = _instants()
    assert said["path"] == "einsum" and "blocks of 1" in said["reason"]
    assert (said["bn"], said["n"], said["c"], said["heads"]) == (67, 16, 12, 3)
    ref = WindowAttention(12, 3, 4, attn_impl="xla").apply({"params": params}, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError, match="blocks of 1"):
        WindowAttention(12, 3, 4, attn_impl="pallas_interpret").apply(
            {"params": params}, x
        )


def test_module_pallas_impl_matches_xla(telemetry):
    """Same Flax params, both impls, identical outputs + parameter grads."""
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((8, 16, 12)), jnp.float32)
    mask = None  # module-level mask parity is covered by the SwinIR test
    mods = {
        impl: WindowAttention(12, 3, 4, attn_impl=impl)
        for impl in ("xla", "pallas_interpret")
    }
    params = mods["xla"].init(jax.random.key(0), x, mask)["params"]
    assert _instants() == []  # the named reference is no decision

    def loss(impl, p):
        out = mods[impl].apply({"params": p}, x, mask)
        return jnp.mean(out**2)

    lx, gx = jax.value_and_grad(lambda p: loss("xla", p))(params)
    lp, gp = jax.value_and_grad(lambda p: loss("pallas_interpret", p))(params)
    assert [s["path"] for s in _instants()] == ["kernel"]
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
