"""Pallas flash attention vs XLA reference (interpret mode on CPU)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
from pytorch_distributedtraining_tpu.observe import trace
from pytorch_distributedtraining_tpu.ops import pallas_attn
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
    """The Pallas backward vs XLA AD across block shapes (bwd is
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


def _masked_einsum(q, k, v, causal, window):
    from pytorch_distributedtraining_tpu.models.smallthinker import (
        banded_attention,
    )

    if causal:
        return banded_attention(q, k, v, window=window)
    group = q.shape[2] // k.shape[2]
    return default_attention(
        q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2), causal=False
    )


# (window, group, causal, dtype): the causal window shorter than a block of
# 32, one block, several blocks, the whole sequence, None = the causal
# triangle; then the whole square, and bf16 operands
BAND_CASES = [
    *((w, g, True, jnp.float32) for g in (1, 7) for w in (None, 20, 32, 75, 128)),
    (None, 1, False, jnp.float32),
    (None, 7, False, jnp.float32),
    (None, 7, True, jnp.bfloat16),
    (75, 7, True, jnp.bfloat16),
    (None, 1, False, jnp.bfloat16),
]


@pytest.mark.parametrize("window,group,causal,dtype", BAND_CASES)
def test_band_and_grouped_heads_match_the_masked_einsum(
    window, group, causal, dtype
):
    """A causal window, the causal triangle or the whole square, and
    ``group`` query heads reading one key-value head where it lies: forward
    and the three gradients (dk and dv summed over the group) against the
    masked einsum in float32, at blocks that divide the band unevenly too
    and with several query and key blocks, so that the fused backward's dq
    is summed over the key-block axis and across the band's edges."""
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q, do = (jax.random.normal(k, (2, T, 2 * group, DH)) for k in keys[:2])
    k, v = (jax.random.normal(k, (2, T, 2, DH)) for k in keys[2:])
    # bf16 operands against float32 arithmetic: the operands' rounding
    atol = (2e-5, 1e-4) if dtype == jnp.float32 else (3e-2, 6e-2)
    want, want_vjp = jax.vjp(
        lambda *a: _masked_einsum(*a, causal, window), q, k, v
    )
    for bq, bk in ((32, 32), (64, 32), (32, 64)):
        out, vjp = jax.vjp(
            lambda *a: flash_attention(*a, causal, bq, bk, True, window),
            *(a.astype(dtype) for a in (q, k, v)),
        )
        assert out.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(out, np.float32), want, atol=atol[0]
        )
        for g, w in zip(vjp(do.astype(dtype)), want_vjp(do)):
            assert g.shape == w.shape
            np.testing.assert_allclose(
                np.asarray(g, np.float32), w, atol=atol[1]
            )


@pytest.mark.parametrize(
    "window,group,causal,dtype",
    [
        (None, 1, True, jnp.float32),
        (75, 7, True, jnp.float32),
        (None, 7, False, jnp.float32),
        (20, 1, True, jnp.bfloat16),
    ],
)
def test_the_fused_backward_is_the_two_kernels(
    monkeypatch, window, group, causal, dtype
):
    """One pass makes the dq, dk and dv the dq and dk/dv kernels make on
    the same inputs, to float32 rounding: each query block's dq is summed
    over the same key blocks in the same order."""
    keys = jax.random.split(jax.random.PRNGKey(38), 4)
    q, do = (
        jax.random.normal(k, (1, 2 * T, 2 * group, DH), dtype)
        for k in keys[:2]
    )
    k, v = (jax.random.normal(k, (1, 2 * T, 2, DH), dtype) for k in keys[2:])

    def grads():
        _, vjp = jax.vjp(
            lambda *a: flash_attention(*a, causal, 64, 32, True, window),
            q, k, v,
        )
        return vjp(do)

    fused = grads()
    monkeypatch.setattr(
        pallas_attn, "backward_path", lambda *a: ("two_kernels", "asked")
    )
    apart = grads()
    for f, a in zip(fused, apart):
        assert f.dtype == a.dtype == dtype
        f, a = np.asarray(f, np.float32), np.asarray(a, np.float32)
        ulp = 1e-6 if dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(f, a, rtol=ulp, atol=ulp * np.abs(a).max())


@contextlib.contextmanager
def _backward_said():
    """The ``path`` attributes, and the rest, of the ``flash.backward``
    instants that the traces made inside leave."""
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    said = []
    try:
        yield said
        said.extend(
            r["attrs"] for r in trace.records()
            if r["name"] == "flash.backward"
        )
    finally:
        trace.clear()
        tracer.enabled = was


def _packed_core(core, heads, dh, bq, bk, interpret):
    """``(fn, last dims of its arguments, of its output)`` for a packed
    core: ``qkv`` as the fused projection wrote it, or q, k, v padded to
    whole lane blocks."""
    if core == "qkv":
        return (
            lambda qkv: pallas_attn.flash_attention_qkv(
                qkv, heads, bq, bk, interpret
            ),
            [3 * heads * dh], heads * dh,
        )
    lanes = -(-heads * dh // 128) * 128
    return (
        lambda q, k, v: pallas_attn.flash_attention_lanes(
            q, k, v, dh, bq, bk, interpret
        ),
        [lanes] * 3, lanes,
    )


@pytest.mark.parametrize(
    "family,t,dh,path,calls",
    [
        pytest.param("split_head", 16384, 128, "fused", 2, id="128-fused-2"),
        pytest.param(
            "split_head", 16384, 256, "two_kernels", 3, id="256-two_kernels-3"
        ),
        ("packed", 1024, 64, "fused", 2),
        ("packed", 16384, 64, "two_kernels", 3),
    ],
)
def test_the_backward_path_follows_its_vmem_footprint(
    family, t, dh, path, calls
):
    """At blocks of 512 the fused backward's footprint (Q, dO and dQ whole,
    dQ's float32 accumulator, the statistics of a block's heads) fits the
    ceiling for split heads at T = 16,384 and head size 128 (73 MiB) and not
    at 256 (106 MiB), where the two kernels, which need 74, still run; for
    two packed heads of 64 at T = 1,024 (15 MiB) and not at 16,384 (105
    MiB; the two kernels 89). The choice, the instant ``flash.backward``
    each trace of the backward rule leaves, and the kernels the gradient
    holds (forward + 1, or forward + 2) agree. Traced, not run."""
    block = 512
    if family == "split_head":
        hb, lanes, window = 1, dh, 4096
        fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, True, block, block, False, window
        )
        widths = [(2, dh)] * 3
    else:
        hb, lanes, window = 128 // dh, 128, None
        fn, widths, _ = _packed_core("qkv", 2, dh, block, block, False)
        widths = [(w,) for w in widths]
    args = [jax.ShapeDtypeStruct((1, t, *w), jnp.bfloat16) for w in widths]

    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32))

    with _backward_said() as said:
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)[:len(args)]))(
            *args
        )
    assert pallas_attn.backward_path(
        t, lanes, block, block, jnp.bfloat16, hb
    )[0] == path
    assert [s["path"] for s in said] == [path]
    assert said[0]["t"] == t and said[0]["dh"] == dh
    assert said[0]["window"] == window
    packed = family == "packed"
    assert said[0].get("family") == ("packed" if packed else None)
    assert said[0].get("hb") == (hb if packed else None)
    assert "MiB" in said[0]["reason"]
    assert str(jaxpr).count("pallas_call") == calls


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "core,heads,dh",
    [("qkv", 4, 64), ("lanes", 5, 64), ("qkv", 2, 128), ("lanes", 3, 128)],
)
@pytest.mark.parametrize("bq,bk", [(64, 32), (32, 64)])
def test_the_fused_packed_backward_is_the_two_kernels(
    monkeypatch, core, heads, dh, dtype, bq, bk
):
    """The packed family's one pass makes the dq, dk and dv its dq and dk/dv
    kernels make, to the bit: two heads of 64 a lane block read out of
    ``qkv`` where they lie, an odd count padded to lanes with a head of
    zeros behind (GPT-2 XL's layout), a head of 128 a block (a scale that is
    no power of two), with several query and key blocks each way."""
    fn, widths, width = _packed_core(core, heads, dh, bq, bk, True)
    keys = jax.random.split(jax.random.PRNGKey(40), len(widths) + 1)
    args = [
        jax.random.normal(k, (2, 256, w), dtype) for k, w in zip(keys, widths)
    ]
    do = jax.random.normal(keys[-1], (2, 256, width), dtype)

    def grads():
        with _backward_said() as said:
            _, vjp = jax.vjp(fn, *args)
            out = vjp(do)
        return out, [s["path"] for s in said]

    fused, said = grads()
    assert said == ["fused"]
    monkeypatch.setattr(
        pallas_attn, "backward_path", lambda *a: ("two_kernels", "asked")
    )
    apart, said = grads()
    assert said == ["two_kernels"]
    for f, a in zip(fused, apart):
        assert f.dtype == a.dtype == dtype
        np.testing.assert_array_equal(np.asarray(f), np.asarray(a))


def test_a_window_is_causal_and_heads_must_divide(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, False, 32, 32, True, 16)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(
            jnp.concatenate([q, q[:, :, :1]], 2), k, v, True, 32, 32, True
        )
