"""ops/sparse_attn.py's kernels, interpreted, against the einsums: the
indexer's scores and their exact top-k (ties to the lower key), the core
over the selection and its gradients, the KL and its gradient into the
indexer."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributedtraining_tpu.ops import sparse_attn

T, BLK, TOPK = 96, 32, 16
H, KVH, D, HI, DI = 4, 1, 16, 2, 16


def _inputs(seed, dtype=jnp.float32, integer=False, t=T, kvh=KVH):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    if integer:  # small integers: every score exact, ties planted
        qi = jax.random.randint(keys[3], (HI, t, DI), -2, 3).astype(dtype)
        ki = jax.random.randint(keys[4], (t, DI), -2, 3).astype(dtype)
        w = jax.random.randint(keys[5], (HI, t), -2, 3).astype(jnp.float32)
    else:
        qi = jax.random.normal(keys[3], (HI, t, DI), dtype)
        ki = jax.random.normal(keys[4], (t, DI), dtype)
        w = jax.random.normal(keys[5], (HI, t), jnp.float32)
    q = jax.random.normal(keys[0], (H, t, D), dtype)
    k = jax.random.normal(keys[1], (kvh, t, D), dtype)
    v = jax.random.normal(keys[2], (kvh, t, D), dtype)
    return q, k, v, qi, ki, w


def _rows(w):
    """The kernels' layout of the weights: ``[T, HI]``."""
    return w.T


def dense_scores(qi, ki, w):
    z = jnp.einsum("htd,sd->hts", qi.astype(jnp.float32), ki.astype(jnp.float32))
    return jnp.sum(jax.nn.relu(z) * w[:, :, None], 0)


def dense_selection(scores, topk):
    t = scores.shape[0]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    mask = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], idx].set(True)
    return mask & causal


def dense_attention(q, k, v, qi, ki, w, mask):
    """(o, kl) over the mask, the KL's target held constant."""
    group = q.shape[0] // k.shape[0]
    kk, vv = (jnp.repeat(a.astype(jnp.float32), group, 0) for a in (k, v))
    s = jnp.einsum("htd,hsd->hts", q.astype(jnp.float32), kk) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("hts,hsd->htd", probs, vv)
    p = jax.lax.stop_gradient(jnp.mean(probs, 0))
    logq = jax.nn.log_softmax(jnp.where(mask, dense_scores(qi, ki, w), -jnp.inf), -1)
    logp = jnp.log(jnp.where(p > 0, p, 1.0))
    kl = jnp.sum(jnp.where(mask, p * (logp - logq), 0.0), -1)
    return o, kl


def _select(qi, ki, w, topk=TOPK):
    sel, lse_i = sparse_attn.sparse_select(
        qi, ki, _rows(w), topk=topk, blk=BLK, interpret=True
    )
    return sel, lse_i


@pytest.mark.parametrize("integer", [False, True], ids=["normal", "ties"])
def test_selection_is_the_exact_top_k(integer):
    _, _, _, qi, ki, w = _inputs(1, integer=integer)
    sel, lse_i = _select(qi, ki, w)
    got = np.asarray(sparse_attn.unpack_selection(sel))
    scores = dense_scores(qi, ki, w)
    want = np.asarray(dense_selection(scores, TOPK))
    np.testing.assert_array_equal(got, want)
    assert got.sum(1).tolist() == [min(t + 1, TOPK) for t in range(T)]
    assert int(sparse_attn.selected_keys(sel)) == got.sum()
    if integer:  # the planted ties reach the boundary on some rows
        kth = -np.sort(-np.where(want, scores, -np.inf), 1)[:, TOPK - 1]
        causal = np.tril(np.ones((T, T), bool))
        at_kth = (np.asarray(scores) == kth[:, None]) & causal
        assert (at_kth.sum(1) > (at_kth & want).sum(1))[TOPK:].any()
    lse_want = jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), -1)
    np.testing.assert_allclose(lse_i[:, 0], lse_want, rtol=1e-5, atol=1e-5)


def test_rows_shorter_than_topk_see_the_whole_causal_past():
    q, k, v, qi, ki, w = _inputs(2)
    sel, lse_i = _select(qi, ki, w, topk=T)
    o, _ = sparse_attn.sparse_attention(
        q, k, v, qi, ki, _rows(w), sel, lse_i, BLK, True
    )
    causal = jnp.tril(jnp.ones((T, T), bool))
    want, _ = dense_attention(q, k, v, qi, ki, w, causal)
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-4)


def test_core_and_kl_forward():
    q, k, v, qi, ki, w = _inputs(3)
    sel, lse_i = _select(qi, ki, w)
    o, kl = sparse_attn.sparse_attention(
        q, k, v, qi, ki, _rows(w), sel, lse_i, BLK, True
    )
    mask = sparse_attn.unpack_selection(sel)
    want_o, want_kl = dense_attention(q, k, v, qi, ki, w, mask)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-4, atol=1e-5)
    assert float(jnp.min(kl)) >= -1e-5 and float(jnp.max(kl)) > 0


@pytest.mark.parametrize("t", [96, 128])  # key tile 0's sums read back 2, 3 times
@pytest.mark.parametrize("topk", [16, 40])  # 40: rows 32-39 take their whole past
@pytest.mark.parametrize("kvh", [1, 2])
def test_gradients_of_both_outputs(kvh, topk, t):
    q, k, v, qi, ki, w = _inputs(4, t=t, kvh=kvh)
    sel, lse_i = _select(qi, ki, w, topk=topk)
    mask = sparse_attn.unpack_selection(sel)
    c_o = jax.random.normal(jax.random.PRNGKey(9), (H, t, D))
    c_kl = jax.random.uniform(jax.random.PRNGKey(10), (t,))

    def kernels(q, k, v, qi, ki, w):
        o, kl = sparse_attn.sparse_attention(
            q, k, v, qi, ki, _rows(w), sel, lse_i, BLK, True
        )
        return jnp.sum(o * c_o) + jnp.sum(kl * c_kl)

    def dense(q, k, v, qi, ki, w):
        o, kl = dense_attention(q, k, v, qi, ki, w, mask)
        return jnp.sum(o * c_o) + jnp.sum(kl * c_kl)

    args = (q, k, v, qi, ki, w)
    got = jax.grad(kernels, argnums=range(6))(*args)
    want = jax.grad(dense, argnums=range(6))(*args)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "w"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("t", [32, 64, 128])
def test_backward_under_the_tpu_interpreter(t):
    """The one backward pass under the interpreter that models the TPU's
    DMAs (each copy lands at its wait, fresh buffers read NaN): every key
    tile's float32 sums are read back only after their write is waited on,
    so the gradients are those of the plain interpreter to the bit."""
    q, k, v, qi, ki, w = (a.astype(jnp.bfloat16) for a in _inputs(11, t=t, kvh=2))
    w = _rows(w.astype(jnp.float32))
    sel, lse_i = sparse_attn.sparse_select(qi, ki, w, topk=TOPK, blk=BLK,
                                           interpret=True)
    o, lse = sparse_attn._core_forward(q, k, v, sel, blk=BLK, interpret=True)
    do = jax.random.normal(jax.random.PRNGKey(12), q.shape, q.dtype)
    gkl = jax.random.uniform(jax.random.PRNGKey(13), (t,))
    args = (q, k, v, qi, ki, w, sel, lse_i, o, lse, do, gkl)
    tpu = pltpu.InterpretParams(uninitialized_memory="nan")
    got = sparse_attn._backward(*args, blk=BLK, interpret=tpu)
    want = sparse_attn._backward(*args, blk=BLK, interpret=True)
    for name, a, b in zip(("dq", "dk", "dv", "dqi", "dki", "dw"), got, want):
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)

def test_output_gradient_never_reaches_the_indexer():
    q, k, v, qi, ki, w = _inputs(5)
    sel, lse_i = _select(qi, ki, w)

    def only_o(qi, ki, w):
        o, _ = sparse_attn.sparse_attention(
            q, k, v, qi, ki, _rows(w), sel, lse_i, BLK, True
        )
        return jnp.sum(o)

    for g in jax.grad(only_o, argnums=range(3))(qi, ki, w):
        assert not np.any(np.asarray(g))


def test_layer_layout_and_batch():
    """``sparse_attention_layer`` on a model's [B, T, heads, dim] is the
    single-sequence op mapped over the batch, and counts the keys."""
    b = 2
    parts = [_inputs(6 + i) for i in range(b)]
    stack = lambda n: jnp.stack([p[n] for p in parts])  # noqa: E731
    q, k, v, qi = (jnp.swapaxes(stack(n), 1, 2) for n in range(4))
    ki, w = stack(4), jnp.swapaxes(stack(5), 1, 2)
    o, kl, selection, lse_i = sparse_attn.sparse_attention_layer(
        q, k, v, qi, ki, w, topk=TOPK, blk=BLK, interpret=True
    )
    assert o.shape == q.shape and kl.shape == lse_i.shape == (b, T)
    selected = sparse_attn.selected_keys(selection)
    assert int(selected) == b * sum(min(t + 1, TOPK) for t in range(T))
    for n, p in enumerate(parts):
        sel, lse_i = _select(p[3], p[4], p[5])
        want_o, want_kl = sparse_attn.sparse_attention(
            p[0], p[1], p[2], p[3], p[4], _rows(p[5]), sel, lse_i, BLK, True
        )
        np.testing.assert_allclose(jnp.swapaxes(o[n], 0, 1), want_o, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(kl[n], want_kl, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("x", [0.0, -0.0, 1.5, -1.5, 3e-38, -3e-38, 1e30, -1e30])
def test_keys_keep_the_order_of_the_scores(x):
    xs = jnp.asarray([-2.0, -1e-3, -0.0, 0.0, 1e-3, 2.0, x], jnp.float32)
    keys = sparse_attn._key(xs)
    order = np.argsort(np.asarray(xs), kind="stable")
    assert np.all(np.diff(np.asarray(keys)[order]) >= 0)
    assert keys[2] == keys[3]  # -0 and +0 are one key
    np.testing.assert_array_equal(
        sparse_attn._unkey(keys)[np.asarray(xs) != 0], xs[np.asarray(xs) != 0]
    )
