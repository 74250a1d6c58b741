"""The held-experts layer moves only the rows that land
(``models/held_experts.py``): its two row primitives and the whole
``held_experts_sum`` against a dense reference that knows no buffer: every
token through every held expert, under a mask."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import held_experts as he

N, K, D, WIDTH = 40, 4, 32, 24
EXPERTS, HELD = 16, (2, 5, 11)
TILE = 16  # rows a loop step moves here: the buffer's 160 rows are 10 tiles
CASES = ("none", "one_row", "eighth", "ragged", "all_on_one")


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """The tile is read, and the buffers are made, when a jitted piece is
    traced: no trace made under a patch outlives it."""
    jitted = (he.find_landed, he.spread, he.gather_sum)
    for fn in jitted:
        fn.clear_cache()
    monkeypatch.setattr(he, "ROW_TILE", TILE)
    yield
    for fn in jitted:
        fn.clear_cache()


def poison_unwritten(monkeypatch):
    """Every buffer the layer allocates holds NaN until written."""
    monkeypatch.setattr(he, "_unwritten", lambda s, d: jnp.full(s, jnp.nan, d))
    for fn in (he.find_landed, he.spread, he.gather_sum):
        fn.clear_cache()  # traces made with the real allocation


def picks(case):
    """``sel`` [N, K] over the published experts, and how many of its
    assignments land on a held one."""
    rng = np.random.default_rng(7)
    absent = np.setdiff1d(np.arange(EXPERTS), HELD)
    sel = rng.choice(absent, (N, K))
    if case == "one_row":
        sel[17, 2] = HELD[1]
    elif case == "eighth":
        where = rng.random((N, K)) < 1 / 8
        sel[where] = rng.choice(HELD, int(where.sum()))
    elif case == "ragged":  # no multiple of the tile, several to a token
        flat = rng.permutation(N * K)[:37]
        sel.reshape(-1)[flat] = rng.choice(HELD, 37)
    elif case == "all_on_one":  # the worst case: the buffer is full
        sel[:] = HELD[0]
    landed = int(np.isin(sel, HELD).sum())
    assert landed == {"none": 0, "one_row": 1, "ragged": 37,
                      "all_on_one": N * K}.get(case, landed)
    if case in ("eighth", "ragged"):
        assert landed % TILE and landed > TILE
    return jnp.asarray(sel, jnp.int32), landed


def landed_of(sel):
    local = np.full((EXPERTS,), len(HELD), np.int32)
    local[list(HELD)] = np.arange(len(HELD))
    return he.find_landed(jnp.asarray(local)[sel.reshape(-1)], len(HELD), K)


def inputs(seed=0):
    kt, kw = jax.random.split(jax.random.PRNGKey(seed))
    tokens = jax.random.normal(kt, (N, D))
    weights = jax.nn.softmax(jax.random.normal(kw, (N, K)), -1)
    return tokens, weights


@pytest.mark.parametrize("case", CASES)
def test_spread_writes_the_landed_rows_and_no_other(case, monkeypatch):
    """Row ``r < L`` is its assignment's token; nothing is written from the
    end of L's tile on (the buffer is handed over holding NaN)."""
    poison_unwritten(monkeypatch)
    sel, count = picks(case)
    tokens, _ = inputs()
    landed = landed_of(sel)
    assert int(landed.count) == count
    rows = np.asarray(he.spread(tokens, landed))
    order = np.asarray(landed.order)
    np.testing.assert_array_equal(
        rows[:count], np.asarray(tokens)[order[:count] // K]
    )
    assert np.isin(np.asarray(sel).reshape(-1)[order[:count]], HELD).all()
    written = min(-(-count // TILE) * TILE, N * K)
    assert np.isnan(rows[written:]).all()


@pytest.mark.parametrize("case", CASES)
def test_gather_sum_is_each_tokens_weighted_sum_of_its_landed_rows(
    case, monkeypatch
):
    """Against a loop over the assignments, with every row past L poisoned:
    none of them meets the sum."""
    poison_unwritten(monkeypatch)
    sel, count = picks(case)
    _, weights = inputs()
    landed = landed_of(sel)
    rows = jax.random.normal(jax.random.PRNGKey(3), (N * K, D))
    rows = jnp.where((jnp.arange(N * K) < count)[:, None], rows, jnp.nan)
    want = np.zeros((N, D), np.float32)
    slot = np.asarray(landed.slot)
    mine = np.asarray(landed.rank).reshape(-1) >= 0
    for a in np.flatnonzero(mine):
        want[a // K] += np.asarray(weights).reshape(-1)[a] * np.asarray(
            rows[slot[a]]
        )
    got = he.gather_sum(rows, landed, weight=weights)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    ones = he.gather_sum(rows, landed)
    np.testing.assert_allclose(
        ones,
        he.gather_sum(rows, landed, weight=jnp.ones_like(weights)),
        rtol=1e-6,
    )


class Layer(nn.Module):
    """What a caller is: a Flax module that owns the experts' parameters."""

    @nn.compact
    def __call__(self, tokens, sel, weights):
        return he.held_experts_sum(
            self, tokens, sel, weights, he.expert_loads(sel, EXPERTS),
            held=HELD, width=WIDTH, gate=nn.silu,
            init=nn.initializers.normal(0.3), dtype=tokens.dtype,
            interpret=True,
        )


def dense_reference(params, tokens, sel, weights):
    """Every token through every held expert; a pick counts where it names
    the expert."""
    out = jnp.zeros_like(tokens)
    for i, expert in enumerate(HELD):
        y = (
            nn.silu(tokens @ params["experts_gate"][i])
            * (tokens @ params["experts_up"][i])
        ) @ params["experts_down"][i]
        out = out + jnp.sum(jnp.where(sel == expert, weights, 0), 1)[:, None] * y
    return out


def value_and_grads(fn, params, tokens, weights, sel):
    target = jax.random.normal(jax.random.PRNGKey(9), tokens.shape)
    return jax.value_and_grad(
        lambda p, t, w: jnp.sum(fn(p, t, sel, w) * target), argnums=(0, 1, 2)
    )(params, tokens, weights)


def layer_fn(params, tokens, sel, weights):
    return Layer().apply({"params": params}, tokens, sel, weights)


@pytest.fixture(scope="module")
def params():
    sel, _ = picks("eighth")
    return Layer().init(jax.random.PRNGKey(1), *inputs()[:1], sel,
                        inputs()[1])["params"]


@pytest.mark.parametrize("case", CASES)
def test_the_layer_is_the_dense_reference_in_value_and_every_gradient(
    case, params
):
    sel, count = picks(case)
    tokens, weights = inputs()
    out, counters = Layer().apply(
        {"params": params}, tokens, sel, weights, mutable=[he.MOE_COUNTERS]
    )
    assert float(counters[he.MOE_COUNTERS]["landed"]) == count
    assert float(counters[he.MOE_COUNTERS]["dropped"]) == 0.0
    np.testing.assert_allclose(
        out, dense_reference(params, tokens, sel, weights),
        rtol=2e-5, atol=2e-5,
    )
    got = value_and_grads(layer_fn, params, tokens, weights, sel)
    want = value_and_grads(dense_reference, params, tokens, weights, sel)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_the_layer_differentiates_under_remat_as_the_models_run_it(params):
    """Both sparse models rematerialise each layer: the hand-written rules'
    loops are staged out whole, never differentiated."""
    sel, _ = picks("ragged")
    tokens, weights = inputs()
    plain = value_and_grads(layer_fn, params, tokens, weights, sel)
    remat = value_and_grads(
        jax.checkpoint(layer_fn, static_argnums=()), params, tokens, weights,
        sel,
    )
    for a, b in zip(jax.tree.leaves(remat), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


def poisoned_grouped_matmul(real):
    """``grouped_matmul`` leaves the rows past its groups unwritten, forward
    and backward: here they are NaN, as the chip's memory may be."""

    def gmm(x, w, sizes, *, interpret=False):
        live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
        run = lambda a, b: real(a, b, sizes, interpret=interpret)  # noqa: E731

        @jax.custom_vjp
        def poisoned(x, w):
            return jnp.where(live, run(x, w), jnp.nan)

        def bwd(res, g):
            dx, dw = jax.vjp(run, *res)[1](g)
            return jnp.where(live, dx, jnp.nan), dw

        poisoned.defvjp(lambda x, w: (poisoned(x, w), (x, w)), bwd)
        return poisoned(x, w)

    return gmm


@pytest.mark.parametrize("case", ["one_row", "ragged"])
def test_no_unwritten_row_reaches_the_output_or_a_gradient(
    case, params, monkeypatch
):
    """Every buffer's rows past L hold NaN (the layer's own and the grouped
    matmuls', both directions): output and every gradient stay finite and
    are the clean run's to the bit."""
    sel, _ = picks(case)
    tokens, weights = inputs()
    clean = value_and_grads(layer_fn, params, tokens, weights, sel)
    poison_unwritten(monkeypatch)
    monkeypatch.setattr(
        he, "grouped_matmul", poisoned_grouped_matmul(he.grouped_matmul)
    )
    dirty = value_and_grads(layer_fn, params, tokens, weights, sel)
    for a, b in zip(jax.tree.leaves(dirty), jax.tree.leaves(clean)):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)


def test_a_traced_layer_says_its_routing_path():
    from pytorch_distributedtraining_tpu.observe import trace

    sel, _ = picks("eighth")
    tokens, weights = inputs()
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        jax.eval_shape(
            lambda: Layer().init(jax.random.PRNGKey(0), tokens, sel, weights)
        )
        said = [
            r["attrs"] for r in trace.records() if r["name"] == "routing.path"
        ]
    finally:
        trace.clear()
        tracer.enabled = was
    assert len(said) == 1
    assert said[0]["path"] == "jnp" and "landed" in said[0]["reason"]
    assert {key: said[0][key] for key in ("n", "k", "d", "tile")} == {
        "n": N, "k": K, "d": D, "tile": TILE,
    }
