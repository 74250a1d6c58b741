"""GLM-4.7-Flash (``models/glm4_moe_lite.py``) against its plain float32
reference at a tiny size on seeded random weights, and the properties the
expert layer and latent attention promise."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import (
    Glm4MoeLite, Glm4MoeLiteConfig, cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.models import glm4_moe_lite as glm
from pytorch_distributedtraining_tpu.models import (
    glm4_moe_lite_reference as reference,
)
from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
from pytorch_distributedtraining_tpu.ops.grouped_matmul import grouped_matmul
from pytorch_distributedtraining_tpu.ops.pallas_attn import (
    flash_attention, make_flash_attn_fn,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = {"all": None, "some": (1, 2, 5), "one": (6,)}


def arch_of(cfg):
    return {
        "layers": cfg.num_hidden_layers, "first_dense": cfg.first_k_dense_replace,
        "heads": cfg.num_attention_heads, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "v_dim": cfg.v_head_dim,
        "kv_rank": cfg.kv_lora_rank, "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor, "norm_topk": cfg.norm_topk_prob,
        "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta, "held": cfg.held,
    }


def build(held, seed=0, **kw):
    """Model, parameters, a router state with biases that matter, a batch."""
    cfg = Glm4MoeLiteConfig.tiny(held_experts=HELD[held], **kw)
    model = Glm4MoeLite(cfg, interpret=True)
    tok = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 17))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    variables = model.init(jax.random.PRNGKey(seed), x)
    state = jax.tree.map(
        lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(7), b.shape),
        {glm.ROUTER_STATE: variables[glm.ROUTER_STATE]},
    )
    return cfg, model, variables["params"], state, x, y


@pytest.mark.parametrize("held", sorted(HELD))
def test_logits_match_the_reference(held):
    cfg, model, params, state, x, _ = build(held)
    logits = model.apply({"params": params, **state}, x)
    want = reference.forward(
        params, state[glm.ROUTER_STATE], x, arch_of(cfg), chunk=8
    )
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=2e-6)


@pytest.mark.parametrize("held", sorted(HELD))
def test_loss_and_every_gradient_leaf_match_the_reference(held):
    cfg, model, params, state, x, y = build(held)
    loss, grads = jax.value_and_grad(
        lambda p: cross_entropy_loss(model.apply({"params": p, **state}, x), y)
    )(params)
    want, want_grads = reference.loss_and_grads(
        params, state[glm.ROUTER_STATE], x, y, arch_of(cfg), chunk=8
    )
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == len(want_flat) > 40
    for path, g in flat:
        w = want_flat[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-4, (
            jax.tree_util.keystr(path)
        )


def expert_layer_out(cfg, params, bias, x):
    layer = glm.ExpertLayer(cfg, interpret=True)
    return layer.apply(
        {"params": params, glm.ROUTER_STATE: {"bias": bias}}, x
    )


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two of sixteen experts each; their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are what the uncut reference gives for the whole layer."""
    whole = Glm4MoeLiteConfig.tiny(n_routed_experts=16, num_experts_per_tok=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.hidden_size))
    params = glm.ExpertLayer(whole, interpret=True).init(
        jax.random.PRNGKey(2), x
    )["params"]
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    flat = x.reshape(-1, whole.hidden_size)
    shared = reference.gated_mlp(flat, params["mlp_shared"]).reshape(x.shape)
    total = shared
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        mine = dict(params, **{
            k: params[k][jnp.asarray(held)]
            for k in ("experts_gate", "experts_up", "experts_down")
        })
        cfg = Glm4MoeLiteConfig.tiny(
            n_routed_experts=16, num_experts_per_tok=4, held_experts=held
        )
        total = total + expert_layer_out(cfg, mine, bias, x) - shared
    uncut = reference.expert_layer(
        flat, params, bias, dict(arch_of(whole), held=tuple(range(16)))
    ).reshape(x.shape)
    np.testing.assert_allclose(total, uncut, atol=2e-6)


def test_nothing_is_dropped_when_every_token_lands_on_one_held_expert():
    """The bias sends all of every token's picks to the two held experts:
    the buffer is full (N x k rows), nothing is dropped, and the result is
    the reference's."""
    cfg = Glm4MoeLiteConfig.tiny(held_experts=(3, 4))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.hidden_size))
    layer = glm.ExpertLayer(cfg, interpret=True)
    params = layer.init(jax.random.PRNGKey(5), x)["params"]
    bias = jnp.zeros((8,)).at[jnp.asarray([3, 4])].set(10.0)
    out, sown = layer.apply(
        {"params": params, glm.ROUTER_STATE: {"bias": bias}}, x,
        mutable=[glm.MOE_COUNTERS],
    )
    counters = sown[glm.MOE_COUNTERS]
    n = x.shape[0] * x.shape[1]
    assert float(counters["landed"]) == n * cfg.num_experts_per_tok
    assert float(counters["rows_max"]) == n == float(counters["rows_mean"])
    assert float(counters["dropped"]) == 0.0
    want = reference.expert_layer(
        x.reshape(n, -1), params, bias, arch_of(cfg)
    ).reshape(x.shape)
    np.testing.assert_allclose(out, want, atol=2e-6)


def test_the_bias_selects_and_never_weighs_and_takes_no_gradient():
    cfg = Glm4MoeLiteConfig.tiny()
    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(6), (64, cfg.n_routed_experts))
    )
    bias = jnp.zeros((cfg.n_routed_experts,)).at[7].set(5.0)
    sel0, _ = glm.route(scores, jnp.zeros_like(bias), cfg)
    sel, w = glm.route(scores, bias, cfg)
    assert bool(jnp.all(jnp.any(sel == 7, -1)))  # the bias chose
    assert not bool(jnp.all(jnp.any(sel0 == 7, -1)))
    picked = jnp.take_along_axis(scores, sel, -1)  # the scores weigh
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor,
        rtol=1e-6,
    )
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling_factor, rtol=1e-6)

    _, model, params, state, x, y = build("some")
    g = jax.grad(
        lambda s: cross_entropy_loss(model.apply({"params": params, **s}, x), y)
    )(state)
    assert all(float(jnp.max(jnp.abs(b))) == 0.0 for b in jax.tree.leaves(g))


def test_the_bias_moves_against_the_load_and_counters_are_sown():
    cfg, model, params, state, x, _ = build("some")
    _, new = model.apply(
        {"params": params, **state}, x,
        mutable=[glm.ROUTER_STATE, glm.MOE_COUNTERS],
    )
    for name, layer in new[glm.ROUTER_STATE].items():
        step = layer["moe"]["bias"] - state[glm.ROUTER_STATE][name]["moe"]["bias"]
        size = np.abs(np.asarray(step))
        assert np.all(
            np.isclose(size, 0.0, atol=1e-7)
            | np.isclose(size, cfg.bias_update_rate, atol=1e-7)
        )
        assert size.max() > 0
    counters = glm.routing_counters(new[glm.MOE_COUNTERS])
    assert float(counters["dropped_assignments"]) == 0.0
    assert float(counters["expert_load_max_over_mean"]) >= 1.0
    assert 0 < float(counters["assignments_landed"]) <= 2 * x.size * 2
    # with the parameters alone the biases read zero and nothing is kept
    bare = model.apply({"params": params}, x)
    zero = jax.tree.map(jnp.zeros_like, state)
    np.testing.assert_allclose(
        bare, model.apply({"params": params, **zero}, x), atol=1e-6
    )


def test_mla_has_one_rotary_key_for_all_heads():
    cfg = Glm4MoeLiteConfig.tiny(num_attention_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, cfg.hidden_size))
    mla = glm.MLA(cfg, default_attention)
    params = mla.init(jax.random.PRNGKey(9), x)["params"]
    q, k, v = mla.apply({"params": params}, x, method="qkv")
    nope = cfg.qk_nope_head_dim
    assert q.shape == k.shape == (2, 10, 4, cfg.qk_head_dim)
    assert v.shape == (2, 10, 4, cfg.v_head_dim)
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, nope:], k[:, :, 0, nope:])
        assert not np.allclose(k[:, :, head, :nope], k[:, :, 0, :nope])
        assert not np.allclose(q[:, :, head, nope:], q[:, :, 0, nope:])
    # the latent projection has ONE rotary key's width beside the latent
    assert params["kv_a_proj_with_mqa"]["kernel"].shape == (
        cfg.hidden_size, cfg.kv_lora_rank + cfg.qk_rope_head_dim
    )
    want = reference.mla_qkv(x, params, arch_of(cfg))
    for got, ref in zip((q, k, v), want):
        np.testing.assert_allclose(got, ref, atol=2e-6)


def test_the_attention_the_cell_calls_at_head_size_256():
    """``make_flash_attn_fn`` at the cell's block (512, cut to T here)
    against XLA's attention at head size 256, forward and gradients."""
    keys = jax.random.split(jax.random.PRNGKey(10), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 256, 2, 256)) for kk in keys)
    attn = make_flash_attn_fn(bq=512, bk=512, interpret=True)
    out, vjp = jax.vjp(lambda *a: attn(*a, causal=True), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: default_attention(*a, causal=True), q, k, v
    )
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, w in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(g, w, atol=2e-4)


def test_the_model_calls_the_blockwise_kernel_unless_told_otherwise():
    """``Glm4MoeLite`` with no ``attn_fn`` runs ``ops/pallas_attn.py`` (one
    kernel a layer more than with XLA's attention, which it matches)."""
    cfg, model, params, state, x, _ = build("some")
    xla = Glm4MoeLite(cfg, default_attention, interpret=True)
    run = lambda m: lambda p: m.apply({"params": p, **state}, x)  # noqa: E731
    np.testing.assert_allclose(run(model)(params), run(xla)(params), atol=2e-6)
    kernels = lambda m: str(jax.make_jaxpr(run(m))(params)).count(  # noqa: E731
        "pallas_call"
    )
    assert kernels(model) - kernels(xla) == cfg.num_hidden_layers


def test_every_traced_expert_layer_says_its_routing_path():
    """One ``routing.path`` instant a traced expert layer (the first layer
    is dense), with the shapes the held-experts layer adapts on."""
    from pytorch_distributedtraining_tpu.models import held_experts
    from pytorch_distributedtraining_tpu.observe import trace

    tracer = trace.get_tracer()
    was = tracer.enabled
    cfg, model, params, state, x, _ = build("some")
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        jax.make_jaxpr(lambda p: model.apply({"params": p, **state}, x))(params)
        said = [
            r["attrs"] for r in trace.records() if r["name"] == "routing.path"
        ]
    finally:
        trace.clear()
        tracer.enabled = was
    assert len(said) == cfg.num_hidden_layers - cfg.first_k_dense_replace
    n, k = x.size, cfg.num_experts_per_tok
    assert {(a["path"], a["n"], a["k"], a["d"], a["tile"]) for a in said} == {
        ("jnp", n, k, cfg.hidden_size, min(held_experts.ROW_TILE, n * k))
    }
    assert all("landed rows" in a["reason"] for a in said)


def test_the_probe_gives_each_expert_layers_input_scores_picks_and_output():
    """``mutable=["moe_probe"]``: what a reference needs to be held against
    one expert layer alone; nothing of it without the asking."""
    cfg, model, params, state, x, _ = build("some")
    assert not isinstance(model.apply({"params": params, **state}, x), tuple)
    _, new = model.apply(
        {"params": params, **state}, x, mutable=[glm.MOE_PROBE]
    )
    layers = new[glm.MOE_PROBE]
    assert sorted(layers) == ["layers_1", "layers_2"]  # layer 0 is dense
    arch, n = arch_of(cfg), x.size
    for name, layer in layers.items():
        got, p = layer["moe"], params[name]["moe"]
        bias = state[glm.ROUTER_STATE][name]["moe"]["bias"]
        assert got["input"].shape == got["output"].shape == (n, cfg.hidden_size)
        with jax.default_matmul_precision("highest"):
            scores = reference.router_scores(got["input"], p["router"])
            sel, _ = reference.route(got["input"], p["router"], bias, arch)
            want = reference.expert_layer(got["input"], p, bias, arch)
        np.testing.assert_allclose(got["scores"], scores, atol=1e-6)
        np.testing.assert_array_equal(np.sort(got["picks"]), np.sort(sel))
        np.testing.assert_allclose(got["output"], want, atol=2e-6)


def test_flash_attention_refuses_a_sequence_its_blocks_cannot_hold():
    q = jax.ShapeDtypeStruct((1, 131072, 1, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, True, 512, 512, False),
            q, q, q,
        )


@pytest.mark.parametrize("sizes", [(5, 0, 9, 2), (0, 0, 0, 16), (3, 4, 5, 20)])
def test_grouped_matmul_matches_a_loop_over_the_groups(sizes):
    """Rows sorted by group, an empty group, rows past the groups: the
    result, and both gradients, are the loop's on the rows that exist."""
    sizes = jnp.asarray(sizes, jnp.int32)
    m, k, n = 32, 16, 24
    x = jax.random.normal(jax.random.PRNGKey(11), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(12), (4, k, n))
    dy = jax.random.normal(jax.random.PRNGKey(13), (m, n))
    ends = jnp.cumsum(sizes)
    rows = jnp.arange(m)
    member = (rows[None, :] >= (ends - sizes)[:, None]) & (
        rows[None, :] < ends[:, None]
    )  # [G, M]
    valid = member.any(0)[:, None]

    def loop(x, w):
        return sum(
            jnp.where(member[g][:, None], x @ w[g], 0.0) for g in range(4)
        )

    def kernel(x, w):
        out = grouped_matmul(x, w, sizes, interpret=True)
        return jnp.where(valid, out, 0.0)  # rows past the groups: unwritten

    out, vjp = jax.vjp(kernel, x, w)
    want, want_vjp = jax.vjp(loop, x, w)
    np.testing.assert_allclose(out, want, atol=1e-5)
    for g, wg in zip(vjp(dy), want_vjp(dy)):
        np.testing.assert_allclose(jnp.where(
            valid, g, 0.0) if g.shape == x.shape else g, wg, atol=1e-4)


def test_the_two_copies_of_the_reference_are_one_text():
    assert filecmp.cmp(
        os.path.join(REPO, "chipbench", "reference", "glm4_moe_lite.py"),
        os.path.join(
            REPO, "pytorch_distributedtraining_tpu", "models",
            "glm4_moe_lite_reference.py",
        ),
        shallow=False,
    )


@pytest.mark.parametrize("accum", [1, 2])
def test_router_state_and_counters_ride_the_train_step(accum):
    """The selection bias is ``model_state``: it moves by one rate a step
    (under ``grad_accum`` too: every microbatch routes by the step's bias,
    the last one's loads move it), takes no optimizer update, and the
    routing counters come back as metrics."""
    from pytorch_distributedtraining_tpu import optim, parallel
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    cfg, model, _, _, x, y = build("some")
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-3, clip_grad_norm=1.0)

    def init_fn(rng):
        v = model.init(rng, x)
        return v["params"], {glm.ROUTER_STATE: v[glm.ROUTER_STATE]}

    def loss_fn(params, batch, rng, model_state):
        logits, new = model.apply(
            {"params": params, **model_state}, batch[0],
            mutable=[glm.ROUTER_STATE, glm.MOE_COUNTERS],
        )
        return cross_entropy_loss(logits, batch[1]), {
            "model_state": {glm.ROUTER_STATE: new[glm.ROUTER_STATE]},
            **glm.routing_counters(new[glm.MOE_COUNTERS]),
        }

    state, shardings = parallel.create_train_state(
        init_fn=init_fn, tx=tx, mesh=mesh, policy=parallel.DDP(),
        rng=jax.random.PRNGKey(0),
    )
    step = parallel.TrainStep(
        loss_fn, tx, mesh, parallel.DDP(), state_shardings=shardings,
        grad_accum_steps=accum,
    )
    losses = []
    with mesh:
        for _ in range(3):
            state, metrics = step(state, (x, y))
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert float(metrics["dropped_assignments"]) == 0.0
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0
    for layer in state.model_state[glm.ROUTER_STATE].values():
        bias = np.asarray(layer["moe"]["bias"])
        assert 0 < np.max(np.abs(bias)) <= 3 * cfg.bias_update_rate + 1e-9


def test_rematerialised_layers_run_each_attention_kernel_once(monkeypatch):
    """Under ``remat=True`` a layer keeps its attention kernel's output and
    row statistics: the gradient holds the kernels of the plain model plus
    the rematerialised grouped matmuls, and no second attention forward. A
    forward rule that did not name its residuals ran one more a layer."""
    from pytorch_distributedtraining_tpu.ops import pallas_attn

    def kernels(remat):
        cfg, model, params, state, x, y = build("some", remat=remat)
        return cfg.num_hidden_layers, str(jax.make_jaxpr(jax.grad(
            lambda p: cross_entropy_loss(model.apply({"params": p, **state}, x), y)
        ))(params)).count("pallas_call[")

    layers, plain = kernels(False)
    _, kept = kernels(True)
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    _, untagged = kernels(True)
    assert untagged - kept == layers
    # what is still rematerialised: the expert layers' forward kernels
    assert kept > plain
