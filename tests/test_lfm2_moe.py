"""LFM2-8B-A1B (``models/lfm2_moe.py``) against its plain float32 reference at
a tiny size on seeded random weights, and what its layers promise: conv and
attention mixers in the published pattern, the short convolution placed by
platform, a norm on every head of q and k, the held experts with no shared
one, a tied head."""

import filecmp
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import (
    Lfm2Moe, Lfm2MoeConfig, cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.models import lfm2_moe
from pytorch_distributedtraining_tpu.models import lfm2_moe_reference as reference
from pytorch_distributedtraining_tpu.ops import short_conv as sc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = {"all": None, "some": (1, 2, 5), "one": (6,)}
C, F = lfm2_moe.CONV, lfm2_moe.FULL
# the published period behind a dense layer, and each kind of mixer alone
LAYOUTS = {
    "published": ((C, F, C, C, C), 1), "two_dense": ((C, C, F, C, C), 2),
    "conv": ((C, C), 0), "attention": ((F, F), 0),
}
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl


def arch_of(cfg):
    return {
        "layers": cfg.num_hidden_layers, "dense": cfg.num_dense_layers,
        "attention": tuple(kind == F for kind in cfg.layer_types),
        "heads": cfg.num_attention_heads,
        "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "window": None, "windowed": (False,) * cfg.num_hidden_layers,
        "top_k": cfg.num_experts_per_tok, "scaling": cfg.routed_scaling_factor,
        "norm_topk": cfg.norm_topk_prob, "eps": cfg.norm_eps,
        "theta": cfg.rope_theta, "held": cfg.held,
    }


def build(held="some", layout="published", seed=0, **kw):
    """Model (the attention kernel and the grouped matmul interpreted; the
    short convolution by platform: its jnp form here), parameters and
    selection biases (random, so that they select), a batch of 16 tokens a
    sequence. The norms' gains and the taps are drawn too: at their initial
    values their place in the arithmetic would hardly show."""
    kinds, dense = LAYOUTS[layout]
    cfg = Lfm2MoeConfig.tiny(
        held_experts=HELD[held], num_hidden_layers=len(kinds),
        layer_types=kinds, num_dense_layers=dense, **kw
    )
    model = Lfm2Moe(cfg, interpret=True)
    tok = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 17))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    variables = model.init(jax.random.PRNGKey(seed), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
            if path[-1].key == "scale" else
            0.5 * jax.random.normal(next(keys), leaf.shape)
            if path[-1].key == "weight" else leaf
        ),
        variables["params"],
    )
    bias = jax.tree.map(
        lambda b: 0.2 * jax.random.normal(next(keys), b.shape),
        variables[lfm2_moe.ROUTER_STATE],
    )
    return cfg, model, params, bias, x, y


def apply(model, params, bias, x, **kw):
    return model.apply({"params": params, lfm2_moe.ROUTER_STATE: bias}, x, **kw)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Steer the short convolution as a TPU lowering would, on the CPU: the
    platform's branch is the kernels', and they run interpreted."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent", lambda *a, tpu, default: tpu(*a)
    )
    monkeypatch.setattr(
        sc, "short_conv_kernel",
        functools.partial(sc.short_conv_kernel, interpret=True),
    )


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"), ("one", "published"),
    ("some", "two_dense"), ("some", "conv"), ("some", "attention"),
])
def test_logits_match_the_reference(held, layout):
    cfg, model, params, bias, x, _ = build(held, layout)
    logits = apply(model, params, bias, x)
    want = reference.forward(params, bias, x, arch_of(cfg), chunk=8)
    assert logits.dtype == jnp.float32 and logits.shape == (2, 16, 96)
    np.testing.assert_allclose(logits, want, atol=5e-6)
    # the einsum a CPU runs is the same function
    einsum = Lfm2Moe(cfg, lfm2_moe.banded_attention, interpret=True)
    np.testing.assert_allclose(apply(einsum, params, bias, x), want, atol=5e-6)


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"), ("some", "two_dense"),
    ("some", "conv"), ("some", "attention"),
])
def test_loss_and_every_gradient_leaf_match_the_reference(held, layout):
    cfg, model, params, bias, x, y = build(held, layout)
    loss, grads = jax.value_and_grad(
        lambda p: cross_entropy_loss(apply(model, p, bias, x), y)
    )(params)
    want, want_grads = reference.loss_and_grads(
        params, bias, x, y, arch_of(cfg), chunk=8
    )
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    # a layer: 2 norms; a conv mixer 3 leaves, an attention one 6; 3 of a
    # dense MLP, or a router and 3 held experts' matrices
    convs = sum(kind == C for kind in cfg.layer_types)
    assert len(flat) == len(want_flat) == (
        2 * cfg.num_hidden_layers + 3 * convs
        + 6 * (cfg.num_hidden_layers - convs) + 3 * cfg.num_dense_layers
        + 4 * (cfg.num_hidden_layers - cfg.num_dense_layers) + 2
    )
    for path, g in flat:
        w = want_flat[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 2e-4, (
            jax.tree_util.keystr(path)
        )
    # the taps and the head norms are in the path, in every layer
    for i, kind in enumerate(cfg.layer_types):
        p = grads[f"layers_{i}"]
        leaves = (
            [p["conv"]["weight"]] if kind == C else
            [p["self_attn"]["q_layernorm"]["scale"],
             p["self_attn"]["k_layernorm"]["scale"]]
        )
        assert all(float(jnp.max(jnp.abs(g))) > 1e-6 for g in leaves)


def test_the_kernels_in_the_model_match_the_reference(on_a_tpu):
    """The model as a TPU lowering holds it (the short-convolution pair
    interpreted): logits, loss and every gradient leaf are the reference's,
    and the router state moves by the published rule."""
    cfg, model, params, bias, x, y = build("some")
    arch = arch_of(cfg)
    np.testing.assert_allclose(
        apply(model, params, bias, x),
        reference.forward(params, bias, x, arch, chunk=8), atol=5e-6,
    )
    (loss, moved), grads = jax.value_and_grad(
        lambda p: (lambda out: (cross_entropy_loss(out[0], y), out[1]))(
            apply(model, p, bias, x, mutable=[lfm2_moe.ROUTER_STATE])
        ), has_aux=True,
    )(params)
    want, want_grads = reference.loss_and_grads(params, bias, x, y, arch, chunk=8)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 2e-4
    # the biases moved by 0.001 x sign(mean load - load), the loads of the
    # reference's own routing of each expert layer's input
    h = params["embed_tokens"][x]
    for i in range(cfg.num_hidden_layers):
        p, b = params[f"layers_{i}"], reference.layer_bias(bias, arch, i)
        mixed = reference.mixer_branch(h, p, arch, i, 8)
        if b is not None:
            u = reference.rms(mixed, p["ffn_norm"], cfg.norm_eps)
            sel, _ = reference.route(u.reshape(-1, 256), p["moe"]["router"], b, arch)
            load = jnp.zeros(8).at[sel.reshape(-1)].add(1.0)
            np.testing.assert_allclose(
                moved[lfm2_moe.ROUTER_STATE][f"layers_{i}"]["moe"]["bias"],
                b + 1e-3 * jnp.sign(jnp.mean(load) - load), atol=1e-7,
            )
        h = reference.mlp_branch(mixed, p, b, arch, i)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold two of eight experts each: the held parts, over the
    four shares (no shared expert: nothing is computed alike twice), are
    what the uncut reference gives for the whole layer."""
    whole = Lfm2MoeConfig.tiny(num_experts_per_tok=4)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.hidden_size))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    params = lfm2_moe.ExpertLayer(whole, interpret=True).init(
        jax.random.PRNGKey(2), u
    )["params"]
    total, landed = jnp.zeros_like(u), 0.0
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        cfg = Lfm2MoeConfig.tiny(num_experts_per_tok=4, held_experts=held)
        mine = {
            k: v[jnp.asarray(held)] if k.startswith("experts_") else v
            for k, v in params.items()
        }
        out, new = lfm2_moe.ExpertLayer(cfg, interpret=True).apply(
            {"params": mine, lfm2_moe.ROUTER_STATE: {"bias": bias}}, u,
            mutable=[lfm2_moe.MOE_COUNTERS],
        )
        total = total + out
        counters = new[lfm2_moe.MOE_COUNTERS]
        assert float(counters["dropped"]) == 0.0
        landed += float(counters["landed"])
    assert landed == 24 * 4  # every assignment landed on exactly one chip
    arch = dict(arch_of(whole), held=tuple(range(8)), top_k=4)
    uncut = reference.expert_layer(u.reshape(-1, 256), params, bias, arch)
    np.testing.assert_allclose(total, uncut.reshape(u.shape), atol=5e-6)


def test_the_routing_weights_are_the_published_ones():
    """Sigmoid scores, top-k by score + bias, the picked scores over their
    sum + 1e-6, times ``routed_scaling_factor``: a token whose picked scores
    are tiny keeps weights under 1 in sum."""
    cfg = Lfm2MoeConfig.tiny(routed_scaling_factor=1.0)
    scores = jnp.array([[0.9, 0.1, 0.5, 0.2, 0.0, 0.0, 0.0, 0.0],
                        [1e-6] * 8])
    sel, w = lfm2_moe.route(scores, jnp.zeros(8).at[7].set(5.0), cfg)
    assert sorted(np.asarray(sel[0]).tolist()) == [0, 7]
    np.testing.assert_allclose(w[0, list(sel[0]).index(0)], 0.9 / (0.9 + 1e-6))
    np.testing.assert_allclose(jnp.sum(w[1]), 2e-6 / (2e-6 + 1e-6), rtol=1e-5)


def test_the_head_is_the_embedding():
    """One matrix, ``embed_tokens``, no head of its own: its gradient is the
    sum of what the lookup and the head each give it (the reference untied,
    the two parts taken apart)."""
    cfg, model, params, bias, x, y = build("all")
    assert "lm_head" not in params and params["embed_tokens"].shape == (96, 256)
    arch = arch_of(cfg)
    tied = jax.grad(
        lambda p: cross_entropy_loss(apply(model, p, bias, x), y)
    )(params)["embed_tokens"]

    g_tied = jax.grad(lambda e: reference.loss(
        {**params, "embed_tokens": e}, bias, x, y, arch, chunk=8
    ))(params["embed_tokens"])

    def features(e):
        with jax.default_matmul_precision("highest"):
            h = e[x]
            for i in range(arch["layers"]):
                h = reference.decoder_layer(
                    h, params[f"layers_{i}"],
                    reference.layer_bias(bias, arch, i), arch, i, 8,
                )
            return reference.rms(h, params["embedding_norm"], arch["eps"])

    def untied(lookup, head):
        with jax.default_matmul_precision("highest"):
            logits = features(lookup) @ head.T
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))

    e = params["embed_tokens"]
    g_in, g_out = jax.grad(untied, argnums=(0, 1))(e, e)
    np.testing.assert_allclose(g_in + g_out, g_tied, atol=1e-6)
    np.testing.assert_allclose(tied, g_in + g_out, atol=2e-5)
    # both parts are really there
    assert float(jnp.max(jnp.abs(g_in))) > 1e-4 < float(jnp.max(jnp.abs(g_out)))


@pytest.mark.parametrize("dense", [0, 1, 2, 5])
def test_the_leading_layers_are_dense_and_the_mixers_as_listed(dense):
    cfg = Lfm2MoeConfig.tiny(num_dense_layers=dense)
    shapes = jax.eval_shape(
        lambda: Lfm2Moe(cfg, lfm2_moe.banded_attention, interpret=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
    )
    layers = [shapes["params"][f"layers_{i}"] for i in range(5)]
    assert ["mlp_dense" in p for p in layers] == [i < dense for i in range(5)]
    assert ["self_attn" in p for p in layers] == [False, True, False, False, False]
    assert sorted(shapes.get(lfm2_moe.ROUTER_STATE, {})) == [
        f"layers_{i}" for i in range(dense, 5)
    ]
    conv = layers[0]["conv"]
    assert conv["in_proj"]["kernel"].shape == (256, 768)
    assert conv["weight"].shape == (256, 3)
    attn = layers[1]["self_attn"]
    assert attn["q_proj"]["kernel"].shape == (256, 256)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (256, 128)
    assert attn["q_layernorm"]["scale"].shape == (64,)


def test_the_configuration_is_the_published_one():
    """The defaults are the catalog row's ``config`` (the published keys);
    ``head_dim`` is hidden size over query heads, 64."""
    cfg = Lfm2MoeConfig()
    assert cfg.head_dim == 64 and cfg.held == tuple(range(32))
    assert cfg.layer_types.count(F) == 6 and cfg.layer_types.count(C) == 18
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    published = row["config"]
    for key, value in published.items():
        if hasattr(cfg, key):
            got = getattr(cfg, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=3)
    with pytest.raises(ValueError, match="conv bias"):
        Lfm2MoeConfig(conv_bias=True)


def test_every_mixer_is_probed_on_its_own_inputs():
    """``moe_probe`` holds each conv mixer's ``bcx`` and output, and each
    attention core's q and k AFTER the head norms and rotary, v and output:
    the reference's on those inputs gives the outputs back."""
    cfg, model, params, bias, x, _ = build("all")
    probe = apply(model, params, bias, x, mutable=[lfm2_moe.MOE_PROBE])[1][
        lfm2_moe.MOE_PROBE
    ]
    arch = arch_of(cfg)
    for i, kind in enumerate(cfg.layer_types):
        got = probe[f"layers_{i}"]
        if kind == C:
            conv = got["conv"]
            assert conv["bcx"].shape == (2, 16, 768)
            want = reference.short_conv(
                conv["bcx"], params[f"layers_{i}"]["conv"]["weight"]
            )
            np.testing.assert_allclose(conv["output"], want, atol=2e-5)
        else:
            core = got["self_attn"]
            assert core["q"].shape == (2, 16, 4, 64)
            assert core["k"].shape == core["v"].shape == (2, 16, 2, 64)
            want = reference.banded_attention(
                core["q"], core["k"], core["v"], None, 8
            )
            np.testing.assert_allclose(core["output"], want, atol=2e-5)
            np.testing.assert_allclose(
                jnp.mean(jnp.square(core["q"]), -1), 1.0, rtol=0.5
            )
    x0 = params["embed_tokens"][x]
    p = params["layers_0"]
    a = reference.rms(x0, p["operator_norm"], cfg.norm_eps)
    np.testing.assert_allclose(
        probe["layers_0"]["conv"]["bcx"], a @ p["conv"]["in_proj"]["kernel"],
        atol=2e-5,
    )


@pytest.mark.parametrize("kv_heads", [8, 2])
def test_the_split_head_kernel_at_head_64(kv_heads):
    """``ops/pallas_attn.flash_attention`` interpreted at heads of 64, 32
    query heads on 8 key-value heads (4 each) as the cell has them, causal,
    T (48) three blocks of 16: forward and the three gradients against the
    masked einsum."""
    from pytorch_distributedtraining_tpu.ops.pallas_attn import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (1, 48, 32, 64))
    k, v = (jax.random.normal(key, (1, 48, kv_heads, 64)) for key in keys[1:3])
    mix = jax.random.normal(keys[3], q.shape)
    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, 16, 16, True, None
    )
    einsum = lambda q, k, v: lfm2_moe.banded_attention(  # noqa: E731
        q, k, v, window=None
    )
    np.testing.assert_allclose(kernel(q, k, v), einsum(q, k, v), atol=2e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * mix), argnums=(0, 1, 2)
    )(q, k, v)
    for got, want in zip(grads(kernel), grads(einsum)):
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_model_says_its_paths_and_names_its_scopes():
    """Every traced conv mixer leaves a ``short_conv.path`` instant, every
    attention core an ``attention.path``, every expert layer a
    ``routing.path``; the compiled step's instructions carry the scopes the
    benchmark's readers look for; the same step trains."""
    from pytorch_distributedtraining_tpu import optim, parallel
    from pytorch_distributedtraining_tpu.observe import trace
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    tracer = trace.get_tracer()
    was = tracer.enabled
    cfg, model, params, bias, x, y = build("some", remat=True)
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        jax.make_jaxpr(lambda p: apply(model, p, bias, x))(params)
        said = {
            name: [r["attrs"] for r in trace.records() if r["name"] == name]
            for name in ("short_conv.path", "attention.path", "routing.path")
        }
    finally:
        trace.clear()
        tracer.enabled = was
    assert [(a["path"], a["t"], a["d"], a["taps"]) for a in said[
        "short_conv.path"
    ]] == [("kernel", 16, 256, 3)] * 4
    assert [(a["path"], a["window"], a["heads"], a["kv_heads"]) for a in said[
        "attention.path"
    ]] == [("kernel", None, 4, 2)]
    assert len(said["routing.path"]) == 4

    def loss_fn(params, batch, rng, model_state):
        logits, new = model.apply(
            {"params": params, **model_state}, batch[0],
            mutable=[lfm2_moe.ROUTER_STATE, lfm2_moe.MOE_COUNTERS],
        )
        return cross_entropy_loss(logits, batch[1]), {
            "model_state": {lfm2_moe.ROUTER_STATE: new[lfm2_moe.ROUTER_STATE]},
            **lfm2_moe.routing_counters(new[lfm2_moe.MOE_COUNTERS]),
        }

    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-2, clip_grad_norm=1.0)
    state, shardings = parallel.create_train_state(
        init_fn=lambda rng: (params, {lfm2_moe.ROUTER_STATE: bias}), tx=tx,
        mesh=mesh, policy=parallel.DDP(), rng=jax.random.PRNGKey(0),
    )
    step = parallel.TrainStep(
        loss_fn, tx, mesh, parallel.DDP(), state_shardings=shardings,
        donate=False,
    )
    text = step.compiled_text(state, (x, y))
    found = {
        tuple(re.split(r"[/()]+", n))
        for n in re.findall(r'op_name="([^"]*)"', text)
    }
    for scope in (
        "short_conv", "qk_norm", "attention", "router", "dispatch", "experts",
        "combine", "mlp_dense", "embed", "head",
    ):
        assert any(scope in path for path in found), scope
    # the convolution's scope holds its backward and none of the projections
    assert any("short_conv" in p and "transpose" in p for p in found)
    assert not any("short_conv" in p and ("in_proj" in p or "out_proj" in p)
                   for p in found)
    losses = []
    with mesh:
        for _ in range(3):
            state, metrics = step(state, (x, y))
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert float(metrics["dropped_assignments"]) == 0.0
    moved = state.model_state[lfm2_moe.ROUTER_STATE]["layers_1"]["moe"]["bias"]
    assert not np.allclose(moved, bias["layers_1"]["moe"]["bias"])


def test_rematerialised_layers_run_the_conv_forward_again(on_a_tpu):
    """Under ``remat=True`` the attention kernel's residuals are kept (one
    forward a layer) and the short convolution's forward runs again in each
    conv layer's backward: its output feeds ``out_proj``'s weight gradient
    and no name keeps it."""
    def kernels(remat):
        cfg, model, params, bias, x, y = build("some", remat=remat)
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda p: cross_entropy_loss(apply(model, p, bias, x), y)
        ))(params))
        return jaxpr.count("name=short_conv_fwd"), jaxpr.count(
            "name=short_conv_bwd"
        )

    assert kernels(False) == (4, 4)
    assert kernels(True) == (8, 4)


def test_the_two_copies_of_the_reference_are_one_text():
    assert filecmp.cmp(
        os.path.join(REPO, "chipbench", "reference", "lfm2_moe.py"),
        os.path.join(
            REPO, "pytorch_distributedtraining_tpu", "models",
            "lfm2_moe_reference.py",
        ),
        shallow=False,
    )


def test_the_shared_pieces_are_the_other_models():
    """The attention core is SmallThinker's, the held experts and the norms
    GLM's, so that a change to them is judged on those cells too."""
    from pytorch_distributedtraining_tpu.models import (
        glm4_moe_lite, held_experts, smallthinker,
    )

    assert lfm2_moe.attention_core is smallthinker.attention_core
    assert lfm2_moe.held_experts_sum is held_experts.held_experts_sum
    assert lfm2_moe.RMSNorm is glm4_moe_lite.RMSNorm
    assert lfm2_moe.GatedMLP is glm4_moe_lite.GatedMLP
    assert lfm2_moe.rotary is glm4_moe_lite.rotary
