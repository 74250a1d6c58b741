"""SmallThinker-21BA3B (``models/smallthinker.py``) against its plain float32
reference at a tiny size on seeded random weights, and what its layers
promise: two kinds of layer in the published pattern, grouped-query heads, a
router that reads the layer's raw input, the held-experts layer it shares
with GLM-4.7-Flash."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import (
    Glm4MoeLite, Glm4MoeLiteConfig, SmallThinker, SmallThinkerConfig,
    cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.models import smallthinker as st
from pytorch_distributedtraining_tpu.models import (
    smallthinker_reference as reference,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = {"all": None, "some": (1, 2, 5), "one": (6,)}
# the published pattern, and each kind of layer alone
LAYOUTS = {
    "published": ((0, 1, 1, 1), (0, 1, 1, 1)),
    "full_no_positions": ((0, 0), (0, 0)),
    "window_rotary": ((1, 1), (1, 1)),
}


def arch_of(cfg):
    return {
        "layers": cfg.num_hidden_layers, "heads": cfg.num_attention_heads,
        "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "window": cfg.sliding_window_size,
        "windowed": cfg.sliding_window_layout, "rope": cfg.rope_layout,
        "top_k": cfg.moe_num_active_primary_experts, "eps": cfg.rms_norm_eps,
        "theta": cfg.rope_theta, "held": cfg.held,
    }


def build(held="some", layout="published", seed=0, **kw):
    """Model (both kernels interpreted), parameters, a batch of 16 tokens a
    sequence: three windows of 5 long."""
    windowed, rope = LAYOUTS[layout]
    cfg = SmallThinkerConfig.tiny(
        held_experts=HELD[held], num_hidden_layers=len(rope),
        sliding_window_layout=windowed, rope_layout=rope, **kw
    )
    model = SmallThinker(cfg, interpret=True)
    tok = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 17))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    params = model.init(jax.random.PRNGKey(seed), x)["params"]
    return cfg, model, params, x, y


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"), ("one", "published"),
    ("some", "full_no_positions"), ("some", "window_rotary"),
])
def test_logits_match_the_reference(held, layout):
    cfg, model, params, x, _ = build(held, layout)
    logits = model.apply({"params": params}, x)
    want = reference.forward(params, x, arch_of(cfg), chunk=8)
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=2e-6)
    # the einsum a CPU runs is the same function
    einsum = SmallThinker(cfg, st.banded_attention, interpret=True)
    np.testing.assert_allclose(
        einsum.apply({"params": params}, x), want, atol=2e-6
    )


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"),
    ("some", "full_no_positions"), ("some", "window_rotary"),
])
def test_loss_and_every_gradient_leaf_match_the_reference(held, layout):
    cfg, model, params, x, y = build(held, layout)
    loss, grads = jax.value_and_grad(
        lambda p: cross_entropy_loss(model.apply({"params": p}, x), y)
    )(params)
    want, want_grads = reference.loss_and_grads(
        params, x, y, arch_of(cfg), chunk=8
    )
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert len(flat) == len(want_flat) == 10 * cfg.num_hidden_layers + 3
    for path, g in flat:
        w = want_flat[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-4, (
            jax.tree_util.keystr(path)
        )


def test_the_layers_are_of_the_two_published_kinds():
    """Layer 0 of a period sees the whole past and has no positions (a
    permutation of the earlier tokens leaves the last one's output where it
    was); the others see 5 tokens and turn with position."""
    cfg, model, params, x, _ = build("all")
    assert [cfg.window(i) for i in range(4)] == [None, 5, 5, 5]
    a = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.hidden_size))
    reversed_past = a.at[:, :11].set(a[:, 10::-1])
    swapped_in_window = a.at[:, 7:11].set(a[:, jnp.asarray([8, 7, 10, 9])])
    new_before_window = a.at[:, :7].set(0.5)
    same = lambda layer, p, z: bool(jnp.allclose(  # noqa: E731
        layer.apply(p, a)[:, -1], layer.apply(p, z)[:, -1], atol=1e-6
    ))
    full = st.Attention(cfg, st.banded_attention, None, False)
    p = full.init(jax.random.PRNGKey(2), a)
    assert same(full, p, reversed_past) and same(full, p, swapped_in_window)
    assert not same(full, p, new_before_window)
    sliding = st.Attention(cfg, st.banded_attention, 5, True)
    assert same(sliding, p, new_before_window)
    assert not same(sliding, p, swapped_in_window)
    # 4 query heads on 2 key-value heads: k and v have half q's width
    shapes = jax.tree.map(jnp.shape, params["layers_1"]["attn"])
    assert shapes["q_proj"]["kernel"] == (32, 32)
    assert shapes["k_proj"]["kernel"] == shapes["v_proj"]["kernel"] == (32, 16)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two of sixteen experts each: the held parts, over all
    eight shares, are what the uncut reference gives for the whole layer (no
    shared expert: nothing is counted twice)."""
    whole = SmallThinkerConfig.tiny(
        moe_num_primary_experts=16, moe_num_active_primary_experts=4
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.hidden_size))
    u = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    flat = lambda a: a.reshape(-1, whole.hidden_size)  # noqa: E731
    router = st.Router(whole)
    w_r = router.init(jax.random.PRNGKey(3), flat(x))
    routing = router.apply(w_r, flat(x))
    params = st.ExpertLayer(whole, interpret=True).init(
        jax.random.PRNGKey(2), u, flat(x), *routing
    )["params"]
    total = jnp.zeros_like(u)
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        cfg = SmallThinkerConfig.tiny(
            moe_num_primary_experts=16, moe_num_active_primary_experts=4,
            held_experts=held,
        )
        mine = {k: v[jnp.asarray(held)] for k, v in params.items()}
        total = total + st.ExpertLayer(cfg, interpret=True).apply(
            {"params": mine}, u, flat(x), *routing
        )
    arch = dict(arch_of(whole), held=tuple(range(16)))
    sel, w = reference.route(flat(x), w_r["params"]["kernel"], arch)
    uncut = reference.expert_layer(flat(u), params, sel, w, arch)
    np.testing.assert_allclose(total, uncut.reshape(u.shape), atol=2e-6)
    np.testing.assert_array_equal(np.sort(sel), np.sort(routing[1]))


def test_the_router_reads_the_layers_input_not_the_experts():
    """Perturb ``h`` only (the attention's output projection): the picks
    and weights stay, the layer's output moves. And the weights are the
    softmax over the picked logits."""
    cfg = SmallThinkerConfig.tiny()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.hidden_size))
    layer = st.DecoderLayer(cfg, st.banded_attention, None, False, True)
    params = layer.init(jax.random.PRNGKey(6), x)["params"]
    other = jax.tree.map(lambda a: a, params)
    other["attn"]["o_proj"]["kernel"] = 3.0 * params["attn"]["o_proj"]["kernel"]
    run = lambda p: layer.apply(  # noqa: E731
        {"params": p}, x, mutable=[st.MOE_PROBE]
    )
    (out, probe), (out2, probe2) = run(params), run(other)
    got, got2 = probe[st.MOE_PROBE]["moe"], probe2[st.MOE_PROBE]["moe"]
    assert not np.allclose(got["input"], got2["input"], atol=1e-3)
    assert not np.allclose(out, out2, atol=1e-3)
    np.testing.assert_array_equal(got["picks"], got2["picks"])
    np.testing.assert_array_equal(got["scores"], got2["scores"])
    np.testing.assert_array_equal(got["router_input"], x.reshape(-1, 32))
    logits = x.reshape(-1, 32) @ params["router"]["kernel"]
    np.testing.assert_allclose(got["scores"], logits, atol=1e-6)
    top, sel = jax.lax.top_k(logits, 2)
    np.testing.assert_array_equal(np.sort(got["picks"]), np.sort(sel))
    every = jax.nn.softmax(logits, -1)
    picked = jnp.take_along_axis(every, sel, -1)
    np.testing.assert_allclose(  # softmax over all, pick, renormalise
        jax.nn.softmax(top, -1), picked / picked.sum(-1, keepdims=True),
        rtol=1e-5,
    )


def test_every_attention_core_is_probed_on_its_own_inputs():
    """``moe_probe`` holds, per layer, the core's q and k (after rotary
    where the layer has positions), v and output: the reference's attention
    on those q, k, v under the layer's window gives the output back, and
    under the other kind's window does not."""
    cfg, model, params, x, _ = build(held="all")
    probe = model.apply({"params": params}, x, mutable=[st.MOE_PROBE])[1][
        st.MOE_PROBE
    ]
    assert sorted(probe) == [f"layers_{i}" for i in range(4)]
    for i in range(4):
        got = probe[f"layers_{i}"]["attn"]
        assert got["q"].shape == (2, 16, 4, 8)
        assert got["k"].shape == got["v"].shape == (2, 16, 2, 8)
        q, k, v = got["q"], got["k"], got["v"]
        want = reference.banded_attention(q, k, v, cfg.window(i), 8)
        np.testing.assert_allclose(got["output"], want, atol=2e-5)
        other = None if cfg.window(i) else cfg.sliding_window_size
        wrong = reference.banded_attention(q, k, v, other, 8)
        assert not np.allclose(got["output"], wrong, atol=1e-3)


def test_the_model_calls_the_banded_kernel_unless_told_otherwise():
    """``SmallThinker`` with no ``attn_fn`` runs ``ops/pallas_attn.py`` (one
    kernel a layer more than with the einsum), and every traced layer says
    so in ``attention.path``."""
    from pytorch_distributedtraining_tpu.observe import trace

    tracer = trace.get_tracer()
    was = tracer.enabled
    cfg, model, params, x, _ = build("some")
    einsum = SmallThinker(cfg, st.banded_attention, interpret=True)
    kernels = lambda m: str(  # noqa: E731
        jax.make_jaxpr(lambda p: m.apply({"params": p}, x))(params)
    ).count("pallas_call")
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        assert kernels(model) - kernels(einsum) == cfg.num_hidden_layers
        said = [
            r["attrs"] for r in trace.records()
            if r["name"] == "attention.path"
        ]
    finally:
        trace.clear()
        tracer.enabled = was
    assert [a["window"] for a in said[:4]] == [None, 5, 5, 5]
    assert {a["path"] for a in said[:4]} == {"kernel"}
    assert {a["path"] for a in said[4:]} == {"attn_fn"}
    assert {(a["heads"], a["kv_heads"], a["t"]) for a in said} == {(4, 2, 16)}
    assert said[0]["bq"] == said[0]["bk"] == 16


def test_every_traced_expert_layer_says_its_routing_path():
    """``routing.path``: how the held-experts layer moves its rows, with the
    shapes it adapts on (one instant a traced layer)."""
    from pytorch_distributedtraining_tpu.models import held_experts
    from pytorch_distributedtraining_tpu.observe import trace

    tracer = trace.get_tracer()
    was = tracer.enabled
    cfg, model, params, x, _ = build("some")
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        jax.make_jaxpr(lambda p: model.apply({"params": p}, x))(params)
        said = [
            r["attrs"] for r in trace.records() if r["name"] == "routing.path"
        ]
    finally:
        trace.clear()
        tracer.enabled = was
    assert len(said) == cfg.num_hidden_layers
    n, k = x.size, cfg.moe_num_active_primary_experts
    assert {(a["path"], a["n"], a["k"], a["d"], a["tile"]) for a in said} == {
        ("jnp", n, k, cfg.hidden_size, min(held_experts.ROW_TILE, n * k))
    }
    assert all("landed rows" in a["reason"] for a in said)


def test_the_two_copies_of_the_reference_are_one_text():
    assert filecmp.cmp(
        os.path.join(REPO, "chipbench", "reference", "smallthinker.py"),
        os.path.join(
            REPO, "pytorch_distributedtraining_tpu", "models",
            "smallthinker_reference.py",
        ),
        shallow=False,
    )


def test_the_shared_layer_leaves_glms_parameter_tree_as_it_was():
    """``Glm4MoeLite``'s parameter names and shapes at ``tiny``, pinned: the
    held-experts layer it now shares with this model is a function inside
    its own module, not a module of its own."""
    cfg = Glm4MoeLiteConfig.tiny()
    variables = jax.eval_shape(
        lambda: Glm4MoeLite(cfg, interpret=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
    )
    assert sorted(variables) == [
        "moe_counters", "moe_probe", "params", "router_state"
    ]
    kept = {k: variables[k] for k in ("params", "router_state")}
    shapes = {
        jax.tree_util.keystr(path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(kept)[0]
    }
    mla = {
        "kv_a_proj_with_mqa']['kernel": (32, 20),
        "kv_b_proj']['kernel": (12, 48), "norm_kv']['scale": (12,),
        "norm_q']['scale": (16,), "o_proj']['kernel": (32, 32),
        "q_a_proj']['kernel": (32, 16), "q_b_proj']['kernel": (16, 32),
    }
    mlp = lambda width: {  # noqa: E731
        "down_proj']['kernel": (width, 32), "gate_proj']['kernel": (32, width),
        "up_proj']['kernel": (32, width),
    }
    want = {
        "['params']['embed_tokens']": (96, 32),
        "['params']['lm_head']": (32, 96),
        "['params']['norm_f']['scale']": (32,),
    }
    for i in range(3):
        at = f"['params']['layers_{i}']"
        want[f"{at}['norm_attn']['scale']"] = (32,)
        want[f"{at}['norm_ffn']['scale']"] = (32,)
        want.update({f"{at}['mla']['{k}']": v for k, v in mla.items()})
        if i == 0:
            want.update(
                {f"{at}['mlp_dense']['{k}']": v for k, v in mlp(48).items()}
            )
            continue
        want[f"{at}['moe']['router']"] = (32, 8)
        want[f"{at}['moe']['experts_gate']"] = (8, 32, 24)
        want[f"{at}['moe']['experts_up']"] = (8, 32, 24)
        want[f"{at}['moe']['experts_down']"] = (8, 24, 32)
        want.update({
            f"{at}['moe']['mlp_shared']['{k}']": v for k, v in mlp(24).items()
        })
        want[f"['router_state']['layers_{i}']['moe']['bias']"] = (8,)
    assert shapes == want


def test_counters_ride_the_train_step_and_the_loss_falls():
    from pytorch_distributedtraining_tpu import optim, parallel
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    cfg, model, _, x, y = build("some", remat=True)
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-2, clip_grad_norm=1.0)

    def loss_fn(params, batch, rng, model_state):
        logits, new = model.apply(
            {"params": params}, batch[0], mutable=[st.MOE_COUNTERS]
        )
        return cross_entropy_loss(logits, batch[1]), st.routing_counters(
            new[st.MOE_COUNTERS]
        )

    state, shardings = parallel.create_train_state(
        init_fn=lambda rng: (model.init(rng, x)["params"], {}), tx=tx,
        mesh=mesh, policy=parallel.DDP(), rng=jax.random.PRNGKey(0),
    )
    step = parallel.TrainStep(
        loss_fn, tx, mesh, parallel.DDP(), state_shardings=shardings
    )
    losses = []
    with mesh:
        for _ in range(3):
            state, metrics = step(state, (x, y))
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert float(metrics["dropped_assignments"]) == 0.0
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0
    # 3 of 8 experts held, 2 picks a token, 4 layers of 32 tokens
    assert 0 < float(metrics["assignments_landed"]) <= 4 * 32 * 2


def test_rematerialised_layers_run_each_attention_kernel_once(monkeypatch):
    """Under ``remat=True`` a layer keeps its attention kernel's output and
    row statistics: the gradient holds the kernels of the plain model plus
    the rematerialised grouped matmuls, and no second attention forward. A
    forward rule that did not name its residuals ran one more a layer."""
    from pytorch_distributedtraining_tpu.ops import pallas_attn

    def kernels(remat):
        cfg, model, params, x, y = build("some", remat=remat)
        return cfg.num_hidden_layers, str(jax.make_jaxpr(jax.grad(
            lambda p: cross_entropy_loss(model.apply({"params": p}, x), y)
        ))(params)).count("pallas_call[")

    layers, plain = kernels(False)
    _, kept = kernels(True)
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    _, untagged = kernels(True)
    assert untagged - kept == layers
    # what is still rematerialised: the expert layers' forward kernels
    assert kept > plain
