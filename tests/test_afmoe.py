"""Trinity-Mini (``models/afmoe.py``) against its plain float32 reference at a
tiny size on seeded random weights, and what its layers promise: sliding and
full layers in the published pattern, a norm on every head of q and k, a
sigmoid gate on the core's output, a norm after each branch, GLM's expert
layer as it stands, the banded kernel at eight query heads a key-value
head."""

import filecmp
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import (
    Afmoe, AfmoeConfig, cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.models import afmoe
from pytorch_distributedtraining_tpu.models import afmoe_reference as reference
from pytorch_distributedtraining_tpu.models.glm4_moe_lite import ExpertLayer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = {"all": None, "some": (1, 2, 5), "one": (6,)}
S, F = afmoe.SLIDING, afmoe.FULL
# the published pattern behind a dense layer, and each kind of layer alone
LAYOUTS = {
    "published": ((S, S, S, F), 1), "two_dense": ((S, S, S, F), 2),
    "full": ((F, F), 0), "sliding": ((S, S), 0),
}


def arch_of(cfg):
    return {
        "layers": cfg.num_hidden_layers, "dense": cfg.num_dense_layers,
        "heads": cfg.num_attention_heads,
        "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "window": cfg.sliding_window,
        "windowed": tuple(kind == S for kind in cfg.layer_types),
        "top_k": cfg.num_experts_per_tok, "scaling": cfg.route_scale,
        "norm_topk": cfg.route_norm,
        "embed_scale": cfg.hidden_size ** 0.5 if cfg.mup_enabled else 1.0,
        "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta,
        "held": cfg.expert_layer.held,
    }


def build(held="some", layout="published", seed=0, **kw):
    """Model (both kernels interpreted), parameters and selection biases
    (random, so that they select), a batch of 16 tokens a sequence: three
    windows of 5 long. The norms' gains are drawn too: at their initial 1 a
    gain's place in the arithmetic would not show."""
    kinds, dense = LAYOUTS[layout]
    cfg = AfmoeConfig.tiny(
        held_experts=HELD[held], num_hidden_layers=len(kinds),
        layer_types=kinds, num_dense_layers=dense, **kw
    )
    model = Afmoe(cfg, interpret=True)
    tok = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 17))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    variables = model.init(jax.random.PRNGKey(seed), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if path[-1].key != "scale" else (
            1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
        ),
        variables["params"],
    )
    bias = jax.tree.map(
        lambda b: 0.2 * jax.random.normal(next(keys), b.shape),
        variables[afmoe.ROUTER_STATE],
    )
    return cfg, model, params, bias, x, y


def apply(model, params, bias, x, **kw):
    return model.apply({"params": params, afmoe.ROUTER_STATE: bias}, x, **kw)


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"), ("one", "published"),
    ("some", "two_dense"), ("some", "full"), ("some", "sliding"),
])
def test_logits_match_the_reference(held, layout):
    cfg, model, params, bias, x, _ = build(held, layout)
    logits = apply(model, params, bias, x)
    want = reference.forward(params, bias, x, arch_of(cfg), chunk=8)
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=5e-6)
    # the einsum a CPU runs is the same function
    einsum = Afmoe(cfg, afmoe.banded_attention, interpret=True)
    np.testing.assert_allclose(
        apply(einsum, params, bias, x), want, atol=5e-6
    )


@pytest.mark.parametrize("held, layout", [
    ("all", "published"), ("some", "published"), ("some", "two_dense"),
    ("some", "full"), ("some", "sliding"),
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
    # a layer: 4 norms, 5 projections, 2 head norms; 3 of a dense MLP or
    # router, 3 held experts' matrices and 3 of the shared expert
    dense = cfg.num_dense_layers
    sparse = cfg.num_hidden_layers - dense
    assert len(flat) == len(want_flat) == 14 * dense + 18 * sparse + 3
    for path, g in flat:
        w = want_flat[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 2e-4, (
            jax.tree_util.keystr(path)
        )
    # the gate and both head norms are in the path, in every layer
    for i in range(cfg.num_hidden_layers):
        attn = grads[f"layers_{i}"]["attn"]
        for leaf in (attn["gate_proj"]["kernel"], attn["q_norm"]["scale"],
                     attn["k_norm"]["scale"]):
            assert float(jnp.max(jnp.abs(leaf))) > 1e-6


def test_the_layers_are_of_the_two_published_kinds():
    """A full layer sees the whole past and has no positions (a permutation
    of the earlier tokens leaves the last one's output where it was); a
    sliding one sees 5 tokens and turns with position. The head norms are
    over each head's dimensions: q and k leave them at unit mean square."""
    cfg, _, params, _, _, _ = build("all")
    assert [cfg.window(i) for i in range(4)] == [5, 5, 5, None]
    a = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.hidden_size))
    reversed_past = a.at[:, :11].set(a[:, 10::-1])
    swapped_in_window = a.at[:, 7:11].set(a[:, jnp.asarray([8, 7, 10, 9])])
    new_before_window = a.at[:, :7].set(0.5)
    same = lambda layer, p, z: bool(jnp.allclose(  # noqa: E731
        layer.apply(p, a)[:, -1], layer.apply(p, z)[:, -1], atol=1e-6
    ))
    full = afmoe.Attention(cfg, afmoe.banded_attention, None)
    p = full.init(jax.random.PRNGKey(2), a)
    assert same(full, p, reversed_past) and same(full, p, swapped_in_window)
    assert not same(full, p, new_before_window)
    sliding = afmoe.Attention(cfg, afmoe.banded_attention, 5)
    assert same(sliding, p, new_before_window)
    assert not same(sliding, p, swapped_in_window)
    # a shift of every position: the sliding layer's q and k turn, the
    # full layer's are where they were
    shifted = jnp.concatenate([a[:, :1], a], 1)
    cores = lambda layer, z: layer.apply(p, z, method="qkv")  # noqa: E731
    for got, moved in ((full, False), (sliding, True)):
        q0, k0, _ = cores(got, a)
        q1, k1, _ = cores(got, shifted)
        assert bool(jnp.allclose(q0, q1[:, 1:], atol=1e-6)) != moved
        assert bool(jnp.allclose(k0, k1[:, 1:], atol=1e-6)) != moved
        np.testing.assert_allclose(
            jnp.mean(jnp.square(q0), -1), 1.0, rtol=1e-2
        )
        np.testing.assert_allclose(
            jnp.mean(jnp.square(k0), -1), 1.0, rtol=1e-2
        )
    # 4 query heads on 2 key-value heads: k and v have half q's width, the
    # gate has q's, and a head norm is one gain vector of head_dim
    shapes = jax.tree.map(jnp.shape, params["layers_1"]["attn"])
    assert shapes["q_proj"]["kernel"] == shapes["gate_proj"]["kernel"] == (32, 32)
    assert shapes["k_proj"]["kernel"] == shapes["v_proj"]["kernel"] == (32, 16)
    assert shapes["q_norm"]["scale"] == shapes["k_norm"]["scale"] == (8,)


def test_a_layer_is_gated_and_normed_after_each_branch():
    """What the layer's own equations say, step by step from its probes:
    the stream takes the POST-norm of ``(core * sigmoid(gate)) W_o`` and of
    the MLP's output, and the embedding comes in times sqrt(hidden_size)."""
    cfg, model, params, bias, x, _ = build("all")
    p = params["layers_0"]
    eps = cfg.rms_norm_eps
    x0 = params["embed_tokens"][x] * jnp.sqrt(32.0)
    got = apply(model, params, bias, x, mutable=[afmoe.MOE_PROBE])[1][
        afmoe.MOE_PROBE
    ]
    core = got["layers_0"]["attn"]["output"].reshape(2, 16, -1)
    a = reference.rms(x0, p["input_layernorm"], eps)
    gate = jax.nn.sigmoid(a @ p["attn"]["gate_proj"]["kernel"])
    h = x0 + reference.rms(
        (core * gate) @ p["attn"]["o_proj"]["kernel"],
        p["post_attention_layernorm"], eps,
    )
    u = reference.rms(h, p["pre_mlp_layernorm"], eps)
    y = h + reference.rms(
        reference.gated_mlp(u, p["mlp_dense"]), p["post_mlp_layernorm"], eps
    )
    # layer 1's expert layer reads RMSNorm_pre_mlp of layer 1's stream,
    # which starts from y: hold y through layer 1's attention branch
    h1 = reference.attention_branch(y, params["layers_1"], arch_of(cfg), 1, 8)
    u1 = reference.rms(h1, params["layers_1"]["pre_mlp_layernorm"], eps)
    np.testing.assert_allclose(
        got["layers_1"]["moe"]["input"], u1.reshape(-1, 32), atol=2e-5
    )
    # without the gate, or with the norm before the sum left out, it is off
    for wrong in (
        x0 + reference.rms(
            core @ p["attn"]["o_proj"]["kernel"],
            p["post_attention_layernorm"], eps,
        ),
        x0 + (core * gate) @ p["attn"]["o_proj"]["kernel"],
    ):
        assert not np.allclose(wrong, h, atol=1e-3)


@pytest.mark.parametrize("dense", [0, 1, 2, 4])
def test_the_leading_layers_are_dense(dense):
    cfg = AfmoeConfig.tiny(num_dense_layers=dense)
    shapes = jax.eval_shape(
        lambda: Afmoe(cfg, afmoe.banded_attention, interpret=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )
    )
    kinds = [
        "mlp_dense" if "mlp_dense" in shapes["params"][f"layers_{i}"]
        else "moe" for i in range(4)
    ]
    assert kinds == ["mlp_dense"] * dense + ["moe"] * (4 - dense)
    assert shapes["params"]["layers_0"]["mlp_dense" if dense else "moe"]
    biased = sorted(shapes.get(afmoe.ROUTER_STATE, {}))
    assert biased == [f"layers_{i}" for i in range(dense, 4)]
    if dense:
        assert shapes["params"]["layers_0"]["mlp_dense"]["up_proj"][
            "kernel"
        ].shape == (32, 48)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold two of thirty-two experts each: the held parts,
    over all sixteen shares, with the shared expert (which every chip
    computes alike) counted once, are what the uncut reference gives for
    the whole layer."""
    whole = AfmoeConfig.tiny(num_experts=32, num_experts_per_tok=4)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.hidden_size))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (32,))
    variables = ExpertLayer(whole.expert_layer, interpret=True).init(
        jax.random.PRNGKey(2), u
    )
    params = variables["params"]
    flat = u.reshape(-1, whole.hidden_size)
    shared = reference.gated_mlp(flat, params["mlp_shared"]).reshape(u.shape)
    total, landed = jnp.zeros_like(u), 0.0
    for chip in range(16):
        held = (2 * chip, 2 * chip + 1)
        cfg = AfmoeConfig.tiny(
            num_experts=32, num_experts_per_tok=4, held_experts=held
        )
        mine = {
            k: v[jnp.asarray(held)] if k.startswith("experts_") else v
            for k, v in params.items()
        }
        out, new = ExpertLayer(cfg.expert_layer, interpret=True).apply(
            {"params": mine, afmoe.ROUTER_STATE: {"bias": bias}}, u,
            mutable=[afmoe.MOE_COUNTERS],
        )
        total = total + out - shared
        counters = new[afmoe.MOE_COUNTERS]
        assert float(counters["dropped"]) == 0.0
        landed += float(counters["landed"])
    assert landed == 24 * 4  # every assignment landed on exactly one chip
    arch = dict(arch_of(whole), held=tuple(range(32)))
    uncut = reference.expert_layer(flat, params, bias, arch)
    np.testing.assert_allclose(
        total + shared, uncut.reshape(u.shape), atol=5e-6
    )


def test_the_bias_selects_never_weighs_and_moves_by_the_coefficient():
    """``router_state``: a large bias on one expert puts it among every
    token's picks and leaves its weight the unbiased score's share; after a
    step the biases have moved by ``load_balance_coeff * sign(mean load -
    load)``; the weights of a token sum to ``route_scale``."""
    cfg, model, params, bias, x, _ = build("all")
    lifted = jax.tree.map(lambda b: b.at[3].set(5.0), bias)
    probe, moved = (
        apply(model, params, lifted, x, mutable=[name])[1][name]
        for name in (afmoe.MOE_PROBE, afmoe.ROUTER_STATE)
    )
    for i in range(1, 4):
        got = probe[f"layers_{i}"]["moe"]
        assert bool(jnp.all(jnp.any(got["picks"] == 3, -1)))
        scores = got["scores"]
        assert 0.0 < float(scores.min()) and float(scores.max()) < 1.0
        load = jnp.zeros(8).at[got["picks"].reshape(-1)].add(1.0)
        np.testing.assert_allclose(
            moved[f"layers_{i}"]["moe"]["bias"],
            lifted[f"layers_{i}"]["moe"]["bias"]
            + cfg.load_balance_coeff * jnp.sign(jnp.mean(load) - load),
            atol=1e-7,
        )
    arch = arch_of(cfg)
    flat = probe["layers_1"]["moe"]["input"]
    sel, w = reference.route(
        flat, params["layers_1"]["moe"]["router"],
        lifted["layers_1"]["moe"]["bias"], arch,
    )
    np.testing.assert_array_equal(
        np.sort(sel), np.sort(probe["layers_1"]["moe"]["picks"])
    )
    np.testing.assert_allclose(jnp.sum(w, -1), cfg.route_scale, rtol=1e-5)


def test_every_attention_core_is_probed_on_its_own_inputs():
    """``moe_probe`` holds, per layer, the core's q and k AFTER the head
    norms (and rotary where the layer has positions), v and output: the
    reference's attention on those q, k, v under the layer's window gives
    the output back, and under the other kind's window does not."""
    cfg, model, params, bias, x, _ = build(held="all")
    probe = apply(model, params, bias, x, mutable=[afmoe.MOE_PROBE])[1][
        afmoe.MOE_PROBE
    ]
    assert sorted(probe) == [f"layers_{i}" for i in range(4)]
    arch = arch_of(cfg)
    x0 = params["embed_tokens"][x] * arch["embed_scale"]
    for i in range(4):
        got = probe[f"layers_{i}"]["attn"]
        assert got["q"].shape == (2, 16, 4, 8)
        assert got["k"].shape == got["v"].shape == (2, 16, 2, 8)
        q, k, v = got["q"], got["k"], got["v"]
        want = reference.banded_attention(q, k, v, cfg.window(i), 8)
        np.testing.assert_allclose(got["output"], want, atol=2e-5)
        other = None if cfg.window(i) else cfg.sliding_window
        wrong = reference.banded_attention(q, k, v, other, 8)
        assert not np.allclose(got["output"], wrong, atol=1e-3)
    # layer 0's q is the reference's: normed per head, then turned
    p = params["layers_0"]
    a = reference.rms(x0, p["input_layernorm"], cfg.rms_norm_eps)
    q, k, _ = reference.attention_qkv(a, p["attn"], arch, True)
    np.testing.assert_allclose(probe["layers_0"]["attn"]["q"], q, atol=2e-5)
    np.testing.assert_allclose(probe["layers_0"]["attn"]["k"], k, atol=2e-5)


@pytest.mark.parametrize("window", [12, 20, None])
def test_the_banded_kernel_at_eight_query_heads_a_key_value_head(window):
    """``ops/pallas_attn.flash_attention`` interpreted, at the cell's head
    counts in small (8 query heads on each of 2 key-value heads), a window
    shorter than T and T (40) no multiple of it, blocks of 8 so that a band
    has whole blocks and two masked edges: forward and the three gradients
    against the masked einsum."""
    from pytorch_distributedtraining_tpu.ops.pallas_attn import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, 40, 16, 8))
    k, v = (jax.random.normal(key, (2, 40, 2, 8)) for key in keys[1:3])
    mix = jax.random.normal(keys[3], q.shape)
    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, 8, 8, True, window
    )
    einsum = lambda q, k, v: afmoe.banded_attention(  # noqa: E731
        q, k, v, window=window
    )
    np.testing.assert_allclose(kernel(q, k, v), einsum(q, k, v), atol=2e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * mix), argnums=(0, 1, 2)
    )(q, k, v)
    for got, want in zip(grads(kernel), grads(einsum)):
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_model_says_its_paths_and_names_its_scopes():
    """Every traced layer leaves an ``attention.path`` instant (the kernel
    unless told otherwise), every traced expert layer a ``routing.path``;
    and the compiled step's instructions carry the scopes the benchmark's
    readers look for (``tests/test_program_spans.py``'s way)."""
    from pytorch_distributedtraining_tpu import optim, parallel
    from pytorch_distributedtraining_tpu.observe import trace
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    tracer = trace.get_tracer()
    was = tracer.enabled
    cfg, model, params, bias, x, y = build("some", remat=True)
    einsum = Afmoe(cfg, afmoe.banded_attention, interpret=True)
    kernels = lambda m: str(  # noqa: E731
        jax.make_jaxpr(lambda p: apply(m, p, bias, x))(params)
    ).count("pallas_call")
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        assert kernels(model) - kernels(einsum) == cfg.num_hidden_layers
        said = {
            name: [r["attrs"] for r in trace.records() if r["name"] == name]
            for name in ("attention.path", "routing.path")
        }
    finally:
        trace.clear()
        tracer.enabled = was
    paths = said["attention.path"]
    assert [a["window"] for a in paths[:4]] == [5, 5, 5, None]
    assert {a["path"] for a in paths[:4]} == {"kernel"}
    assert {a["path"] for a in paths[4:]} == {"attn_fn"}
    assert {(a["heads"], a["kv_heads"], a["t"]) for a in paths} == {(4, 2, 16)}
    # three expert layers, each traced with the kernel and with the einsum
    assert len(said["routing.path"]) == 2 * 3
    assert {(a["path"], a["k"], a["d"]) for a in said["routing.path"]} == {
        ("jnp", 2, 32)
    }

    def loss_fn(params, batch, rng, model_state):
        logits, new = model.apply(
            {"params": params, **model_state}, batch[0],
            mutable=[afmoe.ROUTER_STATE, afmoe.MOE_COUNTERS],
        )
        return cross_entropy_loss(logits, batch[1]), {
            "model_state": {afmoe.ROUTER_STATE: new[afmoe.ROUTER_STATE]},
            **afmoe.routing_counters(new[afmoe.MOE_COUNTERS]),
        }

    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-2, clip_grad_norm=1.0)
    state, shardings = parallel.create_train_state(
        init_fn=lambda rng: (params, {afmoe.ROUTER_STATE: bias}), tx=tx,
        mesh=mesh, policy=parallel.DDP(), rng=jax.random.PRNGKey(0),
    )
    step = parallel.TrainStep(
        loss_fn, tx, mesh, parallel.DDP(), state_shardings=shardings,
        donate=False,
    )
    text = step.compiled_text(state, (x, y))
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {tuple(re.split(r"[/()]+", n)) for n in names}
    for scope in (
        "qk_norm", "attention_gate", "post_norm", "attention",
        "attention_sliding", "attention_global", "router", "dispatch",
        "experts", "combine", "shared_expert", "mlp_dense", "embed", "head",
    ):
        assert any(scope in path for path in found), scope
    # the gate's scope holds its projection, forward and backward
    assert any(
        "attention_gate" in p and "gate_proj" in p and "transpose" in p
        for p in found
    )
    assert any("attention_sliding" in p and "layers_0" in p for p in found)
    assert not any("attention_sliding" in p and "layers_3" in p for p in found)

    # the same step trains: counters ride it, the biases move, the loss falls
    losses = []
    with mesh:
        for _ in range(3):
            state, metrics = step(state, (x, y))
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert float(metrics["dropped_assignments"]) == 0.0
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0
    # 3 of 8 experts held, 2 picks a token, 3 expert layers of 32 tokens
    assert 0 < float(metrics["assignments_landed"]) <= 3 * 32 * 2
    moved = state.model_state[afmoe.ROUTER_STATE]["layers_1"]["moe"]["bias"]
    assert not np.allclose(moved, bias["layers_1"]["moe"]["bias"])


def test_the_two_copies_of_the_reference_are_one_text():
    assert filecmp.cmp(
        os.path.join(REPO, "chipbench", "reference", "afmoe.py"),
        os.path.join(
            REPO, "pytorch_distributedtraining_tpu", "models",
            "afmoe_reference.py",
        ),
        shallow=False,
    )


def test_the_attention_core_is_the_one_smallthinker_runs():
    """One function places the banded kernel for both models, so a change
    to it is judged on both cells."""
    from pytorch_distributedtraining_tpu.models import smallthinker

    assert afmoe.attention_core is smallthinker.attention_core
    assert afmoe.banded_attention is smallthinker.banded_attention
    assert afmoe.ExpertLayer is ExpertLayer


def test_rematerialised_layers_run_each_attention_kernel_once(monkeypatch):
    """Under ``remat=True`` a layer keeps its attention kernel's output and
    row statistics: the gradient holds the kernels of the plain model plus
    the rematerialised grouped matmuls, and no second attention forward. A
    forward rule that did not name its residuals ran one more a layer."""
    from pytorch_distributedtraining_tpu.ops import pallas_attn

    def kernels(remat):
        cfg, model, params, bias, x, y = build("some", remat=remat)
        return cfg.num_hidden_layers, str(jax.make_jaxpr(jax.grad(
            lambda p: cross_entropy_loss(apply(model, p, bias, x), y)
        ))(params)).count("pallas_call[")

    layers, plain = kernels(False)
    _, kept = kernels(True)
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    _, untagged = kernels(True)
    assert untagged - kept == layers
    # what is still rematerialised: the expert layers' forward kernels
    assert kept > plain
