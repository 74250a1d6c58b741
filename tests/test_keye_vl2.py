"""Keye-VL-2.0-30B-A3B's trunk (``models/keye_vl2.py``) against its plain
float32 reference at a tiny size on seeded random weights: the forward, both
losses and every gradient; the two losses' gradients kept apart; M-RoPE's
three streams; the held experts' shares."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models import KeyeVL2, KeyeVL2Config
from pytorch_distributedtraining_tpu.models import cross_entropy_loss
from pytorch_distributedtraining_tpu.models import keye_vl2
from pytorch_distributedtraining_tpu.models import keye_vl2_reference as reference
from pytorch_distributedtraining_tpu.models.glm4_moe_lite import rotary
from pytorch_distributedtraining_tpu.ops import sparse_attn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 96  # three blocks of 32: the selection (topk 16) is exercised


def arch_of(cfg):
    return {
        "layers": cfg.num_hidden_layers, "heads": cfg.num_attention_heads,
        "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "top_k": cfg.num_experts_per_tok, "eps": cfg.rms_norm_eps,
        "theta": cfg.rope_theta, "section": cfg.mrope_section,
        "indexer_heads": cfg.indexer_num_heads,
        "indexer_dim": cfg.indexer_head_dim, "topk": cfg.indexer_topk,
        "held": cfg.held,
    }


def build(held=(1, 4), seed=0, **kw):
    """Model (kernels interpreted), parameters (the norms' gains and the
    indexer's LayerNorm drawn too, and every matrix at 0.2 so that the
    scores tell keys apart), a batch of two sequences of T."""
    cfg = KeyeVL2Config.tiny(held_experts=held, **kw)
    model = KeyeVL2(cfg, interpret=True)
    tok = np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, T + 1))
    x, y = tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)
    variables = model.init(jax.random.PRNGKey(seed), x[:, :32])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
            if path[-1].key == "scale" else
            0.3 * jax.random.normal(next(keys), leaf.shape)
            if path[-1].key == "bias" else
            0.2 * jax.random.normal(next(keys), leaf.shape)
        ),
        variables["params"],
    )
    return cfg, model, params, x, y


def program_losses(model, params, x, y):
    logits, new = model.apply(
        {"params": params}, x, mutable=[keye_vl2.INDEXER_STATS]
    )
    counters = keye_vl2.indexer_counters(new[keye_vl2.INDEXER_STATS], x.size)
    return cross_entropy_loss(logits, y), counters["indexer_kl"], logits


def _same_selection(cfg, model, params, x):
    """The program's selections are the reference's top-k (f32 scores)."""
    probe = model.apply({"params": params}, x, mutable=["moe_probe"])[1]
    for name in sorted(probe["moe_probe"]):
        got = probe["moe_probe"][name]["self_attn"]
        qi, ki, w = got["qi"], got["ki"], got["w"]
        for b in range(x.shape[0]):
            sel, _ = sparse_attn.sparse_select(
                jnp.swapaxes(qi[b], 0, 1), ki[b], w[b].astype(jnp.float32),
                topk=cfg.indexer_topk, blk=cfg.indexer_block, interpret=True,
            )
            scores = reference.indexer_scores(qi[b:b + 1], ki[b:b + 1], w[b:b + 1])
            want = reference.selection(scores, 0, cfg.indexer_topk)[0]
            np.testing.assert_array_equal(
                sparse_attn.unpack_selection(sel), want
            )


@pytest.mark.parametrize("held", [None, (1, 4)], ids=["all", "some"])
def test_forward_and_both_losses_match_the_reference(held):
    cfg, model, params, x, y = build(held)
    _same_selection(cfg, model, params, x)
    ce, kl, logits = program_losses(model, params, x, y)
    want_logits, want_kl = reference.forward(params, x, arch_of(cfg), chunk=32)
    want_ce, _ = reference.losses(params, x, y, arch_of(cfg), chunk=32)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(ce, want_ce, rtol=1e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-4, atol=1e-6)
    assert float(kl) > 0


def test_every_gradient_leaf_matches_the_reference():
    cfg, model, params, x, y = build()

    def loss(p):
        ce, kl, _ = program_losses(model, p, x, y)
        return ce + kl

    value, grads = jax.value_and_grad(loss)(params)
    want, want_grads = reference.loss_and_grads(params, x, y, arch_of(cfg), chunk=32)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    wanted = jax.tree.leaves(want_grads)
    for (path, got), ref in zip(flat, wanted):
        scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
        np.testing.assert_allclose(
            got / scale, ref / scale, atol=2e-4, err_msg=jax.tree_util.keystr(path)
        )


def _is_indexer(path):
    return "indexer" in jax.tree_util.keystr(path)


def test_the_two_losses_reach_their_own_parameters():
    """The cross-entropy gives the indexer exactly zero gradient; the KL
    gives exactly zero to everything but the indexer."""
    cfg, model, params, x, y = build()
    for which in (0, 1):
        grads = jax.grad(lambda p: program_losses(model, p, x, y)[which])(params)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            zero = not np.any(np.asarray(g))
            reached = _is_indexer(path) == (which == 1)
            assert zero != reached, (which, jax.tree_util.keystr(path))


def test_mrope_takes_each_stream_by_its_section_and_is_rotary_on_text():
    cfg = KeyeVL2Config.tiny()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, cfg.head_dim))
    pos = jax.random.randint(jax.random.PRNGKey(1), (3, 2, 12), 0, 50)
    got = keye_vl2.mrope(x, pos, cfg.rope_theta, cfg.mrope_section)
    want = reference.mrope(x, pos, cfg.rope_theta, cfg.mrope_section)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the streams differ: moving one stream moves exactly its section's pairs
    moved = keye_vl2.mrope(
        x, pos.at[1].add(7), cfg.rope_theta, cfg.mrope_section
    )
    half, (s0, s1, _) = cfg.head_dim // 2, cfg.mrope_section
    changed = np.any(np.asarray(moved != got), axis=(0, 1, 2))
    pairs = changed[:half] | changed[half:]
    assert pairs.tolist() == [s0 <= i < s0 + s1 for i in range(half)]
    text = keye_vl2.text_positions(2, 12)
    np.testing.assert_allclose(
        keye_vl2.mrope(x, text, cfg.rope_theta, cfg.mrope_section),
        rotary(x, cfg.rope_theta), rtol=1e-5, atol=1e-5,
    )


def test_text_positions_through_the_model_are_the_default():
    cfg, model, params, x, _ = build()
    a = model.apply({"params": params}, x)
    b = model.apply({"params": params}, x, keye_vl2.text_positions(*x.shape))
    np.testing.assert_array_equal(a, b)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips hold two of the 8 experts each: their expert layers'
    outputs on one input add up to the reference's layer with all 8."""
    cfg = KeyeVL2Config.tiny()
    arch = arch_of(cfg)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(keys[0], (40, cfg.hidden_size))
    w_r = jax.random.normal(keys[1], (cfg.hidden_size, cfg.num_experts))
    full = {
        name: 0.2 * jax.random.normal(k, shape) for name, k, shape in zip(
            ("experts_gate", "experts_up", "experts_down"), keys[2:],
            ((8, cfg.hidden_size, 24), (8, cfg.hidden_size, 24),
             (8, 24, cfg.hidden_size)),
        )
    }
    sel, w = reference.route(u, w_r, arch)
    want = reference.expert_layer(u, full, sel, w, arch)
    total = 0
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        layer = keye_vl2.ExpertLayer(
            KeyeVL2Config.tiny(held_experts=held), interpret=True
        )
        part = {k: v[np.asarray(held)] for k, v in full.items()}
        total = total + layer.apply({"params": part}, u[None], w_r)[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_the_layer_counts_the_keys_it_selected():
    cfg, model, params, x, _ = build()
    _, new = model.apply(
        {"params": params}, x, mutable=[keye_vl2.INDEXER_STATS]
    )
    counters = keye_vl2.indexer_counters(new[keye_vl2.INDEXER_STATS], x.size)
    want = sum(min(t + 1, cfg.indexer_topk) for t in range(T))
    assert int(counters["selected_keys"]) == 2 * 2 * want  # layers x batch
    assert float(counters["selected_keys_per_query"]) == pytest.approx(want / T)
    assert counters["indexer_kl_layers"].shape == (cfg.num_hidden_layers,)


def test_the_two_copies_of_the_reference_are_one_text():
    assert filecmp.cmp(
        os.path.join(REPO, "chipbench", "reference", "keye_vl2.py"),
        os.path.join(
            REPO, "pytorch_distributedtraining_tpu", "models",
            "keye_vl2_reference.py",
        ),
        shallow=False,
    )
