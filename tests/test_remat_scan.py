"""Scan-over-layers + named remat policies (ISSUE 3).

Three contracts under test:

1. **Numerics**: remat never changes math — loss AND grads are allclose
   across every policy in the registry × scan_layers on/off, with scanned
   grads converted back to loop layout leaf-for-leaf (so the layout
   converters are covered by the same assertion).
2. **Memory**: XLA's compiled memory plan (``TrainStep.memory_analysis``)
   shows per-block remat strictly cutting projected peak vs "none", and
   the batch-size auto-tuner walks the projection correctly.
3. **Checkpoint compat**: a torch-named SwinIR checkpoint loads into the
   loop layout, stacks into the scan layout, and both models produce the
   same output — scanned models stay interchangeable with the reference's
   checkpoint family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu.models.gpt2 import (
    GPT2,
    GPT2Config,
    cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.models.scan_utils import (
    stack_layer_params,
    unstack_layer_params,
)
from pytorch_distributedtraining_tpu.models.swinir import (
    SwinIR,
    stack_swinir_layer_params,
    unstack_swinir_layer_params,
)
from pytorch_distributedtraining_tpu.models.vit import ViT, ViTConfig
from pytorch_distributedtraining_tpu.observe.memory import (
    MemoryStats,
    tune_batch_size,
)
from pytorch_distributedtraining_tpu.analyze.trace_rules import _walk_eqns
from pytorch_distributedtraining_tpu.ops import pallas_attn
from pytorch_distributedtraining_tpu.parallel.remat import (
    CHECKPOINT_SAVED_NAMES,
    KERNEL_LSE_ROWS,
    KERNEL_RESIDUALS,
    REMAT_POLICIES,
    apply_remat,
    checkpoint_policy,
    kept_names,
    resolve_remat,
)

# "offload" is registered but needs a pinned_host memory space — exercised
# on real chips, not the CPU test mesh
MATRIX_POLICIES = ("none", "full", "dots", "names")


def _flat(tree) -> dict:
    return {
        jax.tree_util.keystr(p): leaf
        for p, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


# ---------------------------------------------------------------- registry


def test_resolve_remat_forms():
    assert resolve_remat(None) == "none"
    assert resolve_remat(False) == "none"
    assert resolve_remat(True) == "full"
    assert resolve_remat("") == "none"
    assert resolve_remat("0") == "none"
    assert resolve_remat("1") == "full"
    assert resolve_remat("DOTS") == "dots"
    assert REMAT_POLICIES == ("none", "full", "dots", "names", "offload")
    for name in REMAT_POLICIES:
        assert resolve_remat(name) == name
    with pytest.raises(ValueError, match="remat"):
        resolve_remat("bogus")


def test_checkpoint_policy_registry():
    assert checkpoint_policy("none") is None
    # every policy that rematerialises keeps the attention kernels'
    # residuals: none asks for a kernel to be run twice
    for name in ("full", "dots", "names", "offload"):
        assert callable(checkpoint_policy(name))
        assert KERNEL_RESIDUALS in kept_names(name)
    assert kept_names("none") == kept_names("none", stacked=True) == ()
    assert kept_names("full") == (KERNEL_RESIDUALS,)
    assert kept_names("names") == CHECKPOINT_SAVED_NAMES
    assert set(CHECKPOINT_SAVED_NAMES) == {"attn_out", KERNEL_RESIDUALS}
    # where the kept residuals are stacked over a scan's layers, the row
    # statistics are kept as dense rows besides
    for name in ("full", "dots", "names", "offload"):
        assert kept_names(name, stacked=True) == kept_names(name) + (
            KERNEL_LSE_ROWS,
        )
    # the kernels' file names its residuals itself; the registry's
    # spellings are those
    assert pallas_attn.RESIDUALS_NAME == KERNEL_RESIDUALS
    assert pallas_attn.DENSE_LSE_NAME == KERNEL_LSE_ROWS


def test_apply_remat_none_is_identity():
    fn = lambda x: x * 2  # noqa: E731
    assert apply_remat(fn, "none") is fn
    assert apply_remat(fn, False) is fn
    assert apply_remat(fn, "full") is not fn


def test_policy_remat_validates_at_construction():
    from pytorch_distributedtraining_tpu.parallel import DDP

    assert DDP(remat="dots").remat_policy == "dots"
    assert DDP(remat=True).remat_policy == "full"
    with pytest.raises(ValueError, match="remat"):
        DDP(remat="bogus")


# ----------------------------------------- what a checkpointed layer keeps

_B, _T, _H, _DH = 1, 256, 2, 64


def _layer_split(w, x):
    q, k, v = (
        a.reshape(_B, _T, _H, _DH) for a in jnp.split(x @ w, 3, axis=-1)
    )
    out = pallas_attn.flash_attention(q, k, v, True, 128, 128, True, 128)
    return out.reshape(_B, _T, -1)


def _layer_qkv(w, x):
    return pallas_attn.flash_attention_qkv(x @ w, _H, 128, 128, True)


def _layer_lanes(w, x):
    q, k, v = jnp.split(x @ w, 3, axis=-1)
    return pallas_attn.flash_attention_lanes(q, k, v, _DH, 128, 128, True)


KERNEL_LAYERS = {
    "flash_attention": _layer_split,
    "flash_attention_qkv": _layer_qkv,
    "flash_attention_lanes": _layer_lanes,
}


def _layer_inputs():
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    d = _H * _DH
    return (
        jax.random.normal(kw, (d, 3 * d), jnp.float32) * 0.05,
        jax.random.normal(kx, (_B, _T, d), jnp.float32),
    )


def _grad_of(layer, policy):
    wrapped = apply_remat(layer, policy)
    return jax.grad(lambda w, x: jnp.sum(wrapped(w, x) ** 2), argnums=(0, 1))


def _primitives(jaxpr) -> list:
    """Every equation's primitive name, sub-jaxprs included."""
    return [eqn.primitive.name for eqn in _walk_eqns(jaxpr)]


def _kernels_in(fn, *args) -> int:
    return _primitives(jax.make_jaxpr(fn)(*args).jaxpr).count("pallas_call")


@pytest.mark.parametrize("policy", ["full", "dots", "names"])
@pytest.mark.parametrize("kernel", sorted(KERNEL_LAYERS))
def test_checkpointed_layer_runs_the_forward_kernel_once(kernel, policy):
    """The gradient of a checkpointed layer holds three kernels (forward,
    dq, dk/dv): the backward reads the kept ``out`` and ``lse`` where it
    would else run the forward kernel again. Gradients equal the
    uncheckpointed layer's to the bit."""
    layer, args = KERNEL_LAYERS[kernel], _layer_inputs()
    assert _kernels_in(_grad_of(layer, policy), *args) == 3
    got = jax.jit(_grad_of(layer, policy))(*args)
    want = jax.jit(_grad_of(layer, "none"))(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kernel", sorted(KERNEL_LAYERS))
def test_untagged_forward_rule_runs_twice_under_full(kernel, monkeypatch):
    """What the tag spares: a forward rule as it was before it named its
    residuals leaves ``full`` nothing of the layer to keep, and the
    rematerialised computation holds a second forward kernel."""
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    layer, args = KERNEL_LAYERS[kernel], _layer_inputs()
    assert _kernels_in(_grad_of(layer, "full"), *args) == 4
    assert _kernels_in(_grad_of(layer, "none"), *args) == 3


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("kernel", sorted(KERNEL_LAYERS))
def test_full_keeps_one_output_and_one_statistic(kernel, stacked):
    """Beside its arguments a layer under ``full`` saves the kernel's
    ``out`` and ``lse``, once each, and nothing else: ``lse`` as the kernel
    wrote it, or, where what is kept is stacked over a scan's layers, as
    whole rows of 128 lanes alone (a [.., T, 8] array is padded to 16 times
    its bytes in a TPU's memory)."""
    from jax._src.ad_checkpoint import saved_residuals

    layer, args = KERNEL_LAYERS[kernel], _layer_inputs()
    saved = saved_residuals(
        jax.checkpoint(layer, policy=checkpoint_policy("full", stacked)),
        *args,
    )
    kept = [
        (aval.shape, aval.dtype) for aval, why in saved
        if "from the argument" not in why
    ]
    assert len(kept) == 2, saved
    out_shape = (
        (_B, _T, _H, _DH) if kernel == "flash_attention"
        else (_B, _T, _H * _DH)
    )
    lse_shape = (_B, _H, _T, pallas_attn._STAT_LANES)
    if stacked:
        lse_shape = (_B * _H * _T * pallas_attn._STAT_LANES // 128, 128)
        assert any(f"named '{KERNEL_LSE_ROWS}'" in why for _, why in saved)
    assert sorted(kept) == sorted(
        [(out_shape, jnp.float32), (lse_shape, jnp.float32)]
    )


@pytest.mark.parametrize("kernel", sorted(KERNEL_LAYERS))
def test_without_remat_the_program_is_what_it_was(kernel, monkeypatch):
    """Outside a checkpoint the tags are identities: the gradient's
    equations are the untagged rule's plus the three ``name``s and the two
    reshapes around the dense rows', which cancel when the program is
    compiled: the compiled program is the untagged rule's text."""
    import collections
    import re

    layer, args = KERNEL_LAYERS[kernel], _layer_inputs()

    def program():
        fn = _grad_of(layer, False)
        prims = _primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        text = jax.jit(fn).lower(*args).compile().as_text()
        # without the stack frames' tables, what points into them, and the
        # counters in the instructions' names
        text = text[text.index("\n\n%"):]
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return collections.Counter(prims), re.sub(r"[._]\d+\b", "", text)

    prims, text = program()
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    was_prims, was_text = program()
    assert prims - was_prims == {"name": 3, "reshape": 2}
    assert not was_prims - prims
    assert text == was_text


@pytest.mark.parametrize("tagged", [False, True])
def test_lint_knows_what_names_keeps_beyond_full(tagged):
    """``remat-tag-coverage`` warns where ``names`` would keep nothing that
    ``full`` does not keep: a step whose only tags are the kernels' own is
    such a step; one that tags ``attn_out`` is not."""
    from jax.ad_checkpoint import checkpoint_name

    from pytorch_distributedtraining_tpu.analyze.registry import (
        RULES, AnalysisContext,
    )

    def layer(w, x):
        out = _layer_qkv(w, x)
        return checkpoint_name(out, "attn_out") if tagged else out

    jaxpr = jax.make_jaxpr(_grad_of(layer, "names"))(*_layer_inputs())
    assert KERNEL_RESIDUALS in str(jaxpr)
    found = list(RULES["remat-tag-coverage"].fn(
        AnalysisContext(jaxpr=jaxpr, remat="names")
    ))
    assert len(found) == (0 if tagged else 1)
    if found:
        assert "behaves like remat='full'" in found[0].message
        assert "attn_out" in found[0].message


# ---------------------------------------------------- numerical equivalence


def _gpt2_loss_and_grads(cfg, params, tok, tgt):
    model = GPT2(cfg)

    def loss_fn(p):
        return cross_entropy_loss(model.apply({"params": p}, tok), tgt)

    return jax.value_and_grad(loss_fn)(params)


def test_gpt2_remat_scan_equivalence_matrix():
    """loss/grads identical across remat policy × scan_layers on a 2-block
    model; scanned grads unstack back to the loop layout for comparison."""
    ref_cfg = GPT2Config.tiny(n_layer=2, n_positions=16)
    tok = (jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) * 7) % 256
    tgt = jnp.roll(tok, -1, axis=1)
    params = GPT2(ref_cfg).init(jax.random.PRNGKey(0), tok)["params"]
    ref_loss, ref_grads = _gpt2_loss_and_grads(ref_cfg, params, tok, tgt)
    stacked = stack_layer_params(dict(params), "h_", 2, "h")

    for scan in (False, True):
        for remat in MATRIX_POLICIES:
            cfg = GPT2Config.tiny(
                n_layer=2, n_positions=16, remat=remat, scan_layers=scan
            )
            p = stacked if scan else params
            loss, grads = _gpt2_loss_and_grads(cfg, p, tok, tgt)
            if scan:
                grads = unstack_layer_params(dict(grads), "h", "h_", 2)
            tag = f"scan={scan} remat={remat}"
            np.testing.assert_allclose(
                float(loss), float(ref_loss), rtol=1e-5, err_msg=tag
            )
            ref_flat, got_flat = _flat(ref_grads), _flat(grads)
            assert set(got_flat) == set(ref_flat), tag
            for k, a in ref_flat.items():
                np.testing.assert_allclose(
                    np.asarray(got_flat[k]), np.asarray(a),
                    rtol=2e-4, atol=1e-5, err_msg=f"{tag} leaf {k}",
                )


def test_vit_scan_matches_loop():
    cfg = ViTConfig.tiny()
    img = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
    params = ViT(cfg).init(jax.random.PRNGKey(0), img)["params"]
    ref = ViT(cfg).apply({"params": params}, img)

    stacked = stack_layer_params(
        dict(params), "encoder_", cfg.num_layers, "encoder"
    )
    scan_cfg = ViTConfig.tiny(scan_layers=True)
    out = ViT(scan_cfg).apply({"params": stacked}, img)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )

    # converter round trip is leaf-exact
    back = unstack_layer_params(
        dict(stacked), "encoder", "encoder_", cfg.num_layers
    )
    pf, bf = _flat(params), _flat(back)
    assert set(pf) == set(bf)
    for k in pf:
        np.testing.assert_array_equal(np.asarray(pf[k]), np.asarray(bf[k]))


SWINIR_CFG = dict(
    img_size=8, window_size=4, depths=(2, 2), embed_dim=16,
    num_heads=(2, 2), mlp_ratio=2.0,
)


def test_swinir_scan_matches_loop():
    model = SwinIR(**SWINIR_CFG)
    x = np.random.default_rng(0).random((2, 8, 8, 3)).astype(np.float32)
    params = model.init(jax.random.PRNGKey(1), x[:1])["params"]
    ref = model.apply({"params": params}, x)

    stacked = stack_swinir_layer_params(dict(params), (2, 2))
    out = SwinIR(**SWINIR_CFG, scan_layers=True).apply(
        {"params": stacked}, x
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )

    back = unstack_swinir_layer_params(dict(stacked), (2, 2))
    pf, bf = _flat(params), _flat(back)
    assert set(pf) == set(bf)
    for k in pf:
        np.testing.assert_array_equal(np.asarray(pf[k]), np.asarray(bf[k]))


def test_swinir_scan_matches_loop_from_torch_checkpoint():
    """Acceptance: the SAME torch checkpoint drives both layouts to the
    same output — torch names → loop layout → stack → scanned model."""
    pytest.importorskip("torch")
    from pytorch_distributedtraining_tpu import interop
    from pytorch_distributedtraining_tpu.models.swinir import TORCH_KEY_MAP

    model = SwinIR(**SWINIR_CFG)
    x = np.random.default_rng(3).random((2, 8, 8, 3)).astype(np.float32)
    src = model.init(jax.random.PRNGKey(4), x[:1])["params"]
    sd = interop.torch_swinir_state_dict(src, model=model)

    template = model.init(jax.random.PRNGKey(9), x[:1])["params"]
    loaded = interop.load_torch_into_template(
        interop._to_numpy_tree(sd), template,
        key_map=TORCH_KEY_MAP, strict=True,
    )
    loop_out = model.apply({"params": loaded}, x)
    scan_out = SwinIR(**SWINIR_CFG, scan_layers=True).apply(
        {"params": stack_swinir_layer_params(dict(loaded), (2, 2))}, x
    )
    np.testing.assert_allclose(
        np.asarray(scan_out), np.asarray(loop_out), rtol=1e-5, atol=1e-5
    )
    # and both reproduce the checkpoint's source model
    np.testing.assert_allclose(
        np.asarray(loop_out),
        np.asarray(model.apply({"params": src}, x)),
        atol=1e-6,
    )


def test_swinir_odd_depth_falls_back_to_loop():
    """depth=1 can't form shift pairs: scan_layers must quietly keep the
    loop layout (layer_0 params), not fail or change names."""
    kw = dict(
        img_size=8, window_size=4, depths=(1,), embed_dim=12,
        num_heads=(2,), mlp_ratio=2.0, scan_layers=True,
    )
    x = jnp.ones((1, 8, 8, 3)) * 0.5
    params = SwinIR(**kw).init(jax.random.PRNGKey(0), x)["params"]
    assert "layer_0" in params["rstb_0"]
    assert "layers" not in params["rstb_0"]


# --------------------------------------------------------- memory accounting


def test_memory_stats_peak_derivation():
    ms = MemoryStats(
        argument_bytes=100, output_bytes=50, temp_bytes=30,
        alias_bytes=60, generated_code_bytes=7,
    )
    assert ms.peak_bytes == 120
    assert ms.as_dict()["peak_bytes"] == 120


def _gpt2_step(devices, remat, scan_layers, tok):
    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.parallel import (
        DDP, TrainStep, create_train_state,
    )
    from pytorch_distributedtraining_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )

    cfg = GPT2Config.tiny(
        n_layer=4, n_positions=tok.shape[1], remat=remat,
        scan_layers=scan_layers,
    )
    model = GPT2(cfg)
    mesh = make_mesh(MeshSpec.ddp(8), devices=devices)
    tx = optim.adamw(lr=1e-3)

    def loss_fn(params, batch, rng, ms):
        t, y = batch
        return cross_entropy_loss(model.apply({"params": params}, t), y), {}

    state, sh = create_train_state(
        init_fn=lambda r: (model.init(r, tok)["params"], {}),
        tx=tx, mesh=mesh, policy=DDP(),
    )
    return TrainStep(
        loss_fn, tx, mesh, DDP(), state_shardings=sh, donate=False
    ), state


def test_trainstep_memory_monotonic(devices8):
    """Per-block remat must cut the compiled step's projected peak HBM
    (the ISSUE's bigger-batches claim, asserted on XLA's own memory plan):
    scan+full < scan none and < loop none. The unrolled pair is asked of
    the TPU's planner (``tests/test_chip_compile.py::
    test_remat_cuts_the_planned_peak``), which plans full at a fifth of
    none: XLA:CPU expands jax.checkpoint's optimization barrier before it
    schedules, hoists the unrolled program's recomputation (95 dots against
    75: it is there) to where the forward ran, and plans the same peak for
    both, to the byte."""
    tok = jnp.arange(8 * 128, dtype=jnp.int32).reshape(8, 128) % 256
    tgt = jnp.roll(tok, -1, axis=1)
    batch = (tok, tgt)

    peaks = {}
    for scan, remat in ((False, "none"), (True, "none"), (True, "full")):
        step, state = _gpt2_step(devices8, remat, scan, tok)
        mem = step.memory_analysis(state, batch)
        assert mem is not None and mem.temp_bytes > 0
        peaks[(scan, remat)] = mem.peak_bytes

    assert peaks[(True, "full")] < peaks[(True, "none")], peaks
    assert peaks[(True, "full")] < peaks[(False, "none")], peaks


def test_tune_batch_size_walks_up():
    calls = []

    def peak(b):
        calls.append(b)
        return b * 100

    best = tune_batch_size(peak, budget_bytes=1000, safety=1.0)
    assert best == 10
    assert calls[0] == 1  # starts at start=1, doubles, then refines

    # everything fits up to the ceiling
    assert tune_batch_size(
        lambda b: 1, budget_bytes=1000, max_batch=64
    ) == 64


def test_tune_batch_size_edge_cases():
    # analysis unavailable -> never guess, return start unchanged
    assert tune_batch_size(
        lambda b: None, budget_bytes=1000, start=3
    ) == 3
    # start already over budget -> explicit error
    with pytest.raises(ValueError, match="exceeds"):
        tune_batch_size(lambda b: 10_000, budget_bytes=1000)
    # no budget and none detectable on CPU -> explicit error
    with pytest.raises(ValueError, match="budget"):
        tune_batch_size(lambda b: 1)


# ------------------------------------------------------------- env plumbing


def test_facade_scan_layers_env(monkeypatch):
    from pytorch_distributedtraining_tpu.stoke.facade import (
        _apply_scan_layers_env,
    )

    monkeypatch.delenv("GRAFT_SCAN_LAYERS", raising=False)
    m = SwinIR(**SWINIR_CFG)
    assert _apply_scan_layers_env(m) is m  # env unset: untouched

    monkeypatch.setenv("GRAFT_SCAN_LAYERS", "1")
    assert _apply_scan_layers_env(m).scan_layers is True
    # cfg-carried flag (GPT2/ViT) flips through dataclasses.replace
    g = GPT2(GPT2Config.tiny())
    assert _apply_scan_layers_env(g).cfg.scan_layers is True

    monkeypatch.setenv("GRAFT_SCAN_LAYERS", "0")
    on = SwinIR(**SWINIR_CFG, scan_layers=True)
    assert _apply_scan_layers_env(on).scan_layers is False


def test_facade_remat_env(monkeypatch):
    from pytorch_distributedtraining_tpu.stoke.facade import _remat_from_env

    monkeypatch.delenv("GRAFT_REMAT", raising=False)
    assert _remat_from_env(False) is False
    assert _remat_from_env("dots") == "dots"

    monkeypatch.setenv("GRAFT_REMAT", "names")
    assert _remat_from_env(False) == "names"
    assert _remat_from_env("dots") == "dots"  # explicit config wins

    monkeypatch.setenv("GRAFT_REMAT", "bogus")
    with pytest.raises(ValueError, match="remat"):
        _remat_from_env(False)
