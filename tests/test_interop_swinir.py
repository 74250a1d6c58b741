"""Torch-SwinIR checkpoint naming → framework params.

Builds a state_dict in the official torch-SwinIR naming
(`layers.N.residual_group.blocks.M.*`, the family the reference loads at
`Stoke-DDP.py:209-213`), nested under 'params' exactly like the
002_lightweightSR checkpoints, including torch-only buffers, and proves a
strict load through the facade reproduces the source model bit-for-bit.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import losses
from pytorch_distributedtraining_tpu.checkpoint import tree_to_flat_dict
from pytorch_distributedtraining_tpu.models.swinir import SwinIR, TORCH_KEY_MAP
from pytorch_distributedtraining_tpu.stoke import Stoke, StokeOptimizer

torch = pytest.importorskip("torch")

CFG = dict(
    img_size=8, window_size=4, depths=(2, 2), embed_dim=16,
    num_heads=(2, 2), mlp_ratio=2.0,
)


def _torch_swinir_state_dict(params) -> dict:
    """Production exporter incl. the torch-only registered buffers the
    loader must drop under strict=True (single source of truth in
    interop.torch_swinir_state_dict)."""
    from pytorch_distributedtraining_tpu import interop

    return interop.torch_swinir_state_dict(params, model=SwinIR(**CFG))


def test_torch_swinir_checkpoint_strict_load(tmp_path):
    model = SwinIR(**CFG)
    x = np.random.default_rng(0).random((8, 8, 8, 3)).astype(np.float32)
    src_params = model.init(jax.random.PRNGKey(1), x[:1])["params"]
    ref_out = model.apply({"params": src_params}, x)

    path = str(tmp_path / "swinir_lightweight_x2.pth")
    torch.save({"params": _torch_swinir_state_dict(src_params)}, path)

    s = Stoke(
        model=SwinIR(**CFG),
        optimizer=StokeOptimizer(optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}),
        loss=losses.mse_loss,
        sample_input=x,
        rng_seed=7,  # different init: loaded weights must fully overwrite
    )
    s.load_model_state(path, strict=True)  # key_map auto-applied for SwinIR

    for a, b in zip(
        jax.tree.leaves(src_params), jax.tree.leaves(s.state.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s.model_access.eval()
    out = np.asarray(s.model(x))
    # facade forward runs dp-sharded over 8 virtual devices: float
    # reassociation vs the single-device reference apply
    np.testing.assert_allclose(out, np.asarray(ref_out), atol=2e-5)


def test_torch_swinir_missing_key_raises(tmp_path):
    model = SwinIR(**CFG)
    x = np.zeros((1, 8, 8, 3), np.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    sd = _torch_swinir_state_dict(params)
    sd.pop("conv_first.weight")
    path = str(tmp_path / "incomplete.pth")
    torch.save({"params": sd}, path)
    s = Stoke(
        model=SwinIR(**CFG),
        optimizer=StokeOptimizer(optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}),
        loss=losses.mse_loss,
        sample_input=x,
    )
    with pytest.raises((KeyError, ValueError)):
        s.load_model_state(path, strict=True)


def test_key_map_covers_every_param():
    """Every param leaf has a torch twin that maps back through
    TORCH_KEY_MAP — no silent unmapped keys in either direction."""
    from pytorch_distributedtraining_tpu import interop
    from pytorch_distributedtraining_tpu.interop import rewrite_keys

    model = SwinIR(**CFG)
    x = np.zeros((1, 8, 8, 3), np.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    flat = tree_to_flat_dict(jax.device_get(params))
    # params-only export (no buffers) gives the name map under test
    torch_keys = dict.fromkeys(interop.torch_swinir_state_dict(params))
    back = rewrite_keys(
        {k.replace(".", "/"): None for k in torch_keys}, TORCH_KEY_MAP
    )
    # after rewrite, the module path must match ours (leaf twins differ:
    # weight vs kernel/scale — interop's heuristic handles those)
    ours = {k.rpartition("/")[0] for k in flat}
    theirs = {k.rpartition("/")[0] for k in back}
    assert ours == theirs


def test_export_round_trip_through_torch_format(tmp_path):
    """Train-here -> save_torch_swinir -> strict reference-style load
    reproduces the exported model exactly (bidirectional interop)."""
    from pytorch_distributedtraining_tpu import interop

    model = SwinIR(**CFG)
    x = np.random.default_rng(5).random((8, 8, 8, 3)).astype(np.float32)
    params = model.init(jax.random.PRNGKey(3), x[:1])["params"]
    ref_out = model.apply({"params": params}, x)

    path = str(tmp_path / "exported_swinir_x2.pth")
    interop.save_torch_swinir(path, params, model=model)

    # torch-side strict-load expectations: registered buffers present,
    # bias table in the official (untransposed) layout
    sd = torch.load(path, weights_only=True)["params"]
    n = CFG["window_size"] ** 2
    assert sd[
        "layers.0.residual_group.blocks.0.attn.relative_position_index"
    ].shape == (n, n)
    assert sd["layers.0.residual_group.blocks.1.attn_mask"].shape[1:] == (n, n)
    table = sd[
        "layers.0.residual_group.blocks.0.attn.relative_position_bias_table"
    ]
    assert table.shape == ((2 * CFG["window_size"] - 1) ** 2, CFG["num_heads"][0])
    # official MLP naming (regression: the fc rules must fire before the
    # block rewrite consumes the "/" separators)
    assert "layers.1.residual_group.blocks.1.mlp.fc2.weight" in sd

    # load it back the way the reference user would (facade, strict)
    s = Stoke(
        model=SwinIR(**CFG),
        optimizer=StokeOptimizer(optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}),
        loss=losses.mse_loss,
        sample_input=x,
        rng_seed=11,
    )
    s.load_model_state(path, strict=True)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(s.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out = np.asarray(s.model(x))
    np.testing.assert_allclose(out, np.asarray(ref_out), atol=2e-5)


def test_classical_pixelshuffle_upsampler_loads(tmp_path):
    """SwinIR-M family (upsampler='pixelshuffle', x4): official naming
    (conv_before_upsample.0 / upsample.{0,2} convs / conv_last) strict-
    loads through TORCH_KEY_MAP_CLASSICAL and upscales 4x."""
    import re

    from pytorch_distributedtraining_tpu.models.swinir import (
        TORCH_KEY_MAP_CLASSICAL,
    )

    kw = dict(depths=[2], embed_dim=12, num_heads=[2], window_size=4,
              upscale=4, upsampler="pixelshuffle")
    model = SwinIR(**kw)
    x = jnp.zeros((1, 16, 16, 3))
    template = model.init(jax.random.key(0), x)["params"]

    def to_torch(k):
        k = re.sub(r"^rstb_(\d+)/layer_(\d+)/",
                   r"layers.\1.residual_group.blocks.\2.", k)
        k = re.sub(r"^rstb_(\d+)/conv/", r"layers.\1.conv.", k)
        k = k.replace("/fc1/", "/mlp.fc1/").replace("/fc2/", "/mlp.fc2/")
        k = re.sub(r"^patch_norm/", "patch_embed.norm.", k)
        k = re.sub(r"^conv_before_up/", "conv_before_upsample.0.", k)
        k = re.sub(r"^up_conv_0/", "upsample.0.", k)
        k = re.sub(r"^up_conv_1/", "upsample.2.", k)
        k = k.replace("/", ".")
        k = re.sub(r"\.(kernel|scale)$", ".weight", k)
        return k

    import torch

    from pytorch_distributedtraining_tpu.checkpoint import tree_to_flat_dict

    sd = {}
    for k, v in tree_to_flat_dict(template).items():
        a = np.array(np.asarray(v, np.float32) + 0.25, copy=True)
        if k.endswith("/kernel"):
            a = np.ascontiguousarray(
                np.transpose(a, (3, 2, 0, 1)) if a.ndim == 4 else a.T
            )
        sd[to_torch(k)] = torch.from_numpy(a)

    from pytorch_distributedtraining_tpu import interop

    loaded = interop.load_torch_into_template(
        interop._to_numpy_tree(sd), template,
        key_map=TORCH_KEY_MAP_CLASSICAL, strict=True,
    )
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(template)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b, np.float32) + 0.25, atol=1e-6
        )
    out = model.apply({"params": loaded}, jnp.ones((1, 16, 16, 3)) * 0.5)
    assert out.shape == (1, 64, 64, 3)  # x4


def test_classical_export_round_trip_and_facade_load(tmp_path):
    """Bidirectional for the classical family too: save_torch_swinir emits
    official names (conv_before_upsample.0/upsample.0/upsample.2), and the
    facade auto-selects TORCH_KEY_MAP_CLASSICAL for pixelshuffle models."""
    from pytorch_distributedtraining_tpu import interop

    kw = dict(depths=[2], embed_dim=12, num_heads=[2], window_size=4,
              upscale=4, upsampler="pixelshuffle")
    model = SwinIR(**kw)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.key(1), x)["params"]

    path = str(tmp_path / "classical_x4.pth")
    interop.save_torch_swinir(path, params)
    sd = torch.load(path, weights_only=True)["params"]
    assert "conv_before_upsample.0.weight" in sd
    assert "upsample.0.weight" in sd and "upsample.2.weight" in sd
    assert not any(k.startswith(("conv_before_up.", "up_conv")) for k in sd)

    s = Stoke(
        model=SwinIR(**kw),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}
        ),
        loss=losses.mse_loss,
        batch_size_per_device=2,
    )
    s.init(np.zeros((2, 16, 16, 3), np.float32))
    s.load_model_state(path, strict=True)
    for a, b in zip(
        jax.tree.leaves(s.state.params), jax.tree.leaves(params)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )


def test_realsr_nearest_conv_round_trip(tmp_path):
    """real-SR family (upsampler='nearest+conv', x4): export emits the
    official names (conv_before_upsample.0/conv_up1/conv_up2/conv_hr/
    conv_last), and the facade strict-loads the file back."""
    from pytorch_distributedtraining_tpu import interop

    kw = dict(depths=[2], embed_dim=12, num_heads=[2], window_size=4,
              upscale=4, upsampler="nearest+conv")
    model = SwinIR(**kw)
    x = jnp.zeros((1, 16, 16, 3))
    params = model.init(jax.random.key(2), x)["params"]
    out = model.apply({"params": params}, jnp.ones((1, 16, 16, 3)) * 0.3)
    assert out.shape == (1, 64, 64, 3)

    path = str(tmp_path / "realsr_x4.pth")
    interop.save_torch_swinir(path, params)
    sd = torch.load(path, weights_only=True)["params"]
    for k in ("conv_before_upsample.0.weight", "conv_up1.weight",
              "conv_up2.weight", "conv_hr.weight", "conv_last.weight"):
        assert k in sd, sorted(sd)[:8]

    s = Stoke(
        model=SwinIR(**kw),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}
        ),
        loss=losses.mse_loss,
        batch_size_per_device=1,
    )
    s.init(np.zeros((1, 16, 16, 3), np.float32))
    s.load_model_state(path, strict=True)
    for a, b in zip(
        jax.tree.leaves(s.state.params), jax.tree.leaves(params)
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )
