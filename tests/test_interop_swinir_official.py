"""Official SwinIR-S checkpoint fixture at FULL size.

The reference's actual artifact is
``002_lightweightSR_DIV2K_s64w8_SwinIR-S_x2.pth`` loaded at
`/root/reference/Stoke-DDP.py:209-213` into the full config
(`:206-208`): upscale=2, img_size=64, window_size=8, depths=[6,6,6,6],
embed_dim=60, num_heads=[6,6,6,6], mlp_ratio=2,
upsampler='pixelshuffledirect', resi_connection='1conv'.

The earlier interop tests prove the key map only at toy size
(img_size=8, depths=(2,2)); a naming/shape gap that appears first at
depth-6 / 4-RSTB scale — or in a buffer only shifted blocks carry —
would slip through. This file pins the complete official key/shape
inventory with an INDEPENDENT generator (hand-derived from the official
torch implementation's module tree, not from our export code), builds
the fixture through the interop exporter, and strict-loads it through
the facade with zero unmatched keys in both directions.

No network: the fixture reproduces the official file's exact key/shape
surface with synthetic values, which is what key-map parity needs.
"""

import jax
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import losses
from pytorch_distributedtraining_tpu.checkpoint import tree_to_flat_dict
from pytorch_distributedtraining_tpu.models.swinir import SwinIR
from pytorch_distributedtraining_tpu.stoke import Stoke, StokeOptimizer

torch = pytest.importorskip("torch")

# the reference's construction, Stoke-DDP.py:206-208 (all are SwinIR's
# defaults — spelled out so this file stands alone as the contract)
FULL = dict(
    upscale=2, in_chans=3, img_size=64, window_size=8, img_range=1.0,
    depths=(6, 6, 6, 6), embed_dim=60, num_heads=(6, 6, 6, 6),
    mlp_ratio=2.0, upsampler="pixelshuffledirect", resi_connection="1conv",
)


def official_inventory() -> dict:
    """key -> shape of the official 002_lightweightSR SwinIR-S x2 file.

    Hand-derived from the official torch ``network_swinir.py`` module
    tree (KAIR/SwinIR): per-block attention + MLP, per-RSTB trailing
    conv, patch-embed norm, final norm, pixelshuffledirect upsample.
    Registered buffers included: ``relative_position_index`` on every
    block, ``attn_mask`` only on shifted (odd-index) blocks, at the
    training img_size.
    """
    e = FULL["embed_dim"]          # 60
    ws = FULL["window_size"]       # 8
    heads = FULL["num_heads"][0]   # 6
    hidden = int(e * FULL["mlp_ratio"])  # 120
    n_win = (FULL["img_size"] // ws) ** 2  # 64 windows at 64x64
    wsq = ws * ws                  # 64
    inv = {
        "conv_first.weight": (e, 3, 3, 3),
        "conv_first.bias": (e,),
        "patch_embed.norm.weight": (e,),
        "patch_embed.norm.bias": (e,),
        "norm.weight": (e,),
        "norm.bias": (e,),
        # 1conv residual connection after the RSTB body (resi_connection)
        "conv_after_body.weight": (e, e, 3, 3),
        "conv_after_body.bias": (e,),
        # pixelshuffledirect: one conv to 3*upscale^2 then PixelShuffle
        "upsample.0.weight": (3 * FULL["upscale"] ** 2, e, 3, 3),
        "upsample.0.bias": (3 * FULL["upscale"] ** 2,),
    }
    for i, depth in enumerate(FULL["depths"]):
        for j in range(depth):
            b = f"layers.{i}.residual_group.blocks.{j}"
            inv.update({
                f"{b}.norm1.weight": (e,),
                f"{b}.norm1.bias": (e,),
                f"{b}.attn.relative_position_bias_table": (
                    (2 * ws - 1) ** 2, heads,
                ),
                f"{b}.attn.relative_position_index": (wsq, wsq),
                f"{b}.attn.qkv.weight": (3 * e, e),
                f"{b}.attn.qkv.bias": (3 * e,),
                f"{b}.attn.proj.weight": (e, e),
                f"{b}.attn.proj.bias": (e,),
                f"{b}.norm2.weight": (e,),
                f"{b}.norm2.bias": (e,),
                f"{b}.mlp.fc1.weight": (hidden, e),
                f"{b}.mlp.fc1.bias": (hidden,),
                f"{b}.mlp.fc2.weight": (e, hidden),
                f"{b}.mlp.fc2.bias": (e,),
            })
            if j % 2 == 1:  # shifted window -> trained-size mask buffer
                inv[f"{b}.attn_mask"] = (n_win, wsq, wsq)
        inv[f"layers.{i}.conv.weight"] = (e, e, 3, 3)
        inv[f"layers.{i}.conv.bias"] = (e,)
    return inv


def _full_size_params():
    """Full-config param tree with synthetic deterministic values,
    without paying a real init: eval_shape gives the structure, then each
    leaf is filled from a seeded stream."""
    model = SwinIR(**FULL)
    shapes = jax.eval_shape(
        lambda r: model.init(r, np.zeros((1, 64, 64, 3), np.float32)),
        jax.random.PRNGKey(0),
    )["params"]
    rng = np.random.default_rng(42)
    flat = {
        k: rng.standard_normal(np.shape(v), dtype=np.float32) * 0.02
        for k, v in sorted(tree_to_flat_dict(shapes).items())
    }
    from pytorch_distributedtraining_tpu.checkpoint import flat_dict_to_tree

    return model, flat_dict_to_tree(flat)


def test_full_size_export_matches_official_inventory():
    """flax -> torch direction: the exporter emits EXACTLY the official
    key set, every shape right, no extra and no missing keys."""
    from pytorch_distributedtraining_tpu import interop

    model, params = _full_size_params()
    sd = interop.torch_swinir_state_dict(params, model=model)
    expected = official_inventory()

    missing = sorted(set(expected) - set(sd))
    unexpected = sorted(set(sd) - set(expected))
    assert not missing, f"export lacks official keys: {missing[:10]}"
    assert not unexpected, f"export invents keys: {unexpected[:10]}"
    for k, shape in expected.items():
        assert tuple(sd[k].shape) == shape, (k, tuple(sd[k].shape), shape)

    # the param count of the real artifact family (SwinIR-S light x2,
    # ~0.9M): catches a structurally wrong (e.g. depth-truncated) model
    n_params = sum(
        int(np.prod(v.shape)) for k, v in sd.items()
        if "relative_position_index" not in k and not k.endswith("attn_mask")
    )
    assert 850_000 < n_params < 950_000, n_params
    # every template leaf was exported (buffers are the only extras)
    n_buffers = sum(
        1 for k in sd
        if "relative_position_index" in k or k.endswith("attn_mask")
    )
    assert len(sd) - n_buffers == len(tree_to_flat_dict(params))


def test_full_size_official_strict_load_through_facade(tmp_path):
    """torch -> flax direction at the reference's real config: the facade
    strict-loads the official-inventory fixture with zero unmatched keys
    and reproduces the source values bit-for-bit."""
    from pytorch_distributedtraining_tpu import interop

    model, src_params = _full_size_params()
    path = str(tmp_path / "002_lightweightSR_DIV2K_s64w8_SwinIR-S_x2.pth")
    interop.save_torch_swinir(path, src_params, model=model)

    # file surface == official surface (belt and braces before the load)
    sd = torch.load(path, weights_only=True)["params"]
    assert set(sd) == set(official_inventory())

    s = Stoke(
        model=SwinIR(**FULL),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3}
        ),
        loss=losses.mse_loss,
        sample_input=np.zeros((8, 64, 64, 3), np.float32),
        rng_seed=7,  # different init: the load must overwrite every leaf
    )
    s.load_model_state(path, strict=True)

    flat_src = tree_to_flat_dict(jax.device_get(src_params))
    flat_got = tree_to_flat_dict(jax.device_get(s.state.params))
    assert set(flat_src) == set(flat_got)
    for k in flat_src:
        np.testing.assert_array_equal(flat_src[k], flat_got[k], err_msg=k)
