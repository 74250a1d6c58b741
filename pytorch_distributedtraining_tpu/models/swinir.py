"""SwinIR: shifted-window attention super-resolution transformer, TPU-native.

Functional equivalent of the reference's missing ``models/network_swinir.
SwinIR`` exactly as configured at `/root/reference/Stoke-DDP.py:206-208`::

    SwinIR(upscale=2, in_chans=3, img_size=64, window_size=8, img_range=1.,
           depths=[6,6,6,6], embed_dim=60, num_heads=[6,6,6,6], mlp_ratio=2,
           upsampler='pixelshuffledirect', resi_connection='1conv')

(SwinIR-S, ~0.9M params). Architecture (Liang et al. 2021): shallow conv →
4 residual Swin transformer blocks (6 layers each, alternating W-MSA /
shifted SW-MSA with relative position bias) → conv + global residual →
pixel-shuffle upsampler.

TPU-first layout decisions:
- NHWC end-to-end; window partition is reshape/transpose (free for XLA);
- attention between the projections is one fused kernel on a TPU
  (``ops/pallas_window_attn.py``: ``qkv`` in the projection's own layout,
  heads split by lane masks, scores in VMEM only) and batched per-head
  einsums everywhere else;
- the shifted-window mask is precomputed host-side per static (H, W) and
  closed over as a constant (no dynamic shapes under jit);
- all matmuls run in the module ``dtype`` (bf16 under the bf16 policy),
  residual adds and norms in f32.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .sr_espcn import pixel_shuffle
from .scan_utils import remat_block, stack_trees, unstack_tree
from ..parallel.spec import published_batch_mesh
from ..runtime.mesh import data_axes


def window_partition(x: jnp.ndarray, ws: int) -> jnp.ndarray:
    """[B, H, W, C] -> [B*nW, ws*ws, C]."""
    b, h, w, c = x.shape
    with jax.named_scope("window_layout"):  # data movement, by name
        x = x.reshape(b, h // ws, ws, w // ws, ws, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(-1, ws * ws, c)


def window_reverse(wins: jnp.ndarray, ws: int, h: int, w: int) -> jnp.ndarray:
    """[B*nW, ws*ws, C] -> [B, H, W, C]."""
    c = wins.shape[-1]
    b = wins.shape[0] // ((h // ws) * (w // ws))
    with jax.named_scope("window_layout"):
        x = wins.reshape(b, h // ws, w // ws, ws, ws, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(b, h, w, c)


def _roll(x: jnp.ndarray, shift: int) -> jnp.ndarray:
    """The cyclic shift of a shifted-window layer, under the same scope as
    the window partition: all of it is layout, none of it arithmetic."""
    with jax.named_scope("window_layout"):
        return jnp.roll(x, (shift, shift), axis=(1, 2))


def _relative_position_index(ws: int) -> np.ndarray:
    """[ws*ws, ws*ws] lookup into the (2ws-1)^2 bias table (host-side)."""
    coords = np.stack(np.meshgrid(np.arange(ws), np.arange(ws), indexing="ij"))
    flat = coords.reshape(2, -1)
    rel = flat[:, :, None] - flat[:, None, :]  # [2, n, n]
    rel = rel.transpose(1, 2, 0) + (ws - 1)
    return (rel[..., 0] * (2 * ws - 1) + rel[..., 1]).astype(np.int32)


def _shift_attn_mask(h: int, w: int, ws: int, shift: int) -> np.ndarray:
    """[nW, ws*ws, ws*ws] additive mask for SW-MSA (host-side, static)."""
    img = np.zeros((1, h, w, 1), np.float32)
    cnt = 0
    for hs in (slice(0, -ws), slice(-ws, -shift), slice(-shift, None)):
        for wsl in (slice(0, -ws), slice(-ws, -shift), slice(-shift, None)):
            img[:, hs, wsl, :] = cnt
            cnt += 1
    wins = np.asarray(
        img.reshape(1, h // ws, ws, w // ws, ws, 1)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(-1, ws * ws)
    )
    diff = wins[:, None, :] - wins[:, :, None]
    return np.where(diff != 0, -100.0, 0.0).astype(np.float32)


# (regex, repl) rewrites from the official torch-SwinIR state_dict naming
# (the checkpoint family the reference loads, `Stoke-DDP.py:209-213`:
# `layers.N.residual_group.blocks.M.*`) onto this module tree. Keys are the
# "/"-joined flat form produced by interop.load_torch_checkpoint; `None`
# replacement drops torch-only buffers. Leaf twins (weight->kernel/scale,
# OIHW->HWIO) are handled downstream by interop's heuristics.
TORCH_KEY_MAP = [
    (r"(^|/)relative_position_index$", None),  # recomputed host-side
    (r"(^|/)attn_mask$", None),  # recomputed per static (H, W)
    (r"^layers/(\d+)/residual_group/blocks/(\d+)/", r"rstb_\1/layer_\2/"),
    (r"^layers/(\d+)/conv/", r"rstb_\1/conv/"),
    (r"/mlp/fc", "/fc"),
    (r"^patch_embed/norm/", "patch_norm/"),
    (r"^upsample/0/", "conv_up/"),  # UpsampleOneStep = Sequential(Conv, PS)
]

# Classical SwinIR-M checkpoints (upsampler='pixelshuffle') use a different
# tail: Sequential(conv, LeakyReLU) before upsampling, then the official
# Upsample module interleaving convs (even indices) with parameter-free
# PixelShuffles — so ``upsample/0`` means a different module than in the
# -S map above and the two families need separate tables.
TORCH_KEY_MAP_CLASSICAL = [
    rule for rule in TORCH_KEY_MAP if not rule[0].startswith("^upsample")
] + [
    (r"^conv_before_upsample/0/", "conv_before_up/"),
    (r"^upsample/0/", "up_conv_0/"),
    (r"^upsample/2/", "up_conv_1/"),
    (r"^upsample/4/", "up_conv_2/"),  # up to x8
]

# Inverse direction (export): framework flat keys -> official torch names.
# Kept next to TORCH_KEY_MAP so the two directions evolve together; the
# leaf twins (kernel->weight + layout) are handled by interop's exporter.
SWINIR_EXPORT_KEY_MAP = [
    # leaf-module renames FIRST: later rules rewrite the "/" separators
    # these patterns anchor on
    (r"/fc1/", "/mlp.fc1/"),
    (r"/fc2/", "/mlp.fc2/"),
    (r"^rstb_(\d+)/layer_(\d+)/", r"layers.\1.residual_group.blocks.\2."),
    (r"^rstb_(\d+)/conv/", r"layers.\1.conv."),
    (r"^patch_norm/", "patch_embed.norm."),
    (r"^conv_up/", "upsample.0."),
    # classical 'pixelshuffle' tail (source names are disjoint from the
    # -S tail's, so one export table serves both families)
    (r"^conv_before_up/", "conv_before_upsample.0."),
    (r"^up_conv_0/", "upsample.0."),
    (r"^up_conv_1/", "upsample.2."),
    (r"^up_conv_2/", "upsample.4."),
]


def _einsum_core(qkv, bias, mask, dtype):
    """Window attention as per-head einsums over ``[bn, heads, n, d]``
    arrays: ``qkv [bn, n, 3c]`` -> ``[bn, n, c]``. The reference every
    other implementation is held to, and what runs wherever the fused
    kernel does not."""
    bn, n, c3 = qkv.shape
    h, c = bias.shape[0], c3 // 3
    head_dim = c // h
    qkv = qkv.reshape(bn, n, 3, h, head_dim).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]  # [bn, h, n, d]
    attn = (q * head_dim**-0.5) @ k.transpose(0, 1, 3, 2)  # [bn, h, n, n]
    attn = attn + bias[None].astype(attn.dtype)

    if mask is not None:  # [nW, n, n] additive
        nw = mask.shape[0]
        attn = attn.reshape(bn // nw, nw, h, n, n) + mask[None, :, None].astype(
            attn.dtype
        )
        attn = attn.reshape(bn, h, n, n)

    attn = jax.nn.softmax(attn.astype(jnp.float32), axis=-1).astype(dtype)
    return (attn @ v).transpose(0, 2, 1, 3).reshape(bn, n, c)


@partial(jax.jit, static_argnames=("dtype", "mesh"))
def _kernel_or_einsum_core(qkv, bias, mask, dtype, mesh=None):
    """The fused kernel where the program is lowered for a TPU, the einsums
    on any other platform: one traced program serves both, and only the
    branch of the platform it is lowered for is compiled. Jitted so that a
    model's layers, which call it with the same shapes, share one trace of
    both branches (24 traces of each cost the cells more set-up than their
    bound allows).

    The partitioner cannot split a Mosaic kernel and refuses a program that
    spans devices with one in it. Given the step's ``mesh``, each device
    runs the core over its own windows (``shard_map`` over the data axes,
    bias and mask whole on every device, ``d bias`` summed across them)."""
    from ..ops.pallas_window_attn import window_attention_qkv

    def core(qkv, bias, mask):
        return jax.lax.platform_dependent(
            qkv, bias, mask, tpu=window_attention_qkv,
            default=partial(_einsum_core, dtype=dtype),
        )

    if mesh is None:
        return core(qkv, bias, mask)
    windows = P(data_axes(mesh))
    return jax.shard_map(
        core, mesh=mesh, in_specs=(windows, P(), P()), out_specs=windows,
        check_vma=False,  # a pallas_call says nothing of varying axes
    )(qkv, bias, mask)


class WindowAttention(nn.Module):
    dim: int
    num_heads: int
    window_size: int
    dtype: jnp.dtype = jnp.float32
    # How the attention between the projections is computed — same
    # parameters, same math for every choice (checkpoints are
    # interchangeable):
    #   'auto'      the fused kernel (ops/pallas_window_attn.py: qkv in the
    #               projection's own layout, scores in VMEM only) where the
    #               program is lowered for a TPU and the shapes meet the
    #               kernel's contract; the einsums everywhere else. On a
    #               mesh the kernel runs under shard_map over the mesh the
    #               step publishes (spec.batch_layout), each device over
    #               its own windows; with several devices visible and none
    #               published, the einsums
    #   'xla'       per-head einsums, the named reference
    #   'pallas'    the kernel or an error: compiles for the TPU or raises;
    #               'pallas_interpret' runs the same kernel interpreted
    #               (CPU tests)
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, mask=None):
        if self.attn_impl not in ("auto", "xla", "pallas", "pallas_interpret"):
            raise ValueError(
                "attn_impl must be one of 'auto'/'xla'/'pallas'/"
                f"'pallas_interpret', got {self.attn_impl!r}"
            )
        c = x.shape[-1]  # x: [B*nW, ws^2, C]
        qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype, name="qkv")(x)
        table = self.param(
            "relative_position_bias_table",
            nn.initializers.truncated_normal(0.02),
            ((2 * self.window_size - 1) ** 2, self.num_heads),
        )
        # score, bias, mask, softmax and value product under one scope,
        # whatever the implementation; the qkv / proj projections keep
        # their Flax names (metadata only: no instruction changes)
        with jax.named_scope("attention"):
            out = self._core(qkv, table, mask)
        return nn.Dense(c, dtype=self.dtype, name="proj")(out)

    def _core(self, qkv, table, mask):
        """Attention between the projections: [bn, n, 3c] -> [bn, n, c]."""
        n, h = qkv.shape[1], self.num_heads
        idx = _relative_position_index(self.window_size)
        bias = table[idx.reshape(-1)].reshape(n, n, h).transpose(2, 0, 1)
        if mask is not None:
            mask = jnp.asarray(mask)

        if self.attn_impl == "xla":
            out = _einsum_core(qkv, bias, mask, self.dtype)
        else:
            out = self._fused(qkv, bias, mask)
        # named-remat tag (parallel/remat.py "names"/"offload"): save the
        # softmax·V product, recompute the cheap projections
        return checkpoint_name(out, "attn_out")

    def _fused(self, qkv, bias, mask):
        """The kernel where it applies. 'auto' decides from what it can
        see: the shapes now, the platform when the program is lowered
        (``_kernel_or_einsum_core``). 'pallas' / 'pallas_interpret' take
        the kernel or raise."""
        from ..observe import trace
        from ..ops import pallas_window_attn as pwa

        bn, n, c3 = qkv.shape
        why = pwa.kernel_contract(
            bn, n, c3 // 3, self.num_heads,
            None if mask is None else mask.shape[0], qkv.dtype,
        )
        bias = bias.astype(jnp.float32)
        if self.attn_impl != "auto":
            if why is not None:
                # a caller who names the kernel gets it or an error
                raise ValueError(f"attn_impl={self.attn_impl!r}: {why}")
            path, why = "kernel", f"attn_impl={self.attn_impl!r}"
            out = pwa.window_attention_qkv(
                qkv, bias, mask, self.attn_impl == "pallas_interpret"
            )
        else:
            mesh = None
            if why is None:
                mesh, why = self._kernel_mesh(qkv, mask)
            if why is not None:
                path = "einsum"
                out = _einsum_core(qkv, bias, mask, self.dtype)
            else:
                # decided when the program is lowered: the kernel for a
                # TPU, the einsums for any other platform
                path = "by_platform"
                why = "shapes meet the kernel's contract" + (
                    "" if mesh is None else
                    f"; each of the mesh's {mesh.size} devices its own windows"
                )
                out = _kernel_or_einsum_core(
                    qkv, bias, mask, self.dtype, mesh
                )
        trace.instant(
            "window_attention.path", path=path, reason=why,
            bn=bn, n=n, c=c3 // 3, heads=self.num_heads,
        )
        return out

    def _kernel_mesh(self, qkv, mask):
        """Where 'auto' may place the kernel: ``(mesh, None)``, with the
        mesh to split the windows over or None for a program on one
        device, or ``(None, why not)``. A program that spans devices takes
        the kernel only where the step has published its mesh
        (``spec.batch_layout``); one device visible cannot be spanned."""
        from ..ops.pallas_window_attn import kernel_contract

        mesh = published_batch_mesh()
        if mesh is None:
            if jax.device_count() == 1:
                return None, None
            return None, (
                f"{jax.device_count()} devices and no step has published "
                "the batch's layout: the program may span them, and the "
                "partitioner cannot split a kernel"
            )
        if mesh.size == 1:
            return None, None
        bn, n, c3 = qkv.shape
        shards = math.prod(mesh.shape[a] for a in data_axes(mesh))
        if bn % shards:
            return None, f"{bn} windows do not split over {shards} devices"
        why = kernel_contract(
            bn // shards, n, c3 // 3, self.num_heads,
            None if mask is None else mask.shape[0], qkv.dtype,
        )
        return (mesh, None) if why is None else (None, f"a device's share: {why}")


class SwinLayer(nn.Module):
    """One STL: (shifted-)window attention + MLP, pre-norm residuals."""

    dim: int
    num_heads: int
    window_size: int
    shift: int
    mlp_ratio: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):  # [B, H, W, C]
        b, hgt, wid, c = x.shape
        ws = self.window_size
        shortcut = x
        y = nn.LayerNorm(dtype=jnp.float32, name="norm1")(x)
        if self.shift > 0:
            y = _roll(y, -self.shift)
            mask = jnp.asarray(_shift_attn_mask(hgt, wid, ws, self.shift))
        else:
            mask = None
        wins = window_partition(y.astype(self.dtype), ws)
        wins = WindowAttention(
            self.dim, self.num_heads, ws, dtype=self.dtype,
            attn_impl=self.attn_impl, name="attn",
        )(wins, mask)
        y = window_reverse(wins, ws, hgt, wid)
        if self.shift > 0:
            y = _roll(y, self.shift)
        x = shortcut + y.astype(shortcut.dtype)

        y = nn.LayerNorm(dtype=jnp.float32, name="norm2")(x).astype(self.dtype)
        hdim = int(self.dim * self.mlp_ratio)
        y = nn.Dense(hdim, dtype=self.dtype, name="fc1")(y)
        y = nn.gelu(y)
        y = nn.Dense(self.dim, dtype=self.dtype, name="fc2")(y)
        return x + y.astype(x.dtype)


class SwinLayerPair(nn.Module):
    """W-MSA + SW-MSA pair — the ``nn.scan`` body for RSTB's layer stack.

    Swin alternates shift=0 / shift=ws//2, so the smallest repeating unit
    is a PAIR of layers, not one layer (the two have different static
    masks). Scan-layout params live under ``layers/a`` (unshifted) and
    ``layers/b`` (shifted), each with a leading ``depth//2`` axis —
    ``stack_swinir_layer_params`` converts loop-layout checkpoints.
    """

    dim: int
    num_heads: int
    window_size: int
    mlp_ratio: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        kw = dict(
            mlp_ratio=self.mlp_ratio, dtype=self.dtype,
            attn_impl=self.attn_impl,
        )
        x = SwinLayer(
            self.dim, self.num_heads, self.window_size, shift=0,
            name="a", **kw,
        )(x)
        x = SwinLayer(
            self.dim, self.num_heads, self.window_size,
            shift=self.window_size // 2, name="b", **kw,
        )(x)
        return x, None  # (carry, scan output)


class RSTB(nn.Module):
    """Residual Swin Transformer Block: depth STLs + conv + residual."""

    dim: int
    depth: int
    num_heads: int
    window_size: int
    mlp_ratio: float
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    # Activation remat per layer/pair: bool (True == "full") or a named
    # policy from parallel/remat.py
    remat: bool | str = False
    # nn.scan over W-MSA/SW-MSA pairs: one compiled pair instead of depth
    # layers. Needs even depth >= 2 (falls back to the loop otherwise).
    scan_layers: bool = False

    @nn.compact
    def __call__(self, x):
        shortcut = x
        kw = dict(
            mlp_ratio=self.mlp_ratio, dtype=self.dtype,
            attn_impl=self.attn_impl,
        )
        if self.scan_layers and self.depth >= 2 and self.depth % 2 == 0:
            # one traced/compiled pair for all depth//2 iterations; remat
            # nests inside the scan (standard form: scan saves only the
            # inter-pair carry, remat recomputes pair internals backward).
            # SwinLayer.__call__ is (self, x) — no static args.
            pair_cls = remat_block(
                SwinLayerPair, self.remat, static_argnums=(), in_scan=True
            )
            pairs = nn.scan(
                pair_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=self.depth // 2,
            )
            x, _ = pairs(
                self.dim, self.num_heads, self.window_size,
                name="layers", **kw,
            )(x)
        else:
            layer_cls = remat_block(SwinLayer, self.remat, static_argnums=())
            for i in range(self.depth):
                x = layer_cls(
                    self.dim, self.num_heads, self.window_size,
                    shift=0 if i % 2 == 0 else self.window_size // 2,
                    name=f"layer_{i}", **kw,
                )(x)
        # resi_connection='1conv' (Stoke-DDP.py:208)
        x = nn.Conv(self.dim, (3, 3), padding="SAME", dtype=self.dtype, name="conv")(x)
        return shortcut + x.astype(shortcut.dtype)


class SwinIR(nn.Module):
    """SwinIR-S with the reference's constructor surface."""

    upscale: int = 2
    in_chans: int = 3
    img_size: int = 64  # training patch size hint; forward is size-agnostic
    window_size: int = 8
    img_range: float = 1.0
    depths: Sequence[int] = (6, 6, 6, 6)
    embed_dim: int = 60
    num_heads: Sequence[int] = (6, 6, 6, 6)
    mlp_ratio: float = 2.0
    upsampler: str = "pixelshuffledirect"
    resi_connection: str = "1conv"
    dtype: jnp.dtype = jnp.float32
    # 'auto' | 'xla' | 'pallas' | 'pallas_interpret' — see
    # WindowAttention.attn_impl for what each computes
    attn_impl: str = "auto"
    # Activation remat per Swin layer/pair: bool (True == "full") or a
    # named policy from parallel/remat.py ("dots"/"names"/"offload")
    remat: bool | str = False
    # nn.scan over each RSTB's W-MSA/SW-MSA pairs: XLA compiles ONE pair
    # per RSTB instead of depth layers — the cold-compile lever. Param
    # layout changes from `layer_{i}` to stacked `layers/{a,b}`;
    # `stack_swinir_layer_params` converts loop-layout checkpoints (incl.
    # torch imports). GRAFT_SCAN_LAYERS toggles this through the facade.
    scan_layers: bool = False

    @nn.compact
    def __call__(self, x):  # [B, H, W, C] in [0, img_range]
        if self.upsampler not in (
            "pixelshuffledirect", "pixelshuffle", "nearest+conv"
        ):
            raise NotImplementedError(
                "upsampler must be 'pixelshuffledirect' (SwinIR-S), "
                "'pixelshuffle' (classical SwinIR-M) or 'nearest+conv' "
                "(real-SR)"
            )
        mean = jnp.asarray([0.4488, 0.4371, 0.4040], x.dtype) * self.img_range
        b, h, w, c = x.shape
        ws = self.window_size
        pad_h = (-h) % ws
        pad_w = (-w) % ws
        x = (x - mean) / self.img_range
        if pad_h or pad_w:  # reflect-pad to window multiples (static)
            x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)), mode="reflect")

        feat = nn.Conv(
            self.embed_dim, (3, 3), padding="SAME", dtype=self.dtype,
            name="conv_first",
        )(x.astype(self.dtype))

        # torch SwinIR's patch_embed norm (patch_norm=True default): a
        # channel LayerNorm between shallow conv and the RSTB body — kept so
        # reference checkpoints map onto an identical function
        y = nn.LayerNorm(dtype=jnp.float32, name="patch_norm")(feat).astype(
            self.dtype
        )
        for i, (depth, heads) in enumerate(zip(self.depths, self.num_heads)):
            y = RSTB(
                self.embed_dim, depth, heads, ws, self.mlp_ratio,
                dtype=self.dtype, attn_impl=self.attn_impl,
                remat=self.remat, scan_layers=self.scan_layers,
                name=f"rstb_{i}",
            )(y)
        y = nn.LayerNorm(dtype=jnp.float32, name="norm")(y).astype(self.dtype)
        y = nn.Conv(
            self.embed_dim, (3, 3), padding="SAME", dtype=self.dtype,
            name="conv_after_body",
        )(y)
        feat = feat + y

        r = self.upscale
        if self.upsampler == "nearest+conv":
            # real-SR tail: nearest 2x resizes interleaved with convs
            # (official naming: conv_before_upsample.0 / conv_up1 /
            # conv_up2 / conv_hr / conv_last), scales 2 and 4
            if r not in (2, 4):
                raise NotImplementedError(
                    f"nearest+conv supports scales 2 and 4, got {r}"
                )
            nf = 64
            # official slopes: conv_before_upsample's activation is a
            # default nn.LeakyReLU (0.01); the shared self.lrelu after
            # conv_up1/conv_up2/conv_hr is 0.2
            lrelu = partial(nn.leaky_relu, negative_slope=0.2)
            nearest2 = lambda a: a.repeat(2, axis=1).repeat(2, axis=2)  # noqa: E731
            y = nn.leaky_relu(nn.Conv(
                nf, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_before_up",
            )(feat), negative_slope=0.01)
            y = lrelu(nn.Conv(
                nf, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_up1",
            )(nearest2(y)))
            if r == 4:
                y = lrelu(nn.Conv(
                    nf, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv_up2",
                )(nearest2(y)))
            y = lrelu(nn.Conv(
                nf, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_hr",
            )(y))
            out = nn.Conv(
                self.in_chans, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_last",
            )(y)
        elif self.upsampler == "pixelshuffledirect":
            # one conv to C*r^2 then depth-to-space (SwinIR-S)
            out = nn.Conv(
                self.in_chans * r * r, (3, 3), padding="SAME",
                dtype=self.dtype, name="conv_up",
            )(feat)
            with jax.named_scope("upsample"):
                out = pixel_shuffle(out, r)
        else:
            # classical SwinIR-M: widen to num_feat=64, staged x2 shuffles
            # (or one x3), then a final conv — the official module tree
            # (conv_before_upsample.0 / upsample.2k / conv_last)
            nf = 64
            y = nn.Conv(
                nf, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_before_up",
            )(feat)
            y = nn.leaky_relu(y, negative_slope=0.01)
            if r & (r - 1) == 0:  # power of two: log2(r) stages of x2
                for s in range(r.bit_length() - 1):
                    y = nn.Conv(
                        4 * nf, (3, 3), padding="SAME", dtype=self.dtype,
                        name=f"up_conv_{s}",
                    )(y)
                    y = pixel_shuffle(y, 2)
            elif r == 3:
                y = nn.Conv(
                    9 * nf, (3, 3), padding="SAME", dtype=self.dtype,
                    name="up_conv_0",
                )(y)
                y = pixel_shuffle(y, 3)
            else:
                raise NotImplementedError(
                    f"pixelshuffle upsampler supports scales 2^n and 3, "
                    f"got {r}"
                )
            out = nn.Conv(
                self.in_chans, (3, 3), padding="SAME", dtype=self.dtype,
                name="conv_last",
            )(y)
        out = out.astype(jnp.float32) * self.img_range + mean
        if pad_h or pad_w:
            out = out[:, : h * r, : w * r, :]
        return out


def stack_swinir_layer_params(params: dict, depths: Sequence[int]) -> dict:
    """Loop layout -> scan layout for every ``rstb_{i}`` subtree:
    ``layer_{2j}`` stacks under ``layers/a`` (unshifted) and
    ``layer_{2j+1}`` under ``layers/b`` (shifted), leading axis depth//2.
    Use on loop-layout checkpoints (incl. torch imports through
    ``interop.load_torch_into_template``) before binding to a
    ``scan_layers=True`` model. Returns a new dict.
    """
    out = dict(params)
    for i, depth in enumerate(depths):
        rstb = dict(out[f"rstb_{i}"])
        a = [rstb.pop(f"layer_{2 * j}") for j in range(depth // 2)]
        b = [rstb.pop(f"layer_{2 * j + 1}") for j in range(depth // 2)]
        rstb["layers"] = {"a": stack_trees(a), "b": stack_trees(b)}
        out[f"rstb_{i}"] = rstb
    return out


def unstack_swinir_layer_params(params: dict, depths: Sequence[int]) -> dict:
    """Scan layout -> loop layout (inverse of ``stack_swinir_layer_params``);
    use before exporting a scanned model to a torch checkpoint."""
    out = dict(params)
    for i, depth in enumerate(depths):
        rstb = dict(out[f"rstb_{i}"])
        layers = rstb.pop("layers")
        for j, tree in enumerate(unstack_tree(layers["a"], depth // 2)):
            rstb[f"layer_{2 * j}"] = tree
        for j, tree in enumerate(unstack_tree(layers["b"], depth // 2)):
            rstb[f"layer_{2 * j + 1}"] = tree
        out[f"rstb_{i}"] = rstb
    return out
