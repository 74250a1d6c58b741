"""GPT-2 causal LM — BASELINE ladder config 4 ("FSDP GPT-2 125M").

The reference's capability contract (BASELINE.json, written against the
Fairscale FSDP surface Stoke exposes — `/root/reference/Stoke-DDP.py:249-250`
flag family) ladders through GPT-2 125M under ZeRO-3. Decoder-only
transformer, pre-LN, learned positional embeddings, tied LM head.

TPU-native choices:
  - [B, T, D] activations, fused QKV projection — one big MXU matmul.
  - the attention core of a training block is decided from what the
    program can observe when no ``attn_fn`` is named: the blockwise kernels
    of `ops/pallas_attn.py` (no T x T scores in HBM) where the program is
    lowered for a TPU, the shapes meet the kernels' contract and the step's
    mesh is known; XLA's einsums (``default_attention``) everywhere else,
    a CPU included. ``attn_fn=default_attention`` names the einsums,
    ``ops.make_flash_attn_fn(...)`` the kernel or an error,
    `ops.ring_attention.make_ring_attn_fn` slots in for sp. Each trace says
    which it took in the instant ``attention.path``.
  - Param layout is Megatron-friendly under pjit: sharding the QKV/MLP-in
    kernels on the output dim and proj/MLP-out on the input dim over "tp"
    yields the classic two-allreduce-per-block pattern from XLA, no manual
    collectives (see parallel/tensor.py rules).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.spec import pin_batch, published_batch_mesh
from ..runtime.mesh import data_axes
from ..precision import fp8_dot_general_cls
from .generate import (
    kv_scale_block,
    paged_attention,
    quantize_kv,
    write_paged_kv,
)
from .scan_utils import remat_block

AttnFn = Callable[..., jnp.ndarray]  # (q, k, v, *, causal) -> out

# (regex, repl) rewrites from the HF/torch GPT-2 state_dict naming onto this
# module tree (flat "/"-joined keys; None drops torch-only buffers). HF
# linear weights use the Conv1D [in, out] convention — load with
# ``interop.load_torch_into_template(..., key_map=HF_KEY_MAP,
# conv1d_kernels=True)`` so they are NOT transposed. ``lm_head`` is dropped
# because this model ties it to ``wte`` (HF GPT2LMHeadModel ties it too).
HF_KEY_MAP = [
    (r"(^|/)attn/(bias|masked_bias)$", None),  # causal-mask buffers
    (r"^lm_head/.*$", None),  # tied to wte
    (r"^transformer/", ""),
    (r"^h/(\d+)/attn/c_attn/", r"h_\1/c_attn/"),
    (r"^h/(\d+)/attn/c_proj/", r"h_\1/c_proj/"),
    (r"^h/(\d+)/mlp/c_fc/", r"h_\1/mlp_fc/"),
    (r"^h/(\d+)/mlp/c_proj/", r"h_\1/mlp_proj/"),
    (r"^h/(\d+)/ln_(1|2)/", r"h_\1/ln_\2/"),
    (r"^wte/weight$", "wte"),
    (r"^wpe/weight$", "wpe"),
]

# Inverse direction (export, `interop.torch_gpt2_state_dict`): framework
# flat keys -> HF ``GPT2LMHeadModel`` names. Kept next to HF_KEY_MAP so
# the two directions evolve together (same convention as
# ``swinir.SWINIR_EXPORT_KEY_MAP``). HF linears are Conv1D [in, out] —
# the flax Dense layout — so kernels export untransposed, EXCEPT an
# untied ``lm_head`` which is an nn.Linear [out, in] (handled by the
# exporter's leaf fixup, not a key rule).
GPT2_EXPORT_KEY_MAP = [
    (r"^h_(\d+)/c_attn/", r"transformer.h.\1.attn.c_attn."),
    (r"^h_(\d+)/c_proj/", r"transformer.h.\1.attn.c_proj."),
    (r"^h_(\d+)/mlp_fc/", r"transformer.h.\1.mlp.c_fc."),
    (r"^h_(\d+)/mlp_proj/", r"transformer.h.\1.mlp.c_proj."),
    (r"^h_(\d+)/ln_(1|2)/", r"transformer.h.\1.ln_\2."),
    (r"^ln_f/", "transformer.ln_f."),
    (r"^wte$", "transformer.wte.weight"),
    (r"^wpe$", "transformer.wpe.weight"),
]


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    tie_word_embeddings: bool = True
    # Checkpoint each block (FSDP memory, SURVEY §7c): bool (True == "full")
    # or a named policy from parallel/remat.py ("dots"/"names"/"offload").
    remat: bool | str = False
    # Run the block stack under `nn.scan` (jax.lax.scan over stacked
    # per-layer params): XLA traces/compiles ONE block instead of n_layer —
    # the cold-compile lever. Param layout changes from `h_{i}/...` to a
    # stacked `h/...` (leading axis n_layer); `scan_utils.stack_layer_params`
    # converts loop-layout checkpoints. Ignored under `decode=True` (the KV
    # cache keeps the unrolled loop).
    scan_layers: bool = False
    # Narrow the block Dense matmuls to fp8 operands ("e4m3"/"e5m2" forward
    # dtype; backward cotangents always e5m2): amax histories land in the
    # "fp8" variable collection, riding TrainState.model_state. The tied
    # embedding matmul stays at cfg.dtype (vocab-sized amax is outlier-bound).
    fp8: str | None = None

    @staticmethod
    def gpt2_125m() -> "GPT2Config":
        return GPT2Config()  # the 125M point IS the default config

    @staticmethod
    def gpt2_medium() -> "GPT2Config":  # 350M
        return GPT2Config(n_embd=1024, n_layer=24, n_head=16)

    @staticmethod
    def gpt2_large() -> "GPT2Config":  # 774M
        return GPT2Config(n_embd=1280, n_layer=36, n_head=20)

    @staticmethod
    def gpt2_xl() -> "GPT2Config":  # 1.5B
        return GPT2Config(n_embd=1600, n_layer=48, n_head=25)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        base = dict(vocab_size=256, n_positions=64, n_embd=32, n_layer=2,
                    n_head=2, dtype=jnp.float32)
        base.update(kw)
        return GPT2Config(**base)


def default_attention(q, k, v, *, causal: bool = True):
    """XLA softmax attention. q/k/v: [B, T, H, Dh] -> [B, T, H, Dh]."""
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh).astype(q.dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _split_heads(qkv, heads):
    """``[B, T, 3 * H * dh]`` as ``c_attn`` wrote it -> q, k, v
    ``[B, T, H, dh]``."""
    b, t, d3 = qkv.shape
    return tuple(
        a.reshape(b, t, heads, d3 // 3 // heads)
        for a in jnp.split(qkv, 3, axis=-1)
    )


@partial(jax.jit, static_argnames=("heads", "mesh"))
def _kernel_or_einsum_attention(qkv, heads, mesh=None):
    """The causal core between the projections, ``qkv [B, T, 3 * H * dh]``
    -> ``[B, T, H, dh]``: the blockwise kernels where the program is lowered
    for a TPU, ``default_attention`` on any other platform. One traced
    program serves both and only the branch of the platform it is lowered
    for is compiled. Jitted so that a model's layers, which call it with the
    same shapes, share one trace of both branches (trace and lowering are
    paid in every run's set-up, compile cache or not).

    The partitioner cannot split a Mosaic kernel and refuses a program that
    spans devices with one in it. Given the step's ``mesh``, each device
    runs the core over its own sequences (``shard_map`` over the data axes)."""
    from ..ops.pallas_attn import causal_attention_qkv

    def core(qkv):
        return jax.lax.platform_dependent(
            qkv,
            tpu=partial(causal_attention_qkv, heads=heads),
            default=lambda qkv: default_attention(
                *_split_heads(qkv, heads), causal=True
            ),
        )

    if mesh is None:
        return core(qkv)
    rows = P(data_axes(mesh))
    return jax.shard_map(
        core, mesh=mesh, in_specs=rows, out_specs=rows,
        check_vma=False,  # a pallas_call says nothing of varying axes
    )(qkv)


def _kernel_placement(b, t, heads, dh, dtype):
    """Where a training block that was given no ``attn_fn`` may place the
    kernels: ``(mesh, None)``, with the mesh to split the batch over or None
    for a program on one device, or ``(None, why not)`` in words. A program
    that spans devices takes them only where the step has published its
    mesh (``spec.batch_layout``) and every axis of it splits the batch."""
    from ..ops.pallas_attn import kernel_contract

    why = kernel_contract(t, heads, dh, dtype)
    if why is not None:
        return None, why
    mesh = published_batch_mesh()
    if mesh is None:
        if jax.device_count() == 1:
            return None, None
        return None, (
            f"{jax.device_count()} devices and no step has published the "
            "batch's layout: the program may span them, and the partitioner "
            "cannot split a kernel"
        )
    if mesh.size == 1:
        return None, None
    shards = math.prod(mesh.shape[a] for a in data_axes(mesh))
    if shards != mesh.size:
        return None, (
            f"the mesh {dict(mesh.shape)} has axes that do not split the "
            "batch: heads or sequence may be split there"
        )
    if b % shards:
        return None, f"{b} sequences do not split over {shards} devices"
    return mesh, None


class Block(nn.Module):
    """Pre-LN transformer block: LN → attn → +res, LN → MLP → +res.

    ``decode=True`` switches attention to incremental KV-cache mode: K/V
    land in a ``"cache"`` collection sized by the init-time sequence length,
    and each call attends the new queries against everything cached so far
    (chunked prefill and single-token decode both work).

    ``paged=(num_pages, page_size)`` (with ``decode=True``) switches to the
    serving layout instead: K/V land in a shared page pool (``"pages"``
    collection), each batch row is a *slot* addressed by a per-call
    ``page_table`` + ``lengths``, and slots at different positions decode
    together (models/generate.py documents the layout and its
    write-before-read invariant).
    """

    cfg: GPT2Config
    # the training path's attention core; None decides from the shapes, the
    # platform the program is lowered for and the step's mesh (``_decide``)
    attn_fn: Optional[AttnFn] = None
    decode: bool = False
    # scan-body mode: return (x, None) so the block slots into nn.scan
    as_scan_body: bool = False
    paged: tuple | None = None  # (num_pages, page_size) page-pool KV layout
    # block-scaled quantized page residency (serve/kv_cache.py): a resolved
    # parallel/compressed.WireFormat; the "pages" collection then holds
    # narrow payloads + per-block f32 scales instead of cfg.dtype K/V
    kv_wire: Optional[object] = None

    def _cached_attention(self, q, k, v, idx):
        """[B, T, H, Dh] step against the persistent cache; ``idx`` is the
        global write position (GPT2's single top-level counter)."""
        is_initialized = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros, k.shape, k.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros, v.shape, v.dtype)
        if not is_initialized:  # init pass defines cache shapes only
            return default_attention(q, k, v, causal=True)
        t = q.shape[1]
        max_len = ck.value.shape[1]
        ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, idx, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, idx, 0, 0))
        dh = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, ck.value) / jnp.sqrt(
            dh
        ).astype(q.dtype)
        qpos = idx + jnp.arange(t)[:, None]  # [T, 1] global positions
        kpos = jnp.arange(max_len)[None, :]
        mask = kpos <= qpos  # causal incl. everything already cached
        logits = jnp.where(mask[None, None], logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, cv.value)

    def _paged_attention(self, q, k, v, page_table, lengths):
        """[B, T, H, Dh] step against this layer's shared page pool.

        Writes the chunk's K/V at each slot's position, then attends the
        gathered page view (generate.paged_attention) — the paged twin of
        :meth:`_cached_attention` with per-slot instead of global position.
        """
        n_pages, page = self.paged
        h, dh = q.shape[2], q.shape[3]
        fmt = self.kv_wire
        kv_dtype = fmt.payload_dtype if fmt is not None else k.dtype
        is_initialized = self.has_variable("pages", "k_pages")
        kp = self.variable(
            "pages", "k_pages", jnp.zeros, (n_pages, page, h, dh), kv_dtype
        )
        vp = self.variable(
            "pages", "v_pages", jnp.zeros, (n_pages, page, h, dh), kv_dtype
        )
        ks = vs = None
        if fmt is not None:
            blk = kv_scale_block(fmt, h, dh)
            n_scales = (h * dh) // blk
            ks = self.variable(
                "pages", "k_scales", jnp.zeros,
                (n_pages, page, n_scales), jnp.float32,
            )
            vs = self.variable(
                "pages", "v_scales", jnp.zeros,
                (n_pages, page, n_scales), jnp.float32,
            )
        if not is_initialized:  # init pass defines pool shapes only
            return default_attention(q, k, v, causal=True)
        if fmt is None:
            kp.value, vp.value = write_paged_kv(
                kp.value, vp.value, k, v, page_table, lengths
            )
            return paged_attention(q, kp.value, vp.value, page_table, lengths)
        # quantize on page write: payload and scales scatter with the same
        # (phys, off) indexing; dequantize happens in the gathered read
        qk, sk = quantize_kv(k, fmt, blk)
        qv, sv = quantize_kv(v, fmt, blk)
        kp.value, vp.value = write_paged_kv(
            kp.value, vp.value, qk, qv, page_table, lengths
        )
        ks.value, vs.value = write_paged_kv(
            ks.value, vs.value, sk, sv, page_table, lengths
        )
        return paged_attention(
            q, kp.value, vp.value, page_table, lengths,
            k_scales=ks.value, v_scales=vs.value,
        )

    def _decide(self, qkv):
        """With no ``attn_fn`` named: may the blockwise kernels compute this
        block's core, and placed over which mesh (None: one device)?
        Decided from what can be seen, the mode, the shapes and the
        published mesh now (the platform when the program is lowered:
        ``_kernel_or_einsum_attention``), and said at every trace."""
        from ..observe import trace
        from ..ops.pallas_attn import attention_blocks

        heads = self.cfg.n_head
        b, t, d3 = qkv.shape
        dh = d3 // 3 // heads
        mesh = None
        if self.decode:
            why = "decode=True: the step attends the " + (
                "KV cache" if self.paged is None else "page pool"
            )
        else:
            mesh, why = _kernel_placement(b, t, heads, dh, qkv.dtype)
        kernels = why is None
        if kernels:
            why = "shapes meet the kernels' contract" + (
                "" if mesh is None else
                f"; each of the mesh's {mesh.size} devices its own sequences"
            )
        bq, bk = attention_blocks(t) if kernels else (None, None)
        trace.instant(
            "attention.path", path="by_platform" if kernels else "einsum",
            reason=why, b=b, t=t, heads=heads, dh=dh, bq=bq, bk=bk,
            mesh=1 if mesh is None else mesh.size,
        )
        return kernels, mesh

    @nn.compact
    def __call__(self, x, deterministic: bool = True, start_index=None,
                 page_table=None, lengths=None):
        cfg = self.cfg
        d, h = cfg.n_embd, cfg.n_head
        # the residual stream enters and leaves every block in the layout
        # the step's batch has (identity unless a step publishes one): in
        # the scan body, so in the rematerialised computation too
        x = pin_batch(x)
        dense = lambda feat, name: nn.Dense(  # noqa: E731
            feat, dtype=cfg.dtype, name=name,
            kernel_init=nn.initializers.normal(0.02),
            dot_general_cls=fp8_dot_general_cls(cfg.fp8),
        )

        y = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_1")(x)
        qkv = dense(3 * d, "c_attn")(y)
        # the attention core (score, mask, softmax, value product; not the
        # projections, which Flax names) under one scope, whatever computes
        # it: metadata only, so a profile gives attention's share
        with jax.named_scope("attention"):
            kernels, mesh = (
                self._decide(qkv) if self.attn_fn is None else (False, None)
            )
            if kernels:
                y = _kernel_or_einsum_attention(qkv, h, mesh)
            else:
                q, k, v = _split_heads(qkv, h)
                if not self.decode:
                    y = (self.attn_fn or default_attention)(
                        q, k, v, causal=True
                    )
                elif self.paged is not None:
                    y = self._paged_attention(q, k, v, page_table, lengths)
                else:
                    y = self._cached_attention(
                        q, k, v,
                        jnp.zeros((), jnp.int32)
                        if start_index is None else start_index,
                    )
        # named-remat tag (parallel/remat.py "names"/"offload" policies):
        # save the softmax·V product, recompute the cheap projections
        y = checkpoint_name(y, "attn_out")
        y = y.reshape(*y.shape[:2], d)
        y = dense(d, "c_proj")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        x = x + y

        y = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_2")(x)
        y = dense(cfg.mlp_ratio * d, "mlp_fc")(y)
        y = nn.gelu(y, approximate=True)
        y = dense(d, "mlp_proj")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        out = pin_batch(x + y)
        if self.as_scan_body:
            return out, None
        return out


class GPT2(nn.Module):
    """GPT-2 LM. ``__call__(tokens [B, T]) -> logits [B, T, vocab]``.

    ``decode=True``: incremental KV-cache inference — init with the max
    sequence length to size the cache, then apply token chunks with
    ``mutable=["cache"]`` (see models/generate.py).

    ``decode=True`` + ``paged=(num_pages, page_size)``: paged serving
    layout — K/V land in a shared page pool (``"pages"`` collection) and
    every call must pass ``page_table`` [B, max_pages] and ``lengths`` [B]
    (per-slot positions; there is no global counter, so slots at different
    sequence positions batch together — the continuous-batching contract).
    """

    cfg: GPT2Config = GPT2Config()
    # None: each training block decides (``Block._decide``: the blockwise
    # kernels on a TPU where shapes and mesh allow, else the einsums);
    # ``default_attention`` names the einsums, ``make_flash_attn_fn(...)``
    # the kernel or an error
    attn_fn: Optional[AttnFn] = None
    decode: bool = False
    paged: tuple | None = None  # (num_pages, page_size); needs decode=True
    # quantized page residency (with ``paged``): resolved WireFormat whose
    # payload dtype + per-block f32 scales replace cfg.dtype pages — see
    # serve/kv_cache.py for the format table and HBM accounting
    kv_wire: Optional[object] = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, *,
                 page_table=None, lengths=None):
        cfg = self.cfg
        b, t = tokens.shape
        wte = self.param(
            "wte", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.n_embd)
        )
        wpe = self.param(
            "wpe", nn.initializers.normal(0.01), (cfg.n_positions, cfg.n_embd)
        )
        start_index = None  # blocks' global KV write position this call
        if self.kv_wire is not None and self.paged is None:
            raise ValueError("kv_wire quantized pages require the paged layout")
        if self.paged is not None:
            if not self.decode:
                raise ValueError("paged KV layout requires decode=True")
            if page_table is None or lengths is None:
                raise ValueError(
                    "paged decode needs page_table [B, max_pages] and "
                    "lengths [B] on every call"
                )
            # per-slot positions; clip keeps padded garbage rows in range
            pos = jnp.clip(
                lengths[:, None] + jnp.arange(t)[None, :],
                0, cfg.n_positions - 1,
            )
            pe = wpe[pos]  # [B, T, D]
        elif self.decode and self.has_variable("cache", "position"):
            pos_var = self.variable(
                "cache", "position", lambda: jnp.zeros((), jnp.int32)
            )
            start_index = pos_var.value
            pos = start_index + jnp.arange(t)
            pos_var.value = start_index + t
            pe = wpe[pos]
        else:
            if self.decode:  # init pass: create the position counter
                self.variable(
                    "cache", "position", lambda: jnp.zeros((), jnp.int32)
                )
            pe = wpe[:t]
        with jax.named_scope("embed"):
            x = pin_batch(wte[tokens].astype(cfg.dtype) + pe.astype(cfg.dtype))
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        if cfg.scan_layers and not self.decode:
            # one traced/compiled block for all n_layer (stacked params on
            # a leading axis under name "h"); per-block remat nests inside
            # the scan — the standard form: scan saves only the inter-layer
            # carry, remat recomputes block internals in backward
            block_cls = remat_block(Block, cfg.remat, in_scan=True)
            blocks = nn.scan(
                block_cls,
                variable_axes={"params": 0, "fp8": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.n_layer,
            )
            x, _ = blocks(
                cfg, self.attn_fn, False, True, name="h"
            )(x, deterministic, start_index)
        else:
            block_cls = remat_block(Block, cfg.remat)
            for i in range(cfg.n_layer):
                x = block_cls(
                    cfg, self.attn_fn, self.decode, paged=self.paged,
                    kv_wire=self.kv_wire, name=f"h_{i}",
                )(x, deterministic, start_index, page_table, lengths)

        x = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="ln_f")(x)
        with jax.named_scope("head"):  # the (tied) output projection
            if cfg.tie_word_embeddings:
                logits = x @ wte.T.astype(cfg.dtype)
            else:
                logits = nn.Dense(
                    cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                    name="lm_head",
                )(x)
            return pin_batch(logits.astype(jnp.float32))


def cross_entropy_loss(logits, targets, ignore_index: int = -100):
    """Token-level CE with ignore mask; logits [B,T,V], targets [B,T]."""
    with jax.named_scope("loss"):
        mask = (targets != ignore_index).astype(jnp.float32)
        safe = jnp.where(targets == ignore_index, 0, targets)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
