"""Keye-VL-2.0-30B-A3B's language trunk (``model_type`` ``KeyeVL2``,
Kwai-Keye): a Qwen3-MoE-shaped decoder with a learned sparse-attention
indexer (DeepSeek Sparse Attention, DSA) in front of every attention core,
driven by the published ``config.json`` keys.

What it has that no other model here has: the indexer
(``ops/sparse_attn.py``), which scores every causal key of a query with 16
small ReLU heads and one key head and hands the core the 2,048 best
(``sa_config``); the KL loss that trains it; M-RoPE (three position streams,
time, height and width, over the sections ``[16, 24, 24]`` of a head's 64
frequencies). What it shares: the held experts of
``models/held_experts.py`` (SwiGLU, no shared expert), the softmax over the
picked logits of ``models/smallthinker.py``'s router, GLM-4.7-Flash's
``RMSNorm``.

Layer ``l`` on input ``x`` (float32 for norms, router logits, the indexer's
weights and logits; operands in ``cfg.dtype`` elsewhere, products
accumulated in float32)::

    a = RMSNorm(x)
    q = RMSNorm_q(a W_q) -> 32 x 128;  k = RMSNorm_k(a W_k), v = a W_v -> 4 x 128
    q, k <- M-RoPE (theta 1e7; for text t = h = w = position: 1-D rotary)
    indexer, on stop_gradient(a):
        qI = a W_qI -> 16 x 64;  kI = LayerNorm(a W_kI) -> 64
        qI, kI <- rotary on their first 32 dimensions (time stream)
        w = (a W_w) / sqrt(16) / sqrt(64)                     (float32)
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
        S_t = the 2,048 largest I[t, .] (all while t < 2,048; ties: lower s)
    o = softmax over S_t of q k^T / sqrt(128), head h on key-value head h // 8
    h = x + o W_o
    u = RMSNorm(h);  sel = top8(u W_r);  g = softmax(logits[sel])
    x' = h + sum_{e in sel and held} g_e W2_e(silu(W1_e u) * W3_e u)
    KL_l = mean_t KL(p_t || softmax(I[t, S_t])),  p_t = stop_gradient(mean_h attn)

then a final RMSNorm and an untied head. The loss is cross-entropy plus the
KL's mean over the layers (weight 1): the cross-entropy gives the indexer no
gradient (its input is stopped and the selection is discrete), the KL
reaches the indexer alone (its target is stopped). Each layer sows its KL
and the keys it selected into ``indexer_stats``; ``indexer_counters``
reduces them. **The expert layer is told which experts it holds**
(``cfg.held_experts``), routes over all 128 and computes only the held
experts' part. No router auxiliary loss (the config gives no coefficient).

Departures, noted: the config gives no QK norm, LayerNorm or rotary layout
for the indexer; those are Qwen3-MoE's and DSA's (the configuration file's
``assumed``). DSA's Hadamard rotation before its fp8 scores is left out
(applied to ``qI`` and ``kI`` alike it changes no dot product), and the
scores are bf16 operands with float32 sums, not fp8. The vision tower is not
built: the trunk takes text positions.

All layers are alike (``decoder_sparse_step`` 1, no ``mlp_only_layers``);
the stack is written out and ``cfg.remat`` rematerialises each layer.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.sparse_attn import selected_keys, sparse_attention_layer
from ..parallel.spec import pin_batch
from .glm4_moe_lite import RMSNorm, _dense
from .held_experts import (  # noqa: F401 (this module's names too)
    MOE_COUNTERS, MOE_PROBE, expert_loads, held_experts_sum,
    routing_counters, sow_probe,
)
from .scan_utils import remat_block

INDEXER_STATS = "indexer_stats"  # each layer's KL and keys selected


@dataclass(frozen=True)
class KeyeVL2Config:
    """The published keys (defaults: Kwai-Keye/Keye-VL-2.0-30B-A3B
    ``config.json``, its language model and ``sa_config``) and what a job
    chooses (``held_experts``, ``dtype``, ``remat``)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128  # the published count: the router's width
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    indexer_block: int = 512  # sa_config's q_chunk_size = kv_chunk_size
    initializer_range: float = 0.02
    # ids of the routed experts held here, None = all of them
    held_experts: tuple | None = None
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool | str = False  # per layer, as GPT2Config.remat

    def __post_init__(self):
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} must cover the "
                f"{self.head_dim // 2} frequencies of a head"
            )
        if not self.norm_topk_prob:
            raise ValueError("models/keye_vl2.py renormalises the picks")

    @property
    def held(self) -> tuple:
        if self.held_experts is None:
            return tuple(range(self.num_experts))
        return tuple(self.held_experts)

    @staticmethod
    def tiny(**kw) -> "KeyeVL2Config":
        """Two layers, 4 query heads of 16 on one key-value head, an indexer
        of 2 heads of 16 picking 16 keys in blocks of 32."""
        base = dict(
            vocab_size=96, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=1, head_dim=16,
            moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
            mrope_section=(2, 3, 3), indexer_num_heads=2, indexer_head_dim=16,
            indexer_topk=16, indexer_block=32, dtype=jnp.float32,
        )
        base.update(kw)
        return KeyeVL2Config(**base)


def text_positions(batch: int, t: int):
    """``[3, B, T]``: time, height and width streams of text, all 0..T-1."""
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, batch, t))


def _turn(x, angle):
    """Split-half pairs of ``x [B, T, H, R]`` turned by ``angle [B, T, R/2]``;
    float32 inside."""
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).astype(x.dtype)


def mrope(x, positions, theta: float, section):
    """M-RoPE on ``x [B, T, H, R]``: frequency ``i`` of the ``R/2`` turns by
    the position of the stream whose section holds ``i`` (time, height,
    width: ``positions [3, B, T]``). Equal streams give 1-D rotary."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(section), total_repeat_length=half)
    pos = positions.astype(jnp.float32)[stream]  # [R/2, B, T]
    return _turn(x, jnp.moveaxis(pos, 0, -1) * inv)


def rotary_front(x, positions, theta: float, dims: int):
    """1-D rotary (split halves) on the first ``dims`` of ``x``'s last axis,
    the rest as it is: DSA's indexer turns half its head."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([_turn(x[..., :dims], angle), x[..., dims:]], -1)


class Indexer(nn.Module):
    """``a [B, T, D]`` (stopped by the caller) -> ``qI [B, T, HI, DI]``,
    ``kI [B, T, DI]``, ``w [B, T, HI]`` (float32, ``HI^-1/2 DI^-1/2``
    folded in)."""

    cfg: KeyeVL2Config

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        b, t, _ = a.shape
        qi = _dense(cfg, hi * di, "wq")(a).reshape(b, t, hi, di)
        # DSA's k_norm: a LayerNorm with gain and bias, float32 inside
        ki = nn.LayerNorm(1e-6, dtype=cfg.dtype, name="k_norm")(
            _dense(cfg, di, "wk")(a)
        )
        w_proj = self.param(
            "weights_proj", nn.initializers.normal(cfg.initializer_range),
            (a.shape[-1], hi),
        )
        w = jnp.dot(
            a, w_proj.astype(cfg.dtype), preferred_element_type=jnp.float32
        ) * (hi**-0.5 * di**-0.5)
        pos = positions[0]  # the time stream
        qi = rotary_front(qi, pos, cfg.rope_theta, di // 2)
        ki = rotary_front(ki[:, :, None], pos, cfg.rope_theta, di // 2)[:, :, 0]
        return qi, ki, w


class Attention(nn.Module):
    """QK-normed grouped-query attention over the indexer's selection."""

    cfg: KeyeVL2Config
    interpret: bool = False

    def setup(self):
        cfg = self.cfg
        dh = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * dh)
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * dh)
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * dh)
        self.o_proj = _dense(cfg, cfg.hidden_size)
        self.q_norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.k_norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.indexer = Indexer(cfg)

    def qkv(self, a, positions):
        """``a [B, T, D]`` -> q ``[B, T, H, dh]``, k and v ``[B, T, KVH,
        dh]``: after the head norms and M-RoPE."""
        cfg = self.cfg
        b, t, _ = a.shape
        heads = lambda x: x.reshape(b, t, -1, cfg.head_dim)  # noqa: E731
        q, k, v = (heads(p(a)) for p in (self.q_proj, self.k_proj, self.v_proj))
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        turn = lambda x: mrope(  # noqa: E731
            x, positions, cfg.rope_theta, cfg.mrope_section
        )
        return turn(q), turn(k), v

    def __call__(self, a, positions):
        cfg = self.cfg
        q, k, v = self.qkv(a, positions)
        with jax.named_scope("indexer"):
            qi, ki, w = self.indexer(jax.lax.stop_gradient(a), positions)
        out, kl, selection, lse_i = sparse_attention_layer(
            q, k, v, qi, ki, w, topk=cfg.indexer_topk,
            blk=cfg.indexer_block, interpret=self.interpret,
        )
        sow_probe(
            self, q=q, k=k, v=v, qi=qi, ki=ki, w=w, selection=selection,
            lse_i=lse_i, output=out, kl=kl,
        )
        for name, value in (
            ("kl", jnp.mean(kl)), ("selected", selected_keys(selection)),
        ):
            self.sow(INDEXER_STATS, name, value,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        out = checkpoint_name(out, "attn_out")
        return self.o_proj(out.reshape(*a.shape[:2], -1))


def route(logits, k: int):
    """Picks ``[N, k]`` (the largest logits) and their weights ``[N, k]``:
    softmax over the picked logits, the same numbers as softmax over all
    then picked and renormalised (``norm_topk_prob``)."""
    top, sel = jax.lax.top_k(logits, k)
    return sel, jax.nn.softmax(top, axis=-1)


class ExpertLayer(nn.Module):
    """The held part of the routed experts' sum, routed here from the
    layer's own input; no shared expert."""

    cfg: KeyeVL2Config
    interpret: bool = False  # the grouped matmul's, for CPU tests

    @nn.compact
    def __call__(self, u, w_router):
        cfg = self.cfg
        tokens = u.reshape(-1, u.shape[-1])
        with jax.named_scope("router"):
            logits = jnp.dot(
                tokens.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            sel, weights = route(logits, cfg.num_experts_per_tok)
            load = expert_loads(sel, cfg.num_experts)
        out = held_experts_sum(
            self, tokens, sel, weights, load, held=cfg.held,
            width=cfg.moe_intermediate_size, gate=nn.silu,
            init=nn.initializers.normal(cfg.initializer_range),
            dtype=cfg.dtype, interpret=self.interpret,
        )
        sow_probe(
            self, router_input=tokens, input=tokens, scores=logits,
            picks=sel, output=out,
        )
        return out.reshape(u.shape)


class Router(nn.Module):
    """The router's weights ``[D, E]`` over all published experts (a module
    of its own: ``router/kernel``, as ``models/smallthinker.py`` names
    it)."""

    cfg: KeyeVL2Config

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        return self.param(
            "kernel", nn.initializers.normal(cfg.initializer_range),
            (cfg.hidden_size, cfg.num_experts),
        )


class DecoderLayer(nn.Module):
    cfg: KeyeVL2Config
    interpret: bool = False

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name
        )
        x = pin_batch(x)
        h = x + Attention(cfg, self.interpret, name="self_attn")(
            norm("input_layernorm")(x), positions
        )
        y = ExpertLayer(cfg, self.interpret, name="moe")(
            norm("post_attention_layernorm")(h), Router(cfg, name="router")()
        )
        return pin_batch(h + y)


def indexer_counters(stats: dict, queries: int) -> dict:
    """The ``indexer_stats`` collection of one call as what a step
    reports: ``indexer_kl`` (the loss's KL term, the mean over layers),
    ``indexer_kl_layers`` (each layer's), ``selected_keys`` (keys selected
    over every layer and query, an exact int32: one key missing shows) and
    ``selected_keys_per_query`` (its mean over the layers and the call's
    ``queries``), device values."""
    layers = [v["self_attn"] for _, v in sorted(stats.items())]
    kl = jnp.stack([layer["kl"] for layer in layers])
    selected = jnp.stack([layer["selected"] for layer in layers])
    return {
        "indexer_kl": jnp.mean(kl), "indexer_kl_layers": kl,
        "selected_keys": jnp.sum(selected),
        "selected_keys_per_query": jnp.mean(selected.astype(jnp.float32))
        / queries,
    }


class KeyeVL2(nn.Module):
    """``__call__(tokens [B, T], positions [3, B, T] | None) -> logits
    [B, T, vocab]`` (float32); ``positions`` None: text.

    ``mutable=[INDEXER_STATS]`` gives every layer's KL (the loss adds their
    mean: ``indexer_counters``) and keys selected back, ``mutable=
    ["moe_counters"]`` the routing counters, ``mutable=["moe_probe"]``
    every expert layer's input, logits, picks and output and every
    attention layer's q, k (after the head norms and M-RoPE), v, the
    indexer's qI, kI, w, its selection and ``lse_I``, the core's output and
    the KL by query.
    ``interpret=True`` interprets the kernels (the indexer's and the core's
    and the grouped matmul), for a CPU."""

    cfg: KeyeVL2Config = KeyeVL2Config()
    interpret: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None):
        cfg = self.cfg
        b, t = tokens.shape
        if positions is None:
            positions = text_positions(b, t)
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param(
            "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size)
        )
        with jax.named_scope("embed"):
            x = pin_batch(embed[tokens].astype(cfg.dtype))
        layer_cls = remat_block(DecoderLayer, cfg.remat, static_argnums=())
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(cfg, self.interpret, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        with jax.named_scope("head"):  # untied; float32 out of the MXU
            lm_head = self.param(
                "lm_head", init, (cfg.hidden_size, cfg.vocab_size)
            )
            logits = jnp.dot(
                x, lm_head.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
            return pin_batch(logits)
