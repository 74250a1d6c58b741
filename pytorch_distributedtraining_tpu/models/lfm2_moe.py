"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``, LiquidAI): a conv/attention
hybrid with sparse experts, driven by the published ``config.json`` keys.

What it has that no other model here has: a second token mixer, the gated
short convolution (``ops/short_conv.py``), in the layers that
``layer_types`` names ``conv``; the ``full_attention`` layers are grouped-
query attention at heads of 64 (32 query heads read 8 key-value heads) with
an RMSNorm over each head of q and k before rotary; the head is TIED to the
embedding. What it shares: the attention core of ``models/smallthinker.py``
(the blockwise kernel, here over the whole causal past), the held experts of
``models/held_experts.py`` (no shared expert), GLM-4.7-Flash's sigmoid
scores with a selection bias in ``router_state`` that selects and never
weighs, and its ``RMSNorm``, ``rotary`` and gated MLP.

Layer ``l`` on input ``x`` (float32 for norms, router scores and logits;
operands in ``cfg.dtype`` elsewhere, products accumulated in float32)::

    a = RMSNorm_op(x)
    conv:        [B | C | X] = a W_in;  z = B * X
                 y_t = w[:, 0] z_{t-2} + w[:, 1] z_{t-1} + w[:, 2] z_t
                 m = (C * y) W_out
    attention:   q = RMSNorm_q(a W_q) -> 32 x 64;  k = RMSNorm_k(a W_k),
                 v = a W_v -> 8 x 64;  q, k <- rotary (split halves, 1e6)
                 head h reads key-value head h // 4
                 m = softmax_causal(q k^T / 8) v W_out
    h = x + m;  u = RMSNorm_ffn(h)
    l < num_dense_layers:  f = W_down(silu(W_gate u) * W_up u), width 7,168
    else:  s = sigmoid(u W_r);  sel = top4(s + b)
           w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
           f = sum_{i in sel and held} w_i E_i(u)
    x' = h + f

then a final RMSNorm (``embedding_norm``) and the embedding's transpose as
the head. ``b`` moves after each step by ``bias_update_rate * sign(mean
load - load_i)`` (GLM's rule; the config gives ``use_expert_bias`` and no
rate). No auxiliary loss. What the config does not say is the public
``transformers`` LFM2 code's (``Lfm2ShortConv``, ``Lfm2Attention``,
``Lfm2DecoderLayer``; the expert block of ``lfm2_moe``).

The layers are not alike, so the stack is written out (no ``scan_layers``);
``cfg.remat`` rematerialises each layer.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.short_conv import short_conv
from ..parallel.spec import pin_batch
from .glm4_moe_lite import (  # noqa: F401 (this module's names too)
    ROUTER_STATE, GatedMLP, RMSNorm, _dense, rotary,
)
from .held_experts import (  # noqa: F401 (this module's names too)
    MOE_COUNTERS, MOE_PROBE, expert_loads, held_experts_sum,
    routing_counters, sow_probe,
)
from .scan_utils import remat_block
from .smallthinker import (  # noqa: F401 (this module's names too)
    BandedAttnFn, attention_core, banded_attention,
)

CONV, FULL = "conv", "full_attention"
PUBLISHED_LAYER_TYPES = (
    CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV,
    CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, FULL, CONV, CONV,
)


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys (defaults: LiquidAI/LFM2-8B-A1B ``config.json``)
    and what a job chooses (``held_experts``, ``dtype``, ``remat``)."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32  # the published count: the router's width
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # ids of the routed experts held here, None = all of them
    held_experts: tuple | None = None
    bias_update_rate: float = 1e-3  # DeepSeek-V3's; the config gives none
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool | str = False  # per layer, as GPT2Config.remat

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.layer_types) != n or set(self.layer_types) - {CONV, FULL}:
            raise ValueError(
                f"{n} layers need {n} layer_types of {CONV!r} or {FULL!r}, "
                f"got {self.layer_types}"
            )
        if self.conv_bias or not self.use_expert_bias:
            raise ValueError(
                "models/lfm2_moe.py has no conv bias and selects by an "
                "expert bias, as published"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> tuple:
        if self.held_experts is None:
            return tuple(range(self.num_experts))
        return tuple(self.held_experts)

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        """A dense conv layer and one period of expert layers (attention,
        conv, conv, conv) as published; 4 query heads of 64 on 2 key-value
        heads, 256 channels so that the conv has two 128-lane blocks."""
        base = dict(
            vocab_size=96, hidden_size=256, intermediate_size=48,
            moe_intermediate_size=24, num_hidden_layers=5, num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, layer_types=(CONV, FULL, CONV, CONV, CONV),
            dtype=jnp.float32,
        )
        base.update(kw)
        return Lfm2MoeConfig(**base)


def route(scores, bias, cfg: Lfm2MoeConfig):
    """``scores`` [N, E] float32 (sigmoid), ``bias`` [E] -> the chosen
    experts [N, k] and their weights [N, k]: the bias selects, the unbiased
    scores weigh, renormalised with the published ``1e-6``."""
    _, sel = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias)[None, :], cfg.num_experts_per_tok
    )
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return sel, w * cfg.routed_scaling_factor


class ExpertLayer(nn.Module):
    """The held part of the routed experts' sum; no shared expert."""

    cfg: Lfm2MoeConfig
    interpret: bool = False  # the grouped matmul's, for CPU tests

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, d = x.shape
        e = cfg.num_experts
        tokens = x.reshape(b * t, d)
        init = nn.initializers.normal(cfg.initializer_range)

        with jax.named_scope("router"):
            w_r = self.param("router", init, (d, e))
            if self.has_variable(ROUTER_STATE, "bias") or (
                self.is_mutable_collection(ROUTER_STATE)
            ):
                bias_var = self.variable(
                    ROUTER_STATE, "bias", jnp.zeros, (e,), jnp.float32
                )
                bias = bias_var.value
            else:  # a bare apply with the parameters alone
                bias_var, bias = None, jnp.zeros((e,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                tokens.astype(jnp.float32), w_r.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            ))
            sel, weights = route(scores, bias, cfg)
            load = expert_loads(sel, e)
            if bias_var is not None and not self.is_initializing() and (
                self.is_mutable_collection(ROUTER_STATE)
            ):
                bias_var.value = bias + cfg.bias_update_rate * jnp.sign(
                    jnp.mean(load) - load
                )

        out = held_experts_sum(
            self, tokens, sel, weights, load, held=cfg.held,
            width=cfg.moe_intermediate_size, gate=nn.silu, init=init,
            dtype=cfg.dtype, interpret=self.interpret,
        )
        sow_probe(self, input=tokens, scores=scores, picks=sel, output=out)
        return out.reshape(b, t, d)


class ShortConv(nn.Module):
    """The gated short convolution between its two projections; the taps
    ``weight`` [D, L] are torch's ``[D, 1, L]`` squeezed."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, a):
        cfg = self.cfg
        d = cfg.hidden_size
        bcx = _dense(cfg, 3 * d, "in_proj")(a)
        taps = self.param(
            "weight", nn.initializers.normal(cfg.initializer_range),
            (d, cfg.conv_L_cache),
        )
        y = short_conv(bcx, taps.astype(cfg.dtype))
        sow_probe(self, bcx=bcx, output=y)
        return _dense(cfg, d, "out_proj")(y)


class Attention(nn.Module):
    """QK-normed grouped-query attention over the whole causal past, with
    rotary on every dimension of q and k."""

    cfg: Lfm2MoeConfig
    attn_fn: BandedAttnFn

    def setup(self):
        cfg = self.cfg
        dh = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * dh)
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * dh)
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * dh)
        self.out_proj = _dense(cfg, cfg.hidden_size)
        self.q_layernorm = RMSNorm(cfg.norm_eps, cfg.dtype)
        self.k_layernorm = RMSNorm(cfg.norm_eps, cfg.dtype)

    def qkv(self, a):
        """``a`` [B, T, D] -> q [B, T, H, dh], k and v [B, T, KVH, dh], as
        the core takes them: after the head norms and rotary."""
        cfg = self.cfg
        b, t, _ = a.shape
        heads = lambda x: x.reshape(b, t, -1, cfg.head_dim)  # noqa: E731
        q, k, v = (heads(p(a)) for p in (self.q_proj, self.k_proj, self.v_proj))
        with jax.named_scope("qk_norm"):
            q, k = self.q_layernorm(q), self.k_layernorm(k)
        return rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta), v

    def __call__(self, a):
        q, k, v = self.qkv(a)
        with jax.named_scope("attention"):  # the core only
            out = self.attn_fn(q, k, v, window=None)
        sow_probe(self, q=q, k=k, v=v, output=out)
        out = checkpoint_name(out, "attn_out").reshape(*a.shape[:2], -1)
        return self.out_proj(out)


class DecoderLayer(nn.Module):
    cfg: Lfm2MoeConfig
    attn_fn: BandedAttnFn
    attention: bool
    dense: bool = False
    interpret: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.norm_eps, cfg.dtype, name=name
        )
        x = pin_batch(x)
        a = norm("operator_norm")(x)
        if self.attention:
            h = x + Attention(cfg, self.attn_fn, name="self_attn")(a)
        else:
            h = x + ShortConv(cfg, name="conv")(a)
        u = norm("ffn_norm")(h)
        if self.dense:
            f = GatedMLP(cfg, cfg.intermediate_size, name="mlp_dense")(u)
        else:
            f = ExpertLayer(cfg, self.interpret, name="moe")(u)
        return pin_batch(h + f)


class Lfm2Moe(nn.Module):
    """``__call__(tokens [B, T]) -> logits [B, T, vocab]`` (float32).

    Apply with ``{"params": ..., "router_state": ...}`` and ``mutable=
    ["router_state", "moe_counters"]`` to get the moved selection biases and
    the routing counters back; with the parameters alone the biases read
    zero and nothing is counted. ``mutable=["moe_probe"]`` gives every
    expert layer's input, router scores, picks and output, every conv
    mixer's ``bcx`` and output, and every attention core's ``q``, ``k``
    (after the head norms and rotary), ``v`` and output.

    The attention core is ``models/smallthinker.attention_core``: the
    blockwise kernel unless ``attn_fn`` says otherwise (``banded_attention``:
    the einsum, for a CPU). ``interpret=True`` interprets the attention
    kernel and the grouped matmul, for a CPU; the short convolution is
    placed by platform (``ops/short_conv.short_conv``)."""

    cfg: Lfm2MoeConfig = Lfm2MoeConfig()
    attn_fn: BandedAttnFn | None = None
    interpret: bool = False

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        attn_fn = attention_core(self.attn_fn, self.interpret, tokens.shape[1])
        embed = self.param(
            "embed_tokens", nn.initializers.normal(cfg.initializer_range),
            (cfg.vocab_size, cfg.hidden_size),
        )
        with jax.named_scope("embed"):
            x = pin_batch(embed[tokens].astype(cfg.dtype))
        layer_cls = remat_block(DecoderLayer, cfg.remat, static_argnums=())
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, attn_fn, cfg.layer_types[i] == FULL,
                i < cfg.num_dense_layers, self.interpret, name=f"layers_{i}",
            )(x)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="embedding_norm")(x)
        with jax.named_scope("head"):  # tied; float32 out of the MXU
            logits = jnp.dot(
                x, embed.astype(cfg.dtype).T,
                preferred_element_type=jnp.float32,
            )
            return pin_batch(logits)
