"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a DeepSeek-V3-shaped
decoder, driven by the published ``config.json`` keys.

What it has that ``models/gpt2.py`` has not: RMSNorm, rotary positions on a
64-wide part of each head, latent attention (MLA: queries through a rank-768
bottleneck, keys and values through one shared rank-512 latent plus ONE
rotary key for all heads), SiLU-gated MLPs, an untied head, and after the
leading dense layer a sigmoid-routed expert layer with a shared expert.

Layer equations (float32 for norms, router scores and the logits; operands
in ``cfg.dtype`` elsewhere, products accumulated in float32)::

    h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA:  c_q = RMSNorm(x W_qa);  q = c_q W_qb            -> H x (192 + 64)
          [c_kv, k_r] = x W_kva                            (512 + 64)
          [k_nope, v] = RMSNorm(c_kv) W_kvb                -> H x (192 + 256)
          q[.., 192:], k_r <- rotary;  k = [k_nope, k_r]   (k_r shared by heads)
          out = softmax_causal(q k^T / sqrt(256)) v  W_o
    FFN (layer 0):   W_down(silu(W_gate x) * W_up x), width 10,240
    FFN (later):     s = sigmoid(x W_r) over all published experts
                     sel = top4(s + b);  w = s[sel] / (sum s[sel] + 1e-20) * 1.8
                     y = sum_{i in sel and held} w_i E_i(x) + E_shared(x)

**The expert layer is told which experts it holds** (``cfg.held_experts``,
ids into the published ``n_routed_experts``; ``None`` = all). The router
keeps its published width and its experts per token; assignments to absent
experts are left out and the partial result goes on: what one chip of an
expert-parallel job computes before the exchange. One implementation serves
held-all and held-some. **No assignment is dropped**: the assignments are
sorted by expert into a buffer of N x top-k rows (the worst case: all of a
token's experts may be here) and ``ops.grouped_matmul`` works only on the
rows that exist. The selection bias ``b`` lives in the ``router_state``
collection (``TrainState.model_state``), selects and never weighs, takes no
gradient, and moves after each step by ``bias_update_rate * sign(mean load -
load_i)``, the loads of all published experts counted over the tokens seen
here (DeepSeek-V3's auxiliary-loss-free balancing). Counters of the routing
are sown into the ``moe_counters`` collection as device scalars.

Not built: the multi-token-prediction module (``num_nextn_predict_layers``).
The rotary pair layout is split halves (a convention under random weights).
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas_attn import make_flash_attn_fn
from ..parallel.spec import pin_batch
from .gpt2 import AttnFn
from .held_experts import (  # noqa: F401 (this module's names too)
    MOE_COUNTERS, MOE_PROBE, expert_loads, held_experts_sum,
    routing_counters, sow_probe,
)
from .scan_utils import remat_block

ROUTER_STATE = "router_state"  # the selection bias, one [E] vector a layer
# bq = bk of ops/pallas_attn.py (cut to T below it): at [2, 4096, 20, 256]
# bf16 on a v5e 4.19 | 14.06 ms forward | forward + backward, against XLA's
# T x T attention 7.30 | 20.13 with 2.7 GB of scores (PERF.md, PR 27)
ATTENTION_BLOCK = 512


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """The published keys (defaults: zai-org/GLM-4.7-Flash ``config.json``)
    and what a job chooses (``held_experts``, ``dtype``, ``remat``)."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64  # the published count: the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # ids of the routed experts held here, None = all of them
    held_experts: tuple | None = None
    bias_update_rate: float = 1e-3  # DeepSeek-V3's; the config gives none
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool | str = False  # per layer, as GPT2Config.remat

    @property
    def held(self) -> tuple:
        if self.held_experts is None:
            return tuple(range(self.n_routed_experts))
        return tuple(self.held_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @staticmethod
    def tiny(**kw) -> "Glm4MoeLiteConfig":
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=24, num_hidden_layers=3,
            num_attention_heads=2, q_lora_rank=16, kv_lora_rank=12,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2, dtype=jnp.float32,
        )
        base.update(kw)
        return Glm4MoeLiteConfig(**base)


def rotary(x, theta: float):
    """Rotary position embedding over the last axis of ``x`` [B, T, H, R],
    positions 0..T-1, pairs laid out as split halves; float32 inside."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps
        )
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


def _dense(cfg, features: int, name: str | None = None):
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, name=name,
        kernel_init=nn.initializers.normal(cfg.initializer_range),
    )


class GatedMLP(nn.Module):
    cfg: Glm4MoeLiteConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.width, "gate_proj")(x)
        up = _dense(cfg, self.width, "up_proj")(x)
        return _dense(cfg, cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class MLA(nn.Module):
    """Latent attention in its training form (nothing absorbed)."""

    cfg: Glm4MoeLiteConfig
    attn_fn: AttnFn

    def setup(self):
        cfg = self.cfg
        h = cfg.num_attention_heads
        # submodules take their attribute's name (the published ones)
        self.q_a_proj = _dense(cfg, cfg.q_lora_rank)
        self.norm_q = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.q_b_proj = _dense(cfg, h * cfg.qk_head_dim)
        self.kv_a_proj_with_mqa = _dense(
            cfg, cfg.kv_lora_rank + cfg.qk_rope_head_dim
        )
        self.norm_kv = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.kv_b_proj = _dense(
            cfg, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        )
        self.o_proj = _dense(cfg, cfg.hidden_size)

    def qkv(self, x):
        """``x`` [B, T, D] -> q, k [B, T, H, 192 + 64] and v [B, T, H, 256];
        ``k[..., 192:]`` is the one rotary key, the same for every head."""
        cfg = self.cfg
        b, t, _ = x.shape
        h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        q = self.q_b_proj(self.norm_q(self.q_a_proj(x)))
        q = q.reshape(b, t, h, cfg.qk_head_dim)
        c_kv, k_r = jnp.split(
            self.kv_a_proj_with_mqa(x), [cfg.kv_lora_rank], axis=-1
        )
        kv = self.kv_b_proj(self.norm_kv(c_kv)).reshape(
            b, t, h, nope + cfg.v_head_dim
        )
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_r = rotary(q[..., nope:], cfg.rope_theta)
        k_r = rotary(k_r[:, :, None, :], cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (b, t, h, k_r.shape[-1]))], axis=-1
        )
        return q, k, v

    def __call__(self, x):
        q, k, v = self.qkv(x)
        # the core only (score, mask, softmax, value product), whatever
        # ``attn_fn``: the chain around it is this module's own scope, "mla"
        with jax.named_scope("attention"):
            out = self.attn_fn(q, k, v, causal=True)
        out = checkpoint_name(out, "attn_out")
        return self.o_proj(out.reshape(*out.shape[:2], -1))


def route(scores, bias, cfg: Glm4MoeLiteConfig):
    """``scores`` [N, E] float32 (sigmoid), ``bias`` [E] -> the chosen
    experts [N, k] and their weights [N, k]: the bias selects (``noaux_tc``
    with one group: no group limit), the unbiased scores weigh."""
    _, sel = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias)[None, :], cfg.num_experts_per_tok
    )
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return sel, w * cfg.routed_scaling_factor


class ExpertLayer(nn.Module):
    """Shared expert + the held part of the routed experts' sum."""

    cfg: Glm4MoeLiteConfig
    interpret: bool = False  # the grouped matmul's, for CPU tests

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, d = x.shape
        n, e, f = b * t, cfg.n_routed_experts, cfg.moe_intermediate_size
        tokens = x.reshape(n, d)
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
            self, tokens, sel, weights, load, held=cfg.held, width=f,
            gate=nn.silu, init=init, dtype=cfg.dtype,
            interpret=self.interpret,
            shared=GatedMLP(cfg, cfg.n_shared_experts * f, name="mlp_shared"),
        )
        sow_probe(self, input=tokens, scores=scores, picks=sel, output=out)
        return out.reshape(b, t, d)


class DecoderLayer(nn.Module):
    cfg: Glm4MoeLiteConfig
    attn_fn: AttnFn
    dense: bool = False
    interpret: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name
        )
        x = pin_batch(x)
        h = x + MLA(cfg, self.attn_fn, name="mla")(norm("norm_attn")(x))
        y = norm("norm_ffn")(h)
        if self.dense:
            y = GatedMLP(cfg, cfg.intermediate_size, name="mlp_dense")(y)
        else:
            y = ExpertLayer(cfg, self.interpret, name="moe")(y)
        return pin_batch(h + y)


class Glm4MoeLite(nn.Module):
    """``__call__(tokens [B, T]) -> logits [B, T, vocab]`` (float32).

    Apply with ``{"params": ..., "router_state": ...}`` and ``mutable=
    ["router_state", "moe_counters"]`` to get the moved selection biases and
    the routing counters back; with the parameters alone the biases read
    zero and nothing is counted. ``mutable=["moe_probe"]`` gives every
    expert layer's input, router scores, picks and output besides.

    The attention core is the blockwise kernel of ``ops/pallas_attn.py`` at
    ``ATTENTION_BLOCK`` unless ``attn_fn`` says otherwise: the scores of 20
    heads x 4,096 squared never exist. ``interpret=True`` interprets both
    kernels (this one and the grouped matmul), for a CPU."""

    cfg: Glm4MoeLiteConfig = Glm4MoeLiteConfig()
    attn_fn: AttnFn | None = None
    interpret: bool = False

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        attn_fn = self.attn_fn or make_flash_attn_fn(
            bq=ATTENTION_BLOCK, bk=ATTENTION_BLOCK, interpret=self.interpret
        )
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param(
            "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size)
        )
        with jax.named_scope("embed"):
            x = pin_batch(embed[tokens].astype(cfg.dtype))
        layer_cls = remat_block(DecoderLayer, cfg.remat, static_argnums=())
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, attn_fn, i < cfg.first_k_dense_replace,
                self.interpret, name=f"layers_{i}",
            )(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        with jax.named_scope("head"):  # untied; float32 out of the MXU
            lm_head = self.param(
                "lm_head", init, (cfg.hidden_size, cfg.vocab_size)
            )
            logits = jnp.dot(
                x, lm_head.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
            return pin_batch(logits)
