"""Trinity-Mini (``model_type`` ``afmoe``, arcee-ai, 26B-A3B): a sparse
decoder with gated, QK-normalised grouped-query attention under sandwich
norms, driven by the published ``config.json`` keys.

What it has that the two other sparse models have not: an RMSNorm over each
head's 128 dimensions of q and k before rotary; a sigmoid output gate (a
fifth projection of the attention's input) multiplied into the core's output
before ``o_proj``; four norms a layer, one before and one AFTER each branch;
the embedding multiplied by ``sqrt(hidden_size)`` (``mup_enabled``); 128
routed experts at 8 a token. What it shares: the layer kinds of
``models/smallthinker.py`` (``layer_types[l]``: a causal window of 2,048
with rotary, or the whole causal past with NO positions, 3:1) through the
same banded attention core, 32 query heads on 4 key-value heads; and the
expert layer of ``models/glm4_moe_lite.py`` as it stands (sigmoid scores
over all published experts, a selection bias in ``router_state`` that
selects and never weighs, normalised weights times ``route_scale``, an
unweighted shared expert, the held experts of ``models/held_experts.py``),
read through :attr:`AfmoeConfig.expert_layer`.

Layer ``l`` on input ``x`` (float32 for norms, the gate's sigmoid, router
scores and logits; operands in ``cfg.dtype`` elsewhere, products accumulated
in float32)::

    x0 = embed[tokens] * sqrt(hidden_size)
    a  = RMSNorm_in(x);  q = a W_q -> 32 x 128;  k = a W_k, v = a W_v -> 4 x 128
    g  = a W_g -> 32 x 128;  q <- RMSNorm_q(q), k <- RMSNorm_k(k)  (per head)
    sliding_attention:  q, k <- rotary (split halves, theta 10,000);
                        query t sees keys t - 2047 .. t
    full_attention:     no positions at all; query t sees keys 0 .. t
    head h reads key-value head h // 8;  o = softmax(q k^T / sqrt(128)) v
    h  = x + RMSNorm_post_attn((o * sigmoid(g)) W_o)
    u  = RMSNorm_pre_mlp(h)
    l < num_dense_layers:  f = W_down(silu(W_gate u) * W_up u), width 6,144
    else:  s = sigmoid(u W_r);  sel = top8(s + b)
           w = s[sel] / (sum s[sel] + 1e-20) * 2.826
           f = sum_{i in sel and held} w_i E_i(u) + E_shared(u)
    y  = h + RMSNorm_post_mlp(f)

then a final RMSNorm and an untied head. ``b`` moves after each step by
``load_balance_coeff * sign(mean load - load_i)`` (GLM's rule; the config
gives only the coefficient). No auxiliary loss. What the config does not
say (the gate, the head norms, the four layer norms, rotary on the sliding
layers only, the embedding's multiplier, the unweighted shared expert) is
the public ``transformers`` ``models/afmoe/modeling_afmoe.py``'s.

The layers are not alike, so the stack is written out (no ``scan_layers``);
``cfg.remat`` rematerialises each layer.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..parallel.spec import pin_batch
from .glm4_moe_lite import (  # noqa: F401 (this module's names too)
    ROUTER_STATE, ExpertLayer, GatedMLP, Glm4MoeLiteConfig, RMSNorm, _dense,
    rotary,
)
from .held_experts import (  # noqa: F401 (this module's names too)
    MOE_COUNTERS, MOE_PROBE, routing_counters, sow_probe,
)
from .scan_utils import remat_block
from .smallthinker import (  # noqa: F401 (this module's names too)
    BandedAttnFn, attention_core, banded_attention,
)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (defaults: arcee-ai/Trinity-Mini ``config.json``)
    and what a job chooses (``held_experts``, ``dtype``, ``remat``)."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128  # the published count: the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    sliding_window: int = 2048
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    load_balance_coeff: float = 1e-3
    initializer_range: float = 0.02
    # ids of the routed experts held here, None = all of them
    held_experts: tuple | None = None
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool | str = False  # per layer, as GPT2Config.remat

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.layer_types) != n or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"{n} layers need {n} layer_types of {SLIDING!r} or "
                f"{FULL!r}, got {self.layer_types}"
            )
        if self.score_func != "sigmoid":
            raise ValueError("models/afmoe.py scores its experts by sigmoid")

    def window(self, layer: int) -> int | None:
        """The layer's window, None where it sees the whole causal past
        (and carries no positions)."""
        return self.sliding_window if self.layer_types[layer] == SLIDING else None

    @property
    def expert_layer(self) -> Glm4MoeLiteConfig:
        """This model's MLPs and expert layer are GLM-4.7-Flash's, to the
        letter: the keys they read, under that module's names."""
        return Glm4MoeLiteConfig(
            hidden_size=self.hidden_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.num_experts,
            n_shared_experts=self.num_shared_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.route_scale,
            norm_topk_prob=self.route_norm,
            initializer_range=self.initializer_range,
            held_experts=self.held_experts,
            bias_update_rate=self.load_balance_coeff, dtype=self.dtype,
        )

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        """A dense layer and one period of expert layers, all sliding but
        the last as published; 4 query heads on 2 key-value heads, a window
        shorter than a test's sequence."""
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=24, num_hidden_layers=4, num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=8, num_experts_per_tok=2, sliding_window=5,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL), dtype=jnp.float32,
        )
        base.update(kw)
        return AfmoeConfig(**base)


class Attention(nn.Module):
    """Gated, QK-normalised grouped-query attention of one layer kind:
    ``window`` None is the whole causal past and no positions at all, a
    window comes with rotary."""

    cfg: AfmoeConfig
    attn_fn: BandedAttnFn
    window: int | None

    def setup(self):
        cfg = self.cfg
        wide = cfg.num_attention_heads * cfg.head_dim
        narrow = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _dense(cfg, wide)
        self.k_proj = _dense(cfg, narrow)
        self.v_proj = _dense(cfg, narrow)
        self.gate_proj = _dense(cfg, wide)
        self.o_proj = _dense(cfg, cfg.hidden_size)
        self.q_norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)
        self.k_norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype)

    def qkv(self, a):
        """``a`` [B, T, D] -> q [B, T, H, dh], k and v [B, T, KVH, dh], as
        the core takes them: after the head norms and rotary."""
        cfg = self.cfg
        b, t, _ = a.shape
        heads = lambda x: x.reshape(b, t, -1, cfg.head_dim)  # noqa: E731
        q, k, v = (heads(p(a)) for p in (self.q_proj, self.k_proj, self.v_proj))
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        if self.window is not None:
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        return q, k, v

    def __call__(self, a):
        q, k, v = self.qkv(a)
        # the core only (score, mask, softmax, value product), by layer kind
        kind = "attention_global" if self.window is None else "attention_sliding"
        with jax.named_scope("attention"), jax.named_scope(kind):
            out = self.attn_fn(q, k, v, window=self.window)
        sow_probe(self, q=q, k=k, v=v, output=out)
        out = checkpoint_name(out, "attn_out").reshape(*a.shape[:2], -1)
        with jax.named_scope("attention_gate"):
            gate = jax.nn.sigmoid(self.gate_proj(a).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate).astype(self.cfg.dtype)
        return self.o_proj(out)


class DecoderLayer(nn.Module):
    cfg: AfmoeConfig
    attn_fn: BandedAttnFn
    window: int | None
    dense: bool = False
    interpret: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name
        )
        x = pin_batch(x)
        branch = Attention(cfg, self.attn_fn, self.window, name="attn")(
            norm("input_layernorm")(x)
        )
        with jax.named_scope("post_norm"):
            h = x + norm("post_attention_layernorm")(branch)
        u = norm("pre_mlp_layernorm")(h)
        if self.dense:
            branch = GatedMLP(
                cfg.expert_layer, cfg.intermediate_size, name="mlp_dense"
            )(u)
        else:
            branch = ExpertLayer(cfg.expert_layer, self.interpret, name="moe")(u)
        with jax.named_scope("post_norm"):
            return pin_batch(h + norm("post_mlp_layernorm")(branch))


class Afmoe(nn.Module):
    """``__call__(tokens [B, T]) -> logits [B, T, vocab]`` (float32).

    Apply with ``{"params": ..., "router_state": ...}`` and ``mutable=
    ["router_state", "moe_counters"]`` to get the moved selection biases and
    the routing counters back; with the parameters alone the biases read
    zero and nothing is counted. ``mutable=["moe_probe"]`` gives every
    expert layer's input, router scores, picks and output, and every
    attention core's ``q``, ``k`` (after the head norms and rotary), ``v``
    and output.

    The attention core is ``models/smallthinker.attention_core``: the banded
    blockwise kernel unless ``attn_fn`` says otherwise (``banded_attention``:
    the einsum, for a CPU). ``interpret=True`` interprets both kernels (this
    one and the grouped matmul), for a CPU."""

    cfg: AfmoeConfig = AfmoeConfig()
    attn_fn: BandedAttnFn | None = None
    interpret: bool = False

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        attn_fn = attention_core(self.attn_fn, self.interpret, tokens.shape[1])
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param(
            "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size)
        )
        with jax.named_scope("embed"):
            x = embed[tokens]
            if cfg.mup_enabled:
                x = x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)
            x = pin_batch(x.astype(cfg.dtype))
        layer_cls = remat_block(DecoderLayer, cfg.remat, static_argnums=())
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, attn_fn, cfg.window(i), i < cfg.num_dense_layers,
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
