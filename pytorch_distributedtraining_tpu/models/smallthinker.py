"""SmallThinker-21BA3B-Instruct (``model_name`` ``smallthinker_21b_instruct``,
PowerInfer): a sparse decoder whose layers are of two kinds, driven by the
published ``config.json`` keys.

What it has that ``models/glm4_moe_lite.py`` has not: grouped-query attention
(28 query heads read 4 key-value heads, 7 each), a per-layer kind read from
two published lists (``sliding_window_layout[l]``: a causal window of 4,096
or the whole causal past; ``rope_layout[l]``: rotary on all of a head's 128
dimensions or NO positions at all; both 0 at layers 0, 4, 8, .. and 1
elsewhere), a router that reads the layer's RAW input, before the attention
norm and before attention, softmax over the picked logits, ReLU gates and no
shared expert.

Layer ``l`` on input ``x`` (float32 for norms, router and logits; operands
in ``cfg.dtype`` elsewhere, products accumulated in float32)::

    r = x W_r                          [.., 64], float32 at 'highest'
    a = RMSNorm_1(x);  q = a W_q -> 28 x 128;  k = a W_k, v = a W_v -> 4 x 128
    rope_layout[l]:  q, k <- rotary (split halves, theta 1.5e6)
    head h reads key-value head h // 7;  scores q . k / sqrt(128), causal;
    sliding_window_layout[l]:  query t sees keys t - 4095 .. t, else 0 .. t
    h = x + concat(heads) W_o
    u = RMSNorm_2(h);  sel = top6(r);  w = softmax(r[sel])
    y = h + sum_{i in sel and held} w_i (relu(u G_i) * (u U_i)) D_i

then a final RMSNorm and an untied head. **The expert layer is told which
experts it holds** (``cfg.held_experts``) and is the one of
``models/held_experts.py``, shared with GLM-4.7-Flash: dropless, the held
part of the routed sum, counters in ``moe_counters``. No auxiliary loss (the
config gives no coefficient). Departure from the published dtype, noted: the
router's matmul is float32 at ``HIGHEST`` whatever the policy, as GLM's is.

The layers are not alike, so the stack is written out (no ``scan_layers``);
``cfg.remat`` rematerialises each layer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas_attn import make_flash_attn_fn
from ..parallel.spec import pin_batch
from .glm4_moe_lite import RMSNorm, rotary
from .held_experts import (  # noqa: F401 (this module's names too)
    MOE_COUNTERS, MOE_PROBE, expert_loads, held_experts_sum,
    routing_counters, sow_probe,
)
from .scan_utils import remat_block

# bq = bk of ops/pallas_attn.py (cut to T below it)
ATTENTION_BLOCK = 512
# (q [B, T, H, dh], k, v [B, T, KVH, dh], *, window) -> [B, T, H, dh], causal
BandedAttnFn = Callable[..., jax.Array]


@dataclass(frozen=True)
class SmallThinkerConfig:
    """The published keys (defaults: PowerInfer/SmallThinker-21BA3B-Instruct
    ``config.json``) and what a job chooses (``held_experts``, ``dtype``,
    ``remat``)."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64  # the published count: the router's width
    moe_num_active_primary_experts: int = 6
    sliding_window_size: int = 4096
    # one entry a layer: 1 = window / rotary, 0 = full / no positions
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13
    rope_layout: tuple = (0, 1, 1, 1) * 13
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    initializer_range: float = 0.02
    # ids of the experts held here, None = all of them
    held_experts: tuple | None = None
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool | str = False  # per layer, as GPT2Config.remat

    def __post_init__(self):
        n = self.num_hidden_layers
        if (len(self.sliding_window_layout), len(self.rope_layout)) != (n, n):
            raise ValueError(
                f"{n} layers need {n} entries in sliding_window_layout and "
                f"rope_layout, got {len(self.sliding_window_layout)} and "
                f"{len(self.rope_layout)}"
            )

    @property
    def held(self) -> tuple:
        if self.held_experts is None:
            return tuple(range(self.moe_num_primary_experts))
        return tuple(self.held_experts)

    def window(self, layer: int) -> int | None:
        """The layer's window, None where it sees the whole causal past."""
        return (
            self.sliding_window_size if self.sliding_window_layout[layer]
            else None
        )

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """Four layers in the published pattern, 4 query heads on 2
        key-value heads, a window shorter than a test's sequence."""
        base = dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_ffn_hidden_size=24, moe_num_primary_experts=8,
            moe_num_active_primary_experts=2, sliding_window_size=5,
            sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
            dtype=jnp.float32,
        )
        base.update(kw)
        return SmallThinkerConfig(**base)


def banded_attention(q, k, v, *, window=None):
    """The einsum a CPU runs and the kernel is held against: causal
    ``softmax(q k^T / sqrt(dh)) v`` with query head ``h`` on key-value head
    ``h // (H / KVH)``, under a ``window`` keys ``t - window + 1 .. t``
    only; scores in float32, all T x T of them."""
    b, t, h, dh = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, t, kvh, h // kvh, dh)
    s = jnp.einsum(
        "bqjgd,bkjd->bjgqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(dh))
    qpos, kpos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = kpos <= qpos
    if window is not None:
        keep = keep & (kpos > qpos - window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bjgqk,bkjd->bqjgd", p.astype(v.dtype), v)
    return out.reshape(b, t, h, dh)


def _dense(cfg, features: int, name: str):
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, name=name,
        kernel_init=nn.initializers.normal(cfg.initializer_range),
    )


class Router(nn.Module):
    """``x`` [N, D], the layer's raw input -> logits [N, E] (float32), the
    picks [N, k], their weights [N, k] (softmax over the picked logits: the
    same numbers as softmax over all, pick, renormalise) and the published
    experts' loads [E]."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        w_r = self.param(
            "kernel", nn.initializers.normal(cfg.initializer_range),
            (tokens.shape[-1], cfg.moe_num_primary_experts),
        )
        logits = jnp.dot(
            tokens.astype(jnp.float32), w_r.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top, sel = jax.lax.top_k(logits, cfg.moe_num_active_primary_experts)
        weights = jax.nn.softmax(top, axis=-1)
        return logits, sel, weights, expert_loads(
            sel, cfg.moe_num_primary_experts
        )


class ExpertLayer(nn.Module):
    """The held part of the routed experts' sum, on picks made elsewhere."""

    cfg: SmallThinkerConfig
    interpret: bool = False  # the grouped matmul's, for CPU tests

    @nn.compact
    def __call__(self, u, routed_from, logits, sel, weights, load):
        cfg = self.cfg
        tokens = u.reshape(-1, u.shape[-1])
        out = held_experts_sum(
            self, tokens, sel, weights, load, held=cfg.held,
            width=cfg.moe_ffn_hidden_size, gate=nn.relu,
            init=nn.initializers.normal(cfg.initializer_range),
            dtype=cfg.dtype, interpret=self.interpret,
        )
        sow_probe(
            self, router_input=routed_from, input=tokens, scores=logits,
            picks=sel, output=out,
        )
        return out.reshape(u.shape)


class Attention(nn.Module):
    """Grouped-query attention of one layer kind: ``window`` None is the
    whole causal past, ``rope`` False no positions at all."""

    cfg: SmallThinkerConfig
    attn_fn: BandedAttnFn
    window: int | None
    rope: bool

    def setup(self):
        cfg = self.cfg
        dh = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * dh, None)
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * dh, None)
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * dh, None)
        self.o_proj = _dense(cfg, cfg.hidden_size, None)

    def qkv(self, a):
        """``a`` [B, T, D] -> q [B, T, H, dh], k and v [B, T, KVH, dh]."""
        cfg = self.cfg
        b, t, _ = a.shape
        heads = lambda x: x.reshape(b, t, -1, cfg.head_dim)  # noqa: E731
        q, k, v = (heads(p(a)) for p in (self.q_proj, self.k_proj, self.v_proj))
        if self.rope:
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        return q, k, v

    def __call__(self, a):
        q, k, v = self.qkv(a)
        # the core only (score, mask, softmax, value product), by layer kind
        kind = "attention_global" if self.window is None else "attention_sliding"
        with jax.named_scope("attention"), jax.named_scope(kind):
            out = self.attn_fn(q, k, v, window=self.window)
        sow_probe(self, q=q, k=k, v=v, output=out)
        out = checkpoint_name(out, "attn_out")
        return self.o_proj(out.reshape(*out.shape[:2], -1))


class DecoderLayer(nn.Module):
    cfg: SmallThinkerConfig
    attn_fn: BandedAttnFn
    window: int | None
    rope: bool
    interpret: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name
        )
        x = pin_batch(x)
        # the router reads what the layer was given, before attention
        raw = x.reshape(-1, x.shape[-1])
        routing = Router(cfg, name="router")(raw)
        h = x + Attention(
            cfg, self.attn_fn, self.window, self.rope, name="attn"
        )(norm("norm_attn")(x))
        y = ExpertLayer(cfg, self.interpret, name="moe")(
            norm("norm_ffn")(h), raw, *routing
        )
        return pin_batch(h + y)


def attention_core(attn_fn: BandedAttnFn | None, interpret: bool, t: int):
    """The core every layer of a model calls on sequences of ``t``: the
    banded blockwise kernel of ``ops/pallas_attn.py`` at ``ATTENTION_BLOCK``
    unless ``attn_fn`` names another; each traced layer says which it got in
    the instant ``attention.path``."""
    from ..observe import trace

    block = min(ATTENTION_BLOCK, t)
    kernel = attn_fn is None
    inner = attn_fn or make_flash_attn_fn(
        bq=block, bk=block, interpret=interpret
    )

    def core(q, k, v, *, window):
        trace.instant(
            "attention.path", path="kernel" if kernel else "attn_fn",
            reason=(
                "the model's own banded blockwise kernel" if kernel
                else "the caller named the attention function"
            ),
            window=window, heads=q.shape[2], kv_heads=k.shape[2], t=t,
            bq=block if kernel else None, bk=block if kernel else None,
        )
        return inner(q, k, v, window=window)

    return core


class SmallThinker(nn.Module):
    """``__call__(tokens [B, T]) -> logits [B, T, vocab]`` (float32).

    ``mutable=["moe_counters"]`` gives the routing counters back,
    ``mutable=["moe_probe"]`` every expert layer's router input, experts'
    input, router logits, picks and output, and every attention core's
    ``q``, ``k``, ``v`` (after rotary) and output.

    The attention core is the banded blockwise kernel of
    ``ops/pallas_attn.py`` at ``ATTENTION_BLOCK`` unless ``attn_fn`` says
    otherwise (``banded_attention``: the einsum, for a CPU): window layers
    run the band's blocks only, 7 query heads read one key-value head where
    it lies. ``interpret=True`` interprets both kernels (this one and the
    grouped matmul), for a CPU."""

    cfg: SmallThinkerConfig = SmallThinkerConfig()
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
            x = pin_batch(embed[tokens].astype(cfg.dtype))
        layer_cls = remat_block(DecoderLayer, cfg.remat, static_argnums=())
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, attn_fn, cfg.window(i), bool(cfg.rope_layout[i]),
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
