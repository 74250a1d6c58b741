"""Ring attention: sequence/context parallelism over the "sp" mesh axis.

Long-context capability the reference lacks (`SURVEY.md` §5 "long-context:
absent") but a TPU-native framework treats as first-class: the sequence is
sharded over "sp"; each device computes blockwise (flash-style, online
softmax) attention for its query chunk while K/V chunks rotate around the
ring via ``ppermute`` — ICI-neighbor traffic only, overlapping compute with
transfer (Liu et al., Ring Attention; blockwise formulation from
Rabe & Staats / FlashAttention, see PAPERS.md).

Layout contract: q/k/v are [B, T, H, Dh] with T sharded over ``axis_name``
(global-view); :func:`make_ring_attn_fn` returns a drop-in ``attn_fn`` for
models/gpt2.py / models/vit.py. Accumulation is f32 regardless of input
dtype (bf16-safe).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import shard_map

_BIG_NEG = -1e30


def _block_update(carry, s, v):
    """Online-softmax accumulate one [.., Tq, Tk] logit block into carry."""
    o, l, m = carry  # [.., Tq, Dh], [.., Tq], [.., Tq]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])  # [.., Tq, Tk]
    l = l * corr + jnp.sum(p, axis=-1)
    o = o * corr[..., None] + p @ v
    return o, l, m_new


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True):
    """Per-shard ring attention; call inside ``shard_map``.

    q/k/v: [B, Tc, H, Dh] — the local sequence chunk. Returns [B, Tc, H, Dh].
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, tc, h, dh = q.shape
    scale = 1.0 / jnp.sqrt(dh)

    # [B, H, Tq, Dh] f32 work layout
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale
    qpos = idx * tc + jnp.arange(tc)  # global query positions

    def body(t, carry):
        o, l, m, kc, vc = carry
        kf = kc.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B,H,Tk,Dh]
        vf = vc.astype(jnp.float32).transpose(0, 2, 1, 3)
        s = qf @ kf.transpose(0, 1, 3, 2)  # [B,H,Tq,Tk]
        if causal:
            kchunk = (idx + t) % n
            kpos = kchunk * tc + jnp.arange(tc)
            mask = kpos[None, :] <= qpos[:, None]  # [Tq,Tk]
            s = jnp.where(mask, s, _BIG_NEG)
        o, l, m = _block_update((o, l, m), s, vf)
        # rotate K/V: device j's chunk moves to j-1, so local kv becomes
        # chunk (idx+t+1) — neighbor traffic only on the ICI ring
        perm = [(j, (j - 1) % n) for j in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return o, l, m, kc, vc

    # derive carry inits from qf so they carry the same varying-axes type
    # (vma) as the rotating k/v under jax>=0.9 shard_map
    o0 = qf * 0.0
    l0 = jnp.sum(o0, axis=-1)
    m0 = l0 + _BIG_NEG
    o, l, m, _, _ = jax.lax.fori_loop(0, n, body, (o0, l0, m0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q, k, v, *, axis_name: str = "sp", causal: bool = True,
    inner=None,
):
    """DeepSpeed-Ulysses-style SP: all-to-all seq<->heads, attend locally.

    Swaps the sequence shard for a head shard (one all-to-all), runs FULL
    -sequence attention on H/n heads, swaps back. Cheaper than ring when
    H divides nicely and the all-to-all fits ICI; exact same math.
    q/k/v: [B, Tc, H, Dh] local chunks inside ``shard_map``.
    """
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the '{axis_name}'"
            f" axis ({n}); use impl='ring' for head-count-agnostic SP"
        )
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2,
        concat_axis=1, tiled=True,
    )  # [B, Tc, H, D] -> [B, T, H/n, D]
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    if inner is None:
        from ..models.gpt2 import default_attention as inner
    out = inner(qh, kh, vh, causal=causal)
    return jax.lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def _seq_specs(mesh: Mesh, axis_name: str) -> P:
    """[B, T, H, Dh] spec: batch over data axes, T over the sp axis."""
    batch = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    return P(batch or None, axis_name, None, None)


def make_ring_attn_fn(
    mesh: Mesh, *, axis_name: str = "sp", impl: str = "ring"
):
    """Drop-in ``attn_fn`` for the model zoo: shard_map'd SP attention.

    ``impl``: "ring" (ppermute ring) or "ulysses" (all-to-all head swap).
    """
    fn = ring_attention if impl == "ring" else ulysses_attention
    spec = _seq_specs(mesh, axis_name)

    def attn_fn(q, k, v, *, causal: bool = True):
        if mesh.shape.get(axis_name, 1) <= 1:
            from ..models.gpt2 import default_attention

            return default_attention(q, k, v, causal=causal)
        return shard_map(
            partial(fn, axis_name=axis_name, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)

    return attn_fn
