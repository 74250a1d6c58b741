"""The gated short convolution and the attention core of
``lfm2-8b-a1b.train-4k`` at the cell's shapes, arm by arm, on the chip.

"conv": one conv layer's ``C * causal_conv(B * X)`` on ``bcx [2, 4096, 3 x
2,048]`` bf16 with taps ``[2,048, 3]``, forward and forward + backward (the
gradient of ``bcx`` and of the taps), three ways, each a function of ``bcx``
as the in-projection wrote it:

- ``ops/short_conv.short_conv_kernel``, the Pallas pair the model runs;
- ``ops/short_conv.short_conv_jnp``, three shifted products, differentiated
  by XLA;
- XLA's own depthwise convolution (``lax.conv_general_dilated``,
  ``feature_group_count = D``), the gate around it.

Each is held to the float32 form at 'highest' and prints one JSON line with
its milliseconds and the bytes a layer must move at 819 GB/s.

"attention": the split-head kernels at heads of 64, 32 query heads on 8
key-value heads, causal at 4,096 (blocks of 512, as
``models/smallthinker.attention_core`` places them), forward and the three
gradients against the masked float32 einsum on the same bf16 inputs.

    python3 benchmarks/short_conv_bench.py [conv] [attention]  # one chip

A call's time is the wall time of ``REPS`` calls enqueued back to back and
waited for once, over ``REPS`` (``attn_bench.py``'s pace)."""

from __future__ import annotations

import contextlib
import json
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)
from attn_bench import paced

B, T, D, TAPS = 2, 4096, 2048, 3
HEADS, KV_HEADS, HEAD = 32, 8, 64
HBM_BYTES_PER_S = 819e9


def rel(got, want):
    import jax.numpy as jnp

    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def xla_conv(bcx, w):
    """``lax.conv_general_dilated`` over time, one group a channel, float32
    operands (its backward takes no mixed dtypes), the result cast back."""
    import jax
    import jax.numpy as jnp

    d = w.shape[0]
    b, c, x = (a.astype(jnp.float32) for a in (
        bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    ))
    y = jax.lax.conv_general_dilated(
        b * x, w.T[:, None, :].astype(jnp.float32), window_strides=(1,),
        padding=[(w.shape[1] - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=d,
    )
    return (c * y).astype(bcx.dtype)


def conv_arms():
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.ops import short_conv as sc

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcx = jax.random.normal(keys[0], (B, T, 3 * D), jnp.bfloat16)
    w = (0.5 * jax.random.normal(keys[1], (D, TAPS))).astype(jnp.bfloat16)
    g = jax.random.normal(keys[2], (B, T, D), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(sc.short_conv_jnp)(bcx.astype(jnp.float32),
                                          w.astype(jnp.float32))
        want_grads = jax.jit(jax.grad(
            lambda x, w: jnp.sum(sc.short_conv_jnp(x, w) * g), (0, 1)
        ))(bcx.astype(jnp.float32), w.astype(jnp.float32))
    elements = B * T * D
    moved = {"forward": 8 * elements, "forward+backward": 22 * elements}
    for name, fn in (
        ("kernel", sc.short_conv_kernel), ("jnp", sc.short_conv_jnp),
        ("xla_conv", xla_conv),
    ):
        fwd = jax.jit(fn)
        both = jax.jit(jax.grad(
            lambda x, w, fn=fn: jnp.sum(fn(x, w).astype(jnp.float32) * g),
            (0, 1),
        ))
        out, fwd_ms = paced(fwd, bcx, w)
        grads, both_ms = paced(both, bcx, w)
        print(json.dumps({
            "arm": f"conv: {name}", "shape": [B, T, 3 * D],
            "forward_ms": fwd_ms, "forward_backward_ms": both_ms,
            "least_ms": {
                k: 1e3 * v / HBM_BYTES_PER_S for k, v in moved.items()
            },
            "rel_out": rel(out, want),
            "rel_d_bcx": rel(grads[0], want_grads[0]),
            "rel_d_taps": rel(grads[1], want_grads[1]),
        }), flush=True)


def attention_arms():
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.smallthinker import (
        attention_core, banded_attention,
    )

    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(keys[0], (B, T, HEADS, HEAD), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, T, KV_HEADS, HEAD), jnp.bfloat16)
            for key in keys[1:3])
    mix = jax.random.normal(keys[3], q.shape, jnp.float32)
    kernel = attention_core(None, False, T)
    arms = {
        "kernel": lambda q, k, v: kernel(q, k, v, window=None),
        "einsum_f32": lambda q, k, v: banded_attention(
            *(a.astype(jnp.float32) for a in (q, k, v)), window=None
        ),
    }
    results = {}
    for name, fn in arms.items():
        fwd = jax.jit(fn)
        both = jax.jit(jax.grad(
            lambda q, k, v, fn=fn: jnp.sum(
                fn(q, k, v).astype(jnp.float32) * mix
            ), (0, 1, 2),
        ))
        # the einsum at 'highest'; the kernel as the model runs it (Mosaic
        # takes no float32 contract precision on bf16 operands)
        with (
            jax.default_matmul_precision("highest") if name == "einsum_f32"
            else contextlib.nullcontext()
        ):
            out, fwd_ms = paced(fwd, q, k, v)
            grads, both_ms = paced(both, q, k, v)
        results[name] = (out, grads, fwd_ms, both_ms)
    want, want_grads, *_ = results["einsum_f32"]
    for name, (out, grads, fwd_ms, both_ms) in results.items():
        print(json.dumps({
            "arm": f"attention: {name}", "q": [B, T, HEADS, HEAD],
            "kv_heads": KV_HEADS, "forward_ms": fwd_ms,
            "forward_backward_ms": both_ms, "rel_out": rel(out, want),
            **{f"rel_d{x}": rel(g, w)
               for x, g, w in zip("qkv", grads, want_grads)},
        }), flush=True)


def main(argv):
    from pytorch_distributedtraining_tpu import runtime

    runtime.initialize()
    wanted = set(argv) or {"conv", "attention"}
    if "conv" in wanted:
        conv_arms()
    if "attention" in wanted:
        attention_arms()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
