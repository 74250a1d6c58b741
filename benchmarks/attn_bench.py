"""Pallas flash attention vs XLA attention on hardware.

Measures forward and forward+backward wall time for the framework's Pallas
flash-attention kernels (`ops/pallas_attn.py`) against plain XLA attention
(`models/gpt2.default_attention`) at GPT-2-class shapes, bf16, causal.
Flash's win is O(T) HBM traffic (no [T,T] logits round trip), so the gap
should widen with T. One JSON line per (T, impl, pass).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import _bootstrap  # noqa: F401  (repo root on sys.path)
from _roofline import guard, verify_finite


def main():
    import jax
    import jax.numpy as jnp

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
    from pytorch_distributedtraining_tpu.ops.pallas_attn import flash_attention

    B, H, D = 8, 12, 64
    STEPS = int(os.environ.get("GRAFT_ATTN_STEPS", "50"))
    platform = jax.devices()[0].platform
    if platform not in ("cpu", "tpu"):
        raise SystemExit(f"attn_bench supports cpu/tpu, got {platform}")
    interpret = platform != "tpu"

    def time_fn(fn, q, k, v):
        # vary q per rep INSIDE one jitted program: every timed call is
        # distinct work — at one dispatch per rep, like the real thing
        wrapped = jax.jit(lambda e, q_, k_, v_: fn(q_ + e, k_, v_))
        eps = [
            jax.device_put(jnp.asarray((i + 1) * 1e-6, q.dtype))
            for i in range(STEPS)
        ]
        out = wrapped(jnp.asarray(0, q.dtype), q, k, v)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for i in range(STEPS):
            out = wrapped(eps[i], q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / STEPS
        verify_finite(
            float(jnp.asarray(jax.tree.leaves(out)[0]).ravel()[0]),
            "attention output",
        )
        return dt

    raw = os.environ.get("GRAFT_ATTN_SIZES", "512,1024,2048,4096")
    try:
        sizes = tuple(int(t) for t in raw.split(",") if t.strip())
    except ValueError:
        raise SystemExit(
            f"GRAFT_ATTN_SIZES must be comma-separated ints, got {raw!r}"
        )
    if not sizes:
        raise SystemExit("GRAFT_ATTN_SIZES parsed to no sizes")
    for T in sizes:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(
                rng.normal(size=(B, T, H, D)).astype(np.float32),
                jnp.bfloat16,
            )
            for _ in range(3)
        )

        def xla_loss(q, k, v):
            return jnp.sum(default_attention(q, k, v, causal=True)
                           .astype(jnp.float32))

        def flash_loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, 128, 128, interpret)
                .astype(jnp.float32)
            )

        arms = {
            ("xla", "fwd"): jax.jit(xla_loss),
            ("flash", "fwd"): jax.jit(flash_loss),
            ("xla", "fwd+bwd"): jax.jit(jax.grad(xla_loss, argnums=(0, 1, 2))),
            ("flash", "fwd+bwd"): jax.jit(
                jax.grad(flash_loss, argnums=(0, 1, 2))
            ),
        }

        # correctness on this hardware first: fwd and
        # grad outputs of the Pallas kernels vs XLA attention in bf16 (grad
        # comparison reuses the timing arms' compiled programs). Gate hard:
        # timing a wrong-math kernel must fail the bench, not decorate it.
        o_xla = jax.jit(
            lambda q, k, v: default_attention(q, k, v, causal=True)
        )(q, k, v).astype(jnp.float32)
        o_fl = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, True, 128, 128, interpret)
        )(q, k, v).astype(jnp.float32)
        g_xla = arms[("xla", "fwd+bwd")](q, k, v)
        g_fl = arms[("flash", "fwd+bwd")](q, k, v)
        gerr = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(g_xla, g_fl)
        )
        ferr = float(jnp.max(jnp.abs(o_xla - o_fl)))
        print(json.dumps({
            "T": T, "impl": "flash", "pass": "correctness",
            "max_abs_err_fwd": round(ferr, 6),
            "max_abs_err_grad": round(gerr, 6),
        }), flush=True)
        # bf16 rounding at these magnitudes is ~1e-2; a real kernel bug is
        # orders of magnitude above these bounds
        if ferr > 0.1 or gerr > 0.3:
            raise SystemExit(
                f"flash-vs-XLA mismatch at T={T}: fwd {ferr}, grad {gerr}"
            )
        for (impl, passes), fn in arms.items():
            sec = time_fn(fn, q, k, v)
            # attention flops: 2 matmuls * 2 flops * B*H*T^2*D (causal ~1/2)
            flops = 2 * 2 * B * H * T * T * D * 0.5
            if passes == "fwd+bwd":
                # XLA bwd reuses stored probs (~2x fwd extra); flash bwd
                # recomputes the forward in-kernel (~2.5x fwd extra)
                flops *= 3.0 if impl == "xla" else 3.5
            tflops = flops / sec / 1e12
            # no v5e-class chip reaches 1 PFLOP/s bf16 (best sustained
            # measurement here: 649 TFLOP/s) — a value
            # above it means the timing loop broke, not a fast kernel
            guard(
                f"{impl}/{passes} T={T}", tflops, "TFLOP/s", 1000.0,
                "1 PFLOP/s chip compute bound",
            )
            print(json.dumps({
                "T": T, "impl": impl, "pass": passes,
                "ms": round(sec * 1e3, 3),
                "tflops": round(tflops, 2),
            }), flush=True)


if __name__ == "__main__":
    main()
