"""GPT-2's attention core at the two cells' shapes, arm by arm, on the chip.

What runs between ``c_attn`` and ``c_proj`` in ``gpt2-125m.train``
(``qkv [12, 1024, 3 * 12 * 64]``) and on one chip of ``gpt2-xl.zero3-4chip``
(``[4, 1024, 3 * 25 * 64]``), bf16, causal, forward and forward + backward.
Every arm is a function of ``qkv`` as the projection wrote it, so the split,
the transposes and the padded tiles an arm needs are inside its time:

- XLA's einsums (``models/gpt2.default_attention``: T x T scores in HBM);
- ``ops/pallas_attn.flash_attention`` over split heads, ``[B, H, T, 64]``
  operands, at blocks of 128 / 256 / 512;
- ``ops/pallas_attn.causal_attention_qkv``, what the default model runs:
  two heads to a 128-lane block, read where ``c_attn`` wrote them (12 heads)
  or copied behind a head of zeros first (25), at several blocks and at
  the blocks it reads from T itself;
- jax's shipped ``pallas.ops.tpu.flash_attention``.

Each is held to float32 attention at 'highest' (``glm4_kernels``' reference)
and prints one JSON line. ISSUE 30's decision gate reads these lines.

    chiprun -- python3 benchmarks/attn_bench.py

A call's time is the wall time of ``REPS`` calls enqueued back to back and
waited for once, over ``REPS``: the calls take 0.5-6 ms, so the host stays
ahead and the device's pace is what is read.
"""

from __future__ import annotations

import json
import sys
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)
from glm4_kernels import attention_reference, rel_err

REPS = 40
SHAPES = {  # cell -> (sequences a chip, T, heads, head size)
    "gpt2-125m.train": (12, 1024, 12, 64),
    "gpt2-xl.zero3-4chip": (4, 1024, 25, 64),
}


def paced(fn, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - t0) / REPS


def arms(heads, dh):
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
    from pytorch_distributedtraining_tpu.ops import pallas_attn as pa

    def split(qkv):
        b, t, _ = qkv.shape
        return (a.reshape(b, t, heads, dh) for a in jnp.split(qkv, 3, -1))

    def flat(out):
        return out.reshape(*out.shape[:2], heads * dh)

    def shipped(block):
        sizes = None if block is None else jfa.BlockSizes(
            block_q=block, block_k_major=block, block_k=block, block_b=1,
            block_q_major_dkv=block, block_k_major_dkv=block,
            block_k_dkv=block, block_q_dkv=block,
            block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
        )

        def fn(qkv):
            q, k, v = (a.transpose(0, 2, 1, 3) for a in split(qkv))
            out = jfa.flash_attention(
                q, k, v, causal=True, sm_scale=dh**-0.5, block_sizes=sizes
            )
            return flat(out.transpose(0, 2, 1, 3))

        return fn

    out = {
        "xla default_attention (T x T scores)": lambda qkv: flat(
            default_attention(*split(qkv), causal=True)
        ),
    }
    for bq, bk in ((128, 128), (256, 256), (512, 512), (1024, 1024)):
        out[f"flash_attention [B,H,T,{dh}] bq={bq} bk={bk}"] = (
            lambda qkv, bq=bq, bk=bk: flat(
                pa.flash_attention(*split(qkv), True, bq, bk, False)
            )
        )
    how = "in place" if pa.packs(heads, dh) else "behind a head of zeros"
    for blocks in (
        (128, 128), (256, 256), (512, 512), (256, 512), (512, 256),
        (1024, 1024), None,
    ):
        at = "its own blocks" if blocks is None else "bq=%d bk=%d" % blocks
        out[f"causal_attention_qkv, heads packed {how}, {at}"] = (
            lambda qkv, blocks=blocks: flat(
                pa.causal_attention_qkv(qkv, heads, blocks=blocks)
            )
        )
    out["jax flash_attention default blocks (128)"] = shipped(None)
    out["jax flash_attention blocks 512"] = shipped(512)
    return out


def run(cell):
    import jax
    import jax.numpy as jnp

    b, t, heads, dh = SHAPES[cell]
    kq, kd = jax.random.split(jax.random.PRNGKey(0))
    qkv = jax.random.normal(kq, (b, t, 3 * heads * dh), jnp.float32).astype(
        jnp.bfloat16
    )
    do = jax.random.normal(kd, (b, t, heads * dh), jnp.float32).astype(
        jnp.bfloat16
    )
    q, k, v = (a.reshape(b, t, heads, dh) for a in jnp.split(qkv, 3, -1))
    ref_out, ref_grads = jax.jit(attention_reference)(
        q, k, v, do.reshape(b, t, heads, dh)
    )
    ref_out = ref_out.reshape(b, t, heads * dh)
    ref_grad = jnp.concatenate(
        [g.reshape(b, t, heads * dh) for g in ref_grads], -1
    )
    scores = b * heads * t * t  # the full square, as PERF.md counts them
    for name, fn in arms(heads, dh).items():
        line = {"cell": cell, "arm": name, "shape": [b, t, heads, dh]}
        try:
            fwd = jax.jit(fn)
            both = jax.jit(lambda qkv, do, fn=fn: jax.vjp(fn, qkv)[1](do)[0])
            out, fwd_ms = paced(fwd, qkv)
            grad, both_ms = paced(both, qkv, do)
            line.update(
                fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
                fwd_ps_per_score=1e9 * fwd_ms / scores,
                bwd_ps_per_score=1e9 * (both_ms - fwd_ms) / scores,
                out_rel_err=rel_err(out, ref_out),
                grad_rel_err=rel_err(grad, ref_grad),
            )
        except Exception as e:  # noqa: BLE001 - an arm that cannot compile is a reading
            line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(line), flush=True)


def main(argv):
    import jax

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit("attn_bench measures on a TPU, found none")
    for cell in argv or SHAPES:
        run(cell)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
