"""The two kernel choices of the GLM-4.7-Flash cell, measured on the chip.

Attention at the cell's shape ([2, 4096, 20, 256] bf16, causal, forward and
forward + backward): ``ops/pallas_attn.py`` against jax's shipped TPU flash
attention (``jax.experimental.pallas.ops.tpu.flash_attention``), each checked
against float32 attention computed by query chunks at 'highest'. Grouped
matmul at the expert layer's shapes (a 32,768-row buffer of which an
eighth holds rows, 8 groups, 2,048 x 1,536 and back): ``ops/grouped_matmul.py``
(the Pallas ``megablox.gmm``) against ``jax.lax.ragged_dot``, each checked
against a loop over the groups. One JSON line per arm; the model calls the
faster arm that is correct, and PERF.md keeps both readings (ISSUE 27).

    chiprun -- python3 benchmarks/glm4_kernels.py [attention] [grouped]

Wall time of a jitted call ending in ``block_until_ready``, the median of
``REPS``. The attention calls take 4-55 ms; the grouped matmuls about a
millisecond, of which a few tenths are dispatch: read their arms against
each other, and the kernel's own time from the cell's trace
(``grouped_matmul_roofline_pct``).
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

REPS = 20
B, T, H, DH = 2, 4096, 20, 256
ROWS, GROUPS, D_MODEL, D_FF = 32768, 8, 2048, 1536


def timed(fn, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, 1e3 * statistics.median(times)


def rel_err(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def attention_reference(q, k, v, do, chunk=512):
    """float32 causal attention and its gradients, queries in chunks."""
    import jax
    import jax.numpy as jnp

    def attn(q, k, v):
        t, dh = q.shape[1], q.shape[-1]
        kpos = jnp.arange(t)

        def one(q_c, start):
            s = jnp.einsum("bqhd,bkhd->bhqk", q_c, k) / jnp.sqrt(
                jnp.float32(dh)
            )
            qpos = start + jnp.arange(chunk)
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

        chunks = q.reshape(q.shape[0], t // chunk, chunk, *q.shape[2:])
        outs = jax.lax.map(
            lambda a: jax.checkpoint(one)(a[0], a[1]),
            (chunks.swapaxes(0, 1), jnp.arange(0, t, chunk)),
        )
        return outs.swapaxes(0, 1).reshape(q.shape)

    with jax.default_matmul_precision("highest"):
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        out, vjp = jax.vjp(attn, *f32)
        return out, vjp(do.astype(jnp.float32))


def attention_arms():
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from pytorch_distributedtraining_tpu.ops.pallas_attn import flash_attention

    def ours(bq, bk):
        return lambda q, k, v: flash_attention(q, k, v, True, bq, bk, False)

    def shipped(block):
        sizes = None if block is None else jfa.BlockSizes(
            block_q=block, block_k_major=block, block_k=block, block_b=1,
            block_q_major_dkv=block, block_k_major_dkv=block,
            block_k_dkv=block, block_q_dkv=block,
            block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
        )

        def fn(q, k, v):
            bhtd = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
            out = jfa.flash_attention(
                bhtd(q), bhtd(k), bhtd(v), causal=True,
                sm_scale=1.0 / DH**0.5, block_sizes=sizes,
            )
            return out.transpose(0, 2, 1, 3)

        return fn

    def xla(q, k, v):
        from pytorch_distributedtraining_tpu.models.gpt2 import (
            default_attention,
        )

        return default_attention(q, k, v, causal=True)

    return {
        "ops.pallas_attn bq=bk=128": ours(128, 128),
        "ops.pallas_attn bq=bk=256": ours(256, 256),
        "ops.pallas_attn bq=256 bk=512": ours(256, 512),
        "ops.pallas_attn bq=bk=512": ours(512, 512),
        "jax flash_attention default blocks (128)": shipped(None),
        "jax flash_attention blocks 256": shipped(256),
        "jax flash_attention blocks 512": shipped(512),
        "xla default_attention (T x T scores)": xla,
    }


def run_attention():
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (
        jax.random.normal(kk, (B, T, H, DH), jnp.float32).astype(jnp.bfloat16)
        for kk in keys
    )
    ref_out, ref_grads = jax.jit(attention_reference)(q, k, v, do)
    # causal half: 2 matmuls forward, 5 in a flash backward, 2 * T^2/2 * dh each
    fwd_flops = 2 * 2 * B * H * T * T * DH / 2
    for name, fn in attention_arms().items():
        line = {"kernel": "attention", "arm": name, "shape": [B, T, H, DH]}
        try:
            fwd = jax.jit(fn)
            both = jax.jit(lambda q, k, v, do, fn=fn: jax.vjp(fn, q, k, v)[1](do))
            out, fwd_ms = timed(fwd, q, k, v)
            grads, both_ms = timed(both, q, k, v, do)
            line.update(
                fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
                fwd_tflops=fwd_flops / fwd_ms / 1e9,
                fwd_bwd_tflops=3.5 * fwd_flops / both_ms / 1e9,
                out_rel_err=rel_err(out, ref_out),
                grad_rel_err=[
                    rel_err(g, r) for g, r in zip(grads, ref_grads)
                ],
            )
        except Exception as e:  # noqa: BLE001 - an arm that cannot compile is a reading
            line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(line), flush=True)


def grouped_reference(x, w, sizes):
    """Rows of group g times w[g], by a loop over the groups, float32."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        rows = jnp.arange(x.shape[0])
        out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
        for g in range(w.shape[0]):
            mine = (rows >= starts[g]) & (rows < ends[g])
            out = out + jnp.where(
                mine[:, None],
                x.astype(jnp.float32) @ w[g].astype(jnp.float32), 0.0,
            )
        return out


def run_grouped():
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.ops.grouped_matmul import (
        grouped_matmul,
    )

    kx, kw, kd = jax.random.split(jax.random.PRNGKey(1), 3)
    # uneven on purpose: 4,096 rows in all, the fullest group 3 x the mean
    sizes = jnp.asarray([1536, 900, 640, 400, 300, 200, 100, 20], jnp.int32)
    valid = (jnp.arange(ROWS) < sizes.sum())[:, None]
    flops = 2 * int(sizes.sum()) * D_MODEL * D_FF
    for k_dim, n_dim in ((D_MODEL, D_FF), (D_FF, D_MODEL)):
        x = jnp.where(
            valid, jax.random.normal(kx, (ROWS, k_dim), jnp.float32), 0.0
        ).astype(jnp.bfloat16)
        w = (0.02 * jax.random.normal(kw, (GROUPS, k_dim, n_dim))).astype(
            jnp.bfloat16
        )
        dy = jnp.where(
            valid, jax.random.normal(kd, (ROWS, n_dim), jnp.float32), 0.0
        ).astype(jnp.bfloat16)
        ref = jax.jit(grouped_reference)(x, w, sizes)
        ref_dx, ref_dw = jax.jit(
            lambda x, w, dy: jax.vjp(
                lambda a, b: grouped_reference(a, b, sizes),
                x.astype(jnp.float32), w.astype(jnp.float32),
            )[1](dy.astype(jnp.float32))
        )(x, w, dy)
        arms = {
            "ops.grouped_matmul (megablox gmm)": grouped_matmul,
            "jax.lax.ragged_dot": lambda a, b, n: jax.lax.ragged_dot(
                a, b, n, preferred_element_type=jnp.float32
            ).astype(a.dtype),
        }
        for impl, fn in arms.items():
            line = {
                "kernel": "grouped_matmul", "arm": impl,
                "shape": [ROWS, k_dim, n_dim], "groups": GROUPS,
                "rows": int(sizes.sum()),
            }
            try:
                fwd = jax.jit(lambda x, w: fn(x, w, sizes))
                both = jax.jit(
                    lambda x, w, dy: jax.vjp(
                        lambda a, b: fn(a, b, sizes), x, w
                    )[1](dy)
                )
                out, fwd_ms = timed(fwd, x, w)
                (dx, dw), both_ms = timed(both, x, w, dy)
                keep = lambda a: jnp.where(valid, a, 0)  # noqa: E731
                line.update(
                    fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
                    fwd_tflops=flops / fwd_ms / 1e9,
                    fwd_bwd_tflops=3 * flops / both_ms / 1e9,
                    out_rel_err=rel_err(keep(out), ref),
                    dx_rel_err=rel_err(keep(dx), ref_dx),
                    dw_rel_err=rel_err(dw, ref_dw),
                )
            except Exception as e:  # noqa: BLE001
                line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
            print(json.dumps(line), flush=True)


def main(argv):
    import jax

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit("glm4_kernels measures on a TPU, found none")
    what = argv or ["attention", "grouped"]
    if "attention" in what:
        run_attention()
    if "grouped" in what:
        run_grouped()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
