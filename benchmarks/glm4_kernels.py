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

Routing (ISSUE 33) at the two sparse cells' expert layers ([16384, 2560] bf16
tokens at 6 picks, [8192, 2048] at 4; 8 of 64 experts held) with none, an
eighth and all of the N x k assignments landing: the gathers over the whole
worst-case buffer that ``models/held_experts.py`` made until PR 32 against
its row loops over the landed rows, the two primitives alone at five tiles,
and a kernel of one row DMA a landed row. Dispatch + combine with nothing between them, forward and
forward + backward (tokens' and weights' gradients), and the index work
alone.

    chiprun -- python3 benchmarks/glm4_kernels.py [attention] [grouped] [routing]

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
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


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


ROUTING_SHAPES = ((16384, 6, 2560), (8192, 4, 2048))  # N, k, D of a layer
EXPERTS, HELD = 64, 8


def whole_buffer_route(tokens, weights, group):
    """Dispatch and combine as they were until PR 32: every one of the N x k
    rows gathered out and gathered back (the gradients gathers too, by the
    inverse permutation), masked, weighed in a float32 [N, k, D] array and
    summed, whatever landed."""
    import jax
    import jax.numpy as jnp

    (n, d), k = tokens.shape, weights.shape[1]

    @jax.custom_vjp
    def spread_rows(tokens, order, slot):
        return tokens[order // k]

    def spread_bwd(slot, g):
        per_token = g[slot].reshape(n, k, d).astype(jnp.float32)
        return jnp.sum(per_token, 1).astype(g.dtype), None, None

    spread_rows.defvjp(
        lambda t, order, slot: (spread_rows(t, order, slot), slot), spread_bwd
    )

    @jax.custom_vjp
    def collect_rows(rows, slot, order):
        return rows[slot]

    collect_rows.defvjp(
        lambda rows, slot, order: (rows[slot], order),
        lambda order, g: (g[order], None, None),
    )

    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    slot = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32)
    )
    here = group[order] < HELD
    xs = jnp.where(here[:, None], spread_rows(tokens, order, slot), 0)
    mine = (group < HELD).reshape(n, k)
    parts = jnp.where(
        mine[..., None], collect_rows(xs, slot, order).reshape(n, k, d), 0
    ).astype(jnp.float32)
    return jnp.sum(weights[..., None] * parts, 1).astype(tokens.dtype)


def landed_rows_route(tokens, weights, group):
    """Dispatch and combine as ``models/held_experts.py`` makes them: row
    loops over the rows that landed, with their hand-written backward."""
    from pytorch_distributedtraining_tpu.models import held_experts as he

    landed = he.find_landed(group, HELD, weights.shape[1])
    xs = he.spread_rows(tokens, landed)
    return he.weighted_sum(xs, weights, landed, tokens.dtype)


def row_dma_spread(src, token, count, tile=512):
    """``out[r] = src[token[r]]`` for ``r < count``, one HBM -> HBM row DMA
    each, the tile's tokens in SMEM: the kernel the issue proposed for
    dispatch's forward."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = token.shape[0], src.shape[1]

    def kernel(count_ref, token_ref, src_ref, out_ref, sem):
        base = pl.program_id(0) * tile

        def copy(r):
            return pltpu.make_async_copy(
                src_ref.at[pl.ds(token_ref[r], 1)],
                out_ref.at[pl.ds(base + r, 1)], sem,
            )

        @pl.when(base < count_ref[0])
        def _():
            jax.lax.fori_loop(0, tile, lambda r, c: copy(r).start() or c, 0)
            jax.lax.fori_loop(0, tile, lambda r, c: copy(r).wait() or c, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(m // tile,),
            in_specs=[
                pl.BlockSpec((tile,), lambda i, c: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), src.dtype),
    )(count.reshape(1), token, src)


def routing_picks(key, n, k, share):
    """Picks [N, k] over the published experts of which ``share`` land on
    the held ones (0 .. HELD - 1): none, about an eighth (k distinct of all,
    evenly), or all (k distinct of the held)."""
    import jax
    import jax.numpy as jnp

    among = {"none": (HELD, EXPERTS), "eighth": (0, EXPERTS),
             "all": (0, HELD)}[share]
    draw = jax.random.uniform(key, (n, among[1] - among[0]))
    return among[0] + jnp.argsort(draw, axis=-1)[:, :k].astype(jnp.int32)


def run_routing():
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models import held_experts as he

    arms = {
        "whole buffer (until PR 32)": whole_buffer_route,
        f"landed rows, tile {he.ROW_TILE} (models/held_experts.py)":
            landed_rows_route,
    }
    _, floor_ms = timed(jax.jit(lambda x: x + 1), jnp.zeros((8, 128)))
    print(json.dumps({
        "kernel": "routing", "fwd_ms": floor_ms,
        "arm": "a jitted call that does nothing (the floor under every line)",
    }), flush=True)
    for n, k, d in ROUTING_SHAPES:
        kt, kw, kg, ks = jax.random.split(jax.random.PRNGKey(n), 4)
        tokens = jax.random.normal(kt, (n, d), jnp.float32).astype(jnp.bfloat16)
        weights = jax.nn.softmax(jax.random.normal(kw, (n, k)), -1)
        g = jax.random.normal(kg, (n, d), jnp.float32).astype(jnp.bfloat16)
        local = jnp.minimum(jnp.arange(EXPERTS), HELD).astype(jnp.int32)
        for share in ("none", "eighth", "all"):
            group = local[routing_picks(ks, n, k, share).reshape(-1)]
            landed, index_ms = timed(
                jax.jit(lambda group: he.find_landed(group, HELD, k)), group
            )
            base = {"kernel": "routing", "shape": [n, k, d], "lands": share,
                    "landed": int(landed.count)}
            ids = lambda: jnp.arange(n * k, dtype=jnp.int32)  # noqa: E731
            pieces = {  # ways to apply or invert a permutation of N x k
                "sort_ms": lambda at: jax.lax.sort(
                    (at.order, ids()), num_keys=1),
                "scatter_ms": lambda at: jnp.zeros_like(at.order).at[
                    at.order].set(ids()),
                "gather_ms": lambda at: at.slot[at.order],
            }
            print(json.dumps({
                **base, "arm": "find_landed (the index work)",
                "fwd_ms": index_ms, **{
                    name: timed(jax.jit(f), landed)[1]
                    for name, f in pieces.items()
                },
            }), flush=True)
            want = None
            for name, fn in arms.items():
                line = {**base, "arm": name}
                try:
                    out, line["fwd_ms"] = timed(
                        jax.jit(fn), tokens, weights, group
                    )
                    grads, line["fwd_bwd_ms"] = timed(jax.jit(
                        lambda t, w, group, g, fn=fn: jax.vjp(
                            lambda a, b: fn(a, b, group), t, w
                        )[1](g)
                    ), tokens, weights, group, g)
                    if want is None:
                        want = (out, grads)
                    else:
                        line["out_rel_err"] = rel_err(out, want[0])
                        line["grad_rel_err"] = [
                            rel_err(a, b) for a, b in zip(grads, want[1])
                        ]
                except Exception as e:  # noqa: BLE001
                    line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                print(json.dumps(line), flush=True)
            # the two primitives alone, by the rows a loop step moves
            for tile in (128, 256, 512, 1024, 2048):
                rows, spread_ms = timed(jax.jit(
                    lambda t, at, tile=tile: he.spread(t, at, tile=tile)
                ), tokens, landed)
                _, sum_ms = timed(jax.jit(
                    lambda r, w, at, tile=tile: he.gather_sum(
                        r, at, weight=w, tile=tile
                    )
                ), rows, weights, landed)
                print(json.dumps({
                    **base, "arm": f"spread | gather_sum alone, tile {tile}",
                    "spread_ms": spread_ms, "gather_sum_ms": sum_ms,
                }), flush=True)
            if share != "eighth":
                continue
            line = {**base, "arm": "spread as one row DMA a landed row"}
            try:
                ours = he.spread(tokens, landed)
                live = (jnp.arange(n * k) < landed.count)[:, None]
                rows, line["spread_ms"] = timed(
                    jax.jit(row_dma_spread), tokens, landed.token, landed.count
                )
                line["out_rel_err"] = rel_err(
                    jnp.where(live, rows, 0), jnp.where(live, ours, 0)
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
    what = argv or ["attention", "grouped", "routing"]
    if "attention" in what:
        run_attention()
    if "grouped" in what:
        run_grouped()
    if "routing" in what:
        run_routing()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
