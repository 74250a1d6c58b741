"""Compile-time benchmark: cold vs persistent-cache vs scan-over-layers.

ISSUE 3's tentpole claims scan-over-layers cuts COLD-compile time (XLA
traces/compiles one repeated block instead of N) and that the persistent
compile cache turns a recompile into a disk deserialize. This bench
measures all three arms on the same train-grade function (SwinIR loss +
grad, the headline model):

    loop_cold    unrolled RSTB layers, persistent cache off
    loop_cached  same program, cache populated -> deserialize
    scan_cold    nn.scan'd RSTB pairs, persistent cache off
    scan_cached  same, cache populated

Between arms the in-process jit/tracing caches are cleared
(``jax.clear_caches()``) so "cached" isolates the PERSISTENT cache path —
what a fresh process would pay. Cold arms compile with persistence off, so
they are cold whatever earlier runs left in the shared cache directory
(``runtime.cache.cache_dir()``); cached arms compile once untimed to
populate it, then time the reload.

Prints one JSON line per arm {"arm", "compile_s", "cache_entries"} and a
final {"summary": ...} with the scan-vs-loop cold speedup. Runs on any
backend (compile time is host work; CPU numbers are representative).

``GRAFT_COMPILE_BENCH_DEPTH`` (per-RSTB layers, default 6),
``_BLOCKS`` (RSTBs, default 2), ``_DIM`` (embed, default 60),
``_BATCH`` / ``_PATCH`` resize the program.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

DEPTH = int(os.environ.get("GRAFT_COMPILE_BENCH_DEPTH", "6"))
BLOCKS = int(os.environ.get("GRAFT_COMPILE_BENCH_BLOCKS", "2"))
DIM = int(os.environ.get("GRAFT_COMPILE_BENCH_DIM", "60"))
BATCH = int(os.environ.get("GRAFT_COMPILE_BENCH_BATCH", "2"))
PATCH = int(os.environ.get("GRAFT_COMPILE_BENCH_PATCH", "32"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache as cc

    from pytorch_distributedtraining_tpu.models.swinir import SwinIR
    from pytorch_distributedtraining_tpu.runtime.cache import (
        cache_entry_count,
        enable_compile_cache,
    )

    heads = max(1, DIM // 10)
    if DIM % heads:
        raise SystemExit(f"DIM={DIM} not divisible by heads={heads}")

    def build(scan_layers: bool) -> SwinIR:
        return SwinIR(
            img_size=PATCH, window_size=8,
            depths=(DEPTH,) * BLOCKS, embed_dim=DIM,
            num_heads=(heads,) * BLOCKS, mlp_ratio=2.0,
            scan_layers=scan_layers,
        )

    rng = np.random.default_rng(0)
    lr_img = jnp.asarray(
        rng.random((BATCH, PATCH, PATCH, 3), dtype=np.float32)
    )
    hr_img = jnp.asarray(
        rng.random((BATCH, 2 * PATCH, 2 * PATCH, 3), dtype=np.float32)
    )

    cdir = enable_compile_cache()
    if cdir is None:
        raise SystemExit("compile_bench needs the persistent compile cache")
    # even tiny programs must land in the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def compile_once(model, params, *, persistent: bool) -> float:
        """Seconds to AOT-compile loss+grad; in-process caches cleared
        first so the persistent tier is the only reuse path (what a fresh
        process would see)."""
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", persistent)
        cc.reset_cache()  # the cache module latches on/off at first use

        def loss_fn(p):
            out = model.apply({"params": p}, lr_img)
            return jnp.mean((out - hr_img) ** 2)

        t0 = time.perf_counter()
        jax.jit(jax.value_and_grad(loss_fn)).lower(params).compile()
        return time.perf_counter() - t0

    rows = []
    for kind, scan in (("loop", False), ("scan", True)):
        model = build(scan)
        params = model.init(jax.random.PRNGKey(0), lr_img)["params"]
        cold = compile_once(model, params, persistent=False)
        compile_once(model, params, persistent=True)  # populate, untimed
        cached = compile_once(model, params, persistent=True)
        for arm, dt in ((f"{kind}_cold", cold), (f"{kind}_cached", cached)):
            rows.append(
                {"arm": arm, "compile_s": round(dt, 3),
                 "cache_entries": cache_entry_count(cdir)}
            )
            print(json.dumps(rows[-1]), flush=True)

    by_arm = {r["arm"]: r["compile_s"] for r in rows}
    print(json.dumps({
        "summary": "compile_bench",
        "depth": DEPTH, "blocks": BLOCKS, "dim": DIM,
        "loop_cold_s": by_arm["loop_cold"],
        "scan_cold_s": by_arm["scan_cold"],
        "scan_cold_speedup": round(
            by_arm["loop_cold"] / max(by_arm["scan_cold"], 1e-9), 3
        ),
        "loop_cache_speedup": round(
            by_arm["loop_cold"] / max(by_arm["loop_cached"], 1e-9), 3
        ),
        "platform": jax.devices()[0].platform,
    }), flush=True)


if __name__ == "__main__":
    main()
