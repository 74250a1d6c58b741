"""On-chip validation of `parallel.tune_multi_step_k` on the flagship step.

The bench_scan_k* arms measure the scan pattern in isolation; this stage
drives the USER-FACING tuner API end-to-end on the real backend and
prints its verdict — on a dispatch-bound host the best k should be >1; a
backend on which the scan loop is slow resolves to k=1 (that resolution is
the feature: the backend is measured, not guessed about).

One JSON line: {"best_k": ..., "rates_steps_per_sec": {k: steps/sec}}.
Env: GRAFT_BENCH_PLATFORM=cpu self-test (tiny model), GRAFT_TUNE_KS.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)
from _roofline import guard

CPU_SELF_TEST = os.environ.get("GRAFT_BENCH_PLATFORM") == "cpu"


def main() -> None:
    from pytorch_distributedtraining_tpu.runtime.dist import (
        force_platform_from_env,
    )

    force_platform_from_env("GRAFT_BENCH_PLATFORM")
    import jax

    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from pytorch_distributedtraining_tpu.parallel import tune_multi_step_k

    from _flagship import make_flagship_step

    ks_raw = os.environ.get(
        "GRAFT_TUNE_KS", "1,2" if CPU_SELF_TEST else "1,5,10"
    )
    ks = tuple(int(t) for t in ks_raw.split(",") if t.strip())
    steps_per_arm = 4 if CPU_SELF_TEST else 20

    mesh, state, step, batch, batch_n = make_flagship_step(CPU_SELF_TEST)

    t0 = time.perf_counter()
    best_k, rates, _ = tune_multi_step_k(
        step, state, batch, ks=ks, steps_per_arm=steps_per_arm
    )
    if not CPU_SELF_TEST:
        # same flagship bound as bench.py: img/s <= 1 PFLOP/s / 21 GFLOP
        guard(
            f"tune_k={max(rates, key=rates.get)}",
            max(rates.values()) * batch_n,
            "images/sec", 1000e12 / 21e9,
            "1 PFLOP/s / 21 GFLOP per image",
        )
    print(json.dumps({
        "best_k": best_k,
        "rates_steps_per_sec": {str(k): round(r, 2) for k, r in rates.items()},
        "tuning_wall_s": round(time.perf_counter() - t0, 1),
        "batch": batch_n,
    }), flush=True)


if __name__ == "__main__":
    main()
