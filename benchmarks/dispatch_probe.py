"""Micro-probe: per-dispatch cost vs per-step compute through the link.

Round-4 anomaly: a 200-step on-device `lax.scan` of the
flagship step replayed ~90x SLOWER than 200 host dispatches of the same
body, while the host loop itself is dispatch-bound (~1.5 ms/step on a
1-core VM against ~0.8 ms of compute). This probe separates the candidate
costs with three trivial programs, so the numbers are free of model
effects:

1. ``noop xN``    — N dispatches of ``x+1`` on a scalar: pure per-call
   cost (host dispatch + link round-trip amortization).
2. ``scan(N)``    — ONE dispatch of an N-length scalar ``lax.scan``:
   per-call cost paid once + on-device loop rate.
3. ``donate xN``  — N dispatches donating a ~12 MB buffer (the train
   state's size class): per-call cost when buffers are donated.

Each arm runs twice (the second run shows warm steady-state; the first
includes program-load).  Prints one JSON line per arm.

Env: GRAFT_BENCH_PLATFORM=cpu for a self-test; GRAFT_PROBE_N to resize.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

N = max(10, int(os.environ.get("GRAFT_PROBE_N", "200")))


def main() -> None:
    from pytorch_distributedtraining_tpu.runtime.dist import (
        force_platform_from_env,
    )

    force_platform_from_env("GRAFT_BENCH_PLATFORM")
    import numpy as np
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"# platform={dev.platform} kind={dev.device_kind}", flush=True)

    def emit(arm, dt1, dt2, per_what):
        print(
            json.dumps(
                {
                    "arm": arm,
                    "n": N,
                    "run1_ms": round(dt1 * 1e3, 3),
                    "run2_ms": round(dt2 * 1e3, 3),
                    "per_call_us_warm": round(dt2 * 1e6 / N, 2),
                    "unit": per_what,
                }
            ),
            flush=True,
        )

    # -- 1: N dispatches of a scalar no-op --------------------------------
    @jax.jit
    def bump(x):
        return x + 1.0

    x = jax.device_put(jnp.float32(0.0), dev)
    x = bump(x)
    jax.block_until_ready(x)  # compile

    def run_bump():
        t0 = time.perf_counter()
        y = x
        for _ in range(N):
            y = bump(y)
        jax.block_until_ready(y)
        return time.perf_counter() - t0

    emit("noop_dispatch", run_bump(), run_bump(), "us/dispatch")

    # -- 2: one dispatch of an N-length scalar scan ------------------------
    @jax.jit
    def scan_bump(x):
        return jax.lax.scan(lambda c, _: (c + 1.0, ()), x, None, length=N)[0]

    y = scan_bump(x)
    jax.block_until_ready(y)  # compile

    def run_scan():
        t0 = time.perf_counter()
        y = scan_bump(x)
        jax.block_until_ready(y)
        return time.perf_counter() - t0

    emit("scalar_scan_1_dispatch", run_scan(), run_scan(), "us/iteration")

    # -- 3: N dispatches donating a train-state-sized buffer ---------------
    def bump_big(b):
        return b + 1.0

    bump_big_d = jax.jit(bump_big, donate_argnums=0)
    big = jax.device_put(jnp.zeros((3 * 1024 * 1024,), jnp.float32), dev)
    big = bump_big_d(big)
    jax.block_until_ready(big)  # compile

    def run_big():
        nonlocal big
        t0 = time.perf_counter()
        for _ in range(N):
            big = bump_big_d(big)
        jax.block_until_ready(big)
        return time.perf_counter() - t0

    emit("donate_12mb_dispatch", run_big(), run_big(), "us/dispatch")

    # -- 3b: N host->device transfers of a batch-sized buffer --------------
    # (the flagship batch is ~4.4 MB; MultiStep's k-stacks are k of these)
    host_buf = np.ones((1_100_000,), np.float32)  # ~4.4 MB

    def run_h2d():
        t0 = time.perf_counter()
        outs = [jax.device_put(host_buf, dev) for _ in range(N)]
        jax.block_until_ready(outs)
        return time.perf_counter() - t0

    emit("h2d_4mb", run_h2d(), run_h2d(), "us/transfer")

    # -- 4: one dispatch of an N-length scan carrying the 12 MB buffer -----
    def scan_big(b):
        return jax.lax.scan(lambda c, _: (c + 1.0, ()), b, None, length=N)[0]

    scan_big_d = jax.jit(scan_big, donate_argnums=0)
    big2 = jax.device_put(jnp.zeros((3 * 1024 * 1024,), jnp.float32), dev)
    big2 = scan_big_d(big2)
    jax.block_until_ready(big2)  # compile

    def run_scan_big():
        nonlocal big2
        t0 = time.perf_counter()
        big2 = scan_big_d(big2)
        jax.block_until_ready(big2)
        return time.perf_counter() - t0

    emit("carry_12mb_scan_1_dispatch", run_scan_big(), run_scan_big(),
         "us/iteration")


if __name__ == "__main__":
    main()
