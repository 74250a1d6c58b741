"""Two reference points of the attached chip, to read the other numbers by.

A large bf16 matrix multiplication and a plain elementwise pass over 2 GiB,
each timed to ``block_until_ready`` (copied from ``chip_smoke.py``'s
``phase_reference_points``), printed beside the published peaks of
``peaks.json``. Run by hand, once per part; gates nothing::

    chiprun -- python3 -m chipbench.calibrate
"""

from __future__ import annotations

import json
import sys
import time

MATMUL_N = 8192
ELEMENTWISE_BYTES = 2 << 30
REPS = 10


def _timed(fn, *args) -> float:
    """Seconds per call over REPS queued calls, after one warm call."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS


def reference_points() -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench.peaks import peaks_for

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"calibrate needs a TPU, jax found {dev.platform}")
    peaks = peaks_for(dev.device_kind)

    a = jnp.ones((MATMUL_N, MATMUL_N), jnp.bfloat16)
    mm_s = _timed(jax.jit(lambda a, b: a @ b), a, a)
    del a
    x = jnp.ones((ELEMENTWISE_BYTES // 4,), jnp.float32)
    ew_s = _timed(jax.jit(lambda x: x * 2.0 + 1.0), x)
    tflops = 2 * MATMUL_N**3 / mm_s / 1e12
    gbytes = 2 * x.nbytes / ew_s / 1e9  # one read and one write
    return {
        "device_kind": dev.device_kind,
        "matmul_bf16": {
            "n": MATMUL_N, "seconds": mm_s, "tflops": tflops,
            "share_of_peak": tflops * 1e12 / peaks["bf16_flops_per_s"],
        },
        "elementwise_f32": {
            "array_bytes": x.nbytes, "seconds": ew_s, "gbytes_per_s": gbytes,
            "share_of_peak": gbytes * 1e9 / peaks["hbm_bytes_per_s"],
        },
    }


if __name__ == "__main__":
    print(json.dumps(reference_points()), flush=True)
    sys.exit(0)
