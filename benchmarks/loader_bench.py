"""DataLoader worker-mode benchmark: threads vs processes.

Two workloads over the same synthetic dataset:

- ``decode``: PIL-style work that RELEASES the GIL (numpy box-downsample
  on a large buffer) — the case the thread pool was measured adequate for;
- ``gil``: a pure-Python per-sample transform that HOLDS the GIL (the
  numpy-heavy-augmentation-in-Python-loops case) — the workload the
  ``multiprocessing_context`` process-pool escape hatch exists for.

Prints one JSON line per (workload, mode): samples/sec through the full
loader (fetch + collate + queue). Host-only — no accelerator involved.
``GRAFT_LOADER_N`` / ``GRAFT_LOADER_WORKERS`` resize.

NOTE: on a 1-core host neither mode can beat serial; the interesting
comparison needs >= 2 cores (any real TPU host). The run records
``cores`` so a reader can judge the row.
"""

from __future__ import annotations

import json
import os
import time

import _bootstrap  # noqa: F401  (repo root on sys.path)

import numpy as np

N = int(os.environ.get("GRAFT_LOADER_N", "64"))
WORKERS = int(os.environ.get("GRAFT_LOADER_WORKERS", "4"))
BATCH = 8


class _DecodeDataset:
    """GIL-releasing work: ~1.5 MB buffer downsample per sample."""

    def __len__(self):
        return N

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        img = rng.random((352, 352, 3), dtype=np.float32)
        lr = img.reshape(176, 2, 176, 2, 3).mean(axis=(1, 3))
        return lr, img[:64, :64]


class _GilDataset:
    """GIL-holding work: pure-Python loop per sample."""

    def __len__(self):
        return N

    def __getitem__(self, i):
        acc = 0
        for k in range(60_000):  # ~5 ms of bytecode, GIL held throughout
            acc += (k ^ i) & 7
        return np.full((8, 8), acc % 97, np.float32), np.float32(i)


def _time_loader(ds, **kw):
    from pytorch_distributedtraining_tpu.data import DataLoader

    dl = DataLoader(ds, batch_size=BATCH, **kw)
    list(dl)  # warm (spawn startup, caches)
    t0 = time.perf_counter()
    n = sum(b[0].shape[0] for b in dl)
    dt = time.perf_counter() - t0
    if hasattr(dl, "shutdown_workers"):
        dl.shutdown_workers()
    return n / dt


def main() -> None:
    cores = len(os.sched_getaffinity(0))
    for workload, ds in (("decode", _DecodeDataset()), ("gil", _GilDataset())):
        rows = {
            "serial": _time_loader(ds),
            "threads": _time_loader(ds, num_workers=WORKERS),
            "procs": _time_loader(
                ds, num_workers=WORKERS, multiprocessing_context="spawn",
                persistent_workers=True,
            ),
        }
        for mode, sps in rows.items():
            print(json.dumps({
                "metric": f"loader_{workload}_{mode}_samples_per_sec",
                "value": round(sps, 1),
                "unit": "samples/sec",
                "workers": 0 if mode == "serial" else WORKERS,
                "cores": cores,
            }))


if __name__ == "__main__":
    main()
