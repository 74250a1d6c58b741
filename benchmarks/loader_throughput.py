"""Input-pipeline throughput proof.

The reference feeds its chips with 16 DataLoader worker *processes*
(`/root/reference/Stoke-DDP.py:289`); this framework uses worker threads +
the fastpipe C++ collate. The question: can the pipeline keep a chip fed at
the benched train rate (BENCH_r02: ~2900+ img/s for SwinIR-S x2 @ 64x64)?

This box has very few cores (often 1), so the meaningful number is
**images/sec/core** through the full path — PNG decode (PIL) → crop pair →
fastpipe collate — from which the cores needed to saturate the chip
follows. A second arm measures the decode-free path (pre-extracted .npy
patch store, the TPU-native preprocessing answer) which feeds at memcpy
speed. One JSON line per arm, plus a summary line with the derived
feed budget.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import sys

import _bootstrap  # noqa: F401  (repo root on sys.path)

BENCH_RATE = float(os.environ.get("GRAFT_BENCH_RATE", "2935.0"))
N_IMGS = int(os.environ.get("GRAFT_LOADER_IMGS", "256"))
BATCH = 18
PATCH = 64
SECONDS = float(os.environ.get("GRAFT_LOADER_SECONDS", "8"))


def build_png_dataset(root):
    """Paired LR/HR PNG folders like the reference's Flickr2K layout
    (`Stoke-DDP.py:169-170`: --traindata_dir / --valdata_dir)."""
    from PIL import Image

    lr_dir = os.path.join(root, "lr")
    hr_dir = os.path.join(root, "hr")
    os.makedirs(lr_dir, exist_ok=True)
    os.makedirs(hr_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(N_IMGS):
        hr = (rng.random((2 * PATCH, 2 * PATCH, 3)) * 255).astype(np.uint8)
        lr = hr.reshape(PATCH, 2, PATCH, 2, 3).mean(axis=(1, 3)).astype(np.uint8)
        Image.fromarray(hr).save(os.path.join(hr_dir, f"{i:05d}.png"))
        Image.fromarray(lr).save(os.path.join(lr_dir, f"{i:05d}.png"))
    return lr_dir, hr_dir


def time_loader(loader, seconds):
    """Iterate repeatedly for ~seconds; return images/sec."""
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for batch in loader:
            n += batch[0].shape[0]
            if time.perf_counter() - t0 >= seconds:
                break
    return n / (time.perf_counter() - t0)


def main(tmp_root="/tmp/graft_loader_bench"):
    from pytorch_distributedtraining_tpu.data import CustomDataset, DataLoader

    lr_dir, hr_dir = build_png_dataset(tmp_root)
    ncores = os.cpu_count() or 1

    results = {}
    for workers in (0, 1, 2):
        ds = CustomDataset(lr_dir, hr_dir)
        loader = DataLoader(
            ds, batch_size=BATCH, shuffle=True, num_workers=workers,
            drop_last=True, prefetch=4,
        )
        rate = time_loader(loader, SECONDS)
        results[workers] = rate
        print(json.dumps({
            "arm": f"png_decode_workers{workers}",
            "images_per_sec": round(rate, 1),
        }), flush=True)

    # decode-free arm: pre-extracted patch store (npy memmap) + fastpipe
    rng = np.random.default_rng(0)
    hr_store = (rng.random((N_IMGS, 2 * PATCH, 2 * PATCH, 3)) * 255).astype(
        np.uint8
    )
    lr_store = hr_store.reshape(
        N_IMGS, PATCH, 2, PATCH, 2, 3
    ).mean(axis=(2, 4)).astype(np.uint8)
    np.save(os.path.join(tmp_root, "hr.npy"), hr_store)
    np.save(os.path.join(tmp_root, "lr.npy"), lr_store)
    hr_mm = np.load(os.path.join(tmp_root, "hr.npy"), mmap_mode="r")
    lr_mm = np.load(os.path.join(tmp_root, "lr.npy"), mmap_mode="r")

    class PatchStore:
        def __len__(self):
            return N_IMGS

        def __getitem__(self, i):
            return (
                np.asarray(lr_mm[i], dtype=np.float32) / 255.0,
                np.asarray(hr_mm[i], dtype=np.float32) / 255.0,
            )

    loader = DataLoader(
        PatchStore(), batch_size=BATCH, shuffle=True, num_workers=1,
        drop_last=True, prefetch=4,
    )
    npy_rate = time_loader(loader, SECONDS)
    print(json.dumps({
        "arm": "npy_patch_store_workers1",
        "images_per_sec": round(npy_rate, 1),
    }), flush=True)

    per_core = max(results.values())
    print(json.dumps({
        "summary": {
            "host_cores": ncores,
            "png_images_per_sec_per_core": round(per_core, 1),
            "cores_to_feed_bench_rate": round(BENCH_RATE / per_core, 1),
            "reference_worker_count": 16,  # Stoke-DDP.py:289
            "npy_images_per_sec": round(npy_rate, 1),
            "npy_feeds_bench_rate": npy_rate >= BENCH_RATE,
        }
    }), flush=True)


if __name__ == "__main__":
    main()
