"""Data layer: datasets, split, sampler sharding, loader batching."""

import numpy as np
import pytest

from pytorch_distributedtraining_tpu.data import (
    CustomDataset,
    DataLoader,
    DistributedSampler,
    SyntheticSRDataset,
    TensorDataset,
    random_split,
)


def test_synthetic_sr_shapes_and_determinism():
    ds = SyntheticSRDataset(n=8, lr_size=16, scale=2, seed=3)
    lr, hr = ds[0]
    assert lr.shape == (16, 16, 3) and hr.shape == (32, 32, 3)
    assert lr.dtype == np.float32
    # LR is the exact box-downsample of HR
    re = hr.reshape(16, 2, 16, 2, 3).mean(axis=(1, 3))
    np.testing.assert_allclose(lr, re, rtol=1e-6)
    lr2, _ = SyntheticSRDataset(n=8, lr_size=16, scale=2, seed=3)[0]
    np.testing.assert_array_equal(lr, lr2)
    with pytest.raises(IndexError):
        ds[8]


def test_random_split_deterministic_and_disjoint():
    ds = TensorDataset(np.arange(100))
    a, b = random_split(ds, [90, 10], seed=0)
    assert len(a) == 90 and len(b) == 10
    seen = {a[i][0].item() for i in range(90)} | {b[i][0].item() for i in range(10)}
    assert seen == set(range(100))
    a2, _ = random_split(ds, [90, 10], seed=0)
    assert [a[i][0].item() for i in range(5)] == [a2[i][0].item() for i in range(5)]
    with pytest.raises(ValueError, match="sum"):
        random_split(ds, [50, 10])


def test_custom_dataset_paired_folders(tmp_path):
    from PIL import Image

    for sub, size in (("lr", 8), ("hr", 16)):
        d = tmp_path / sub
        d.mkdir()
        for i in range(3):
            Image.fromarray(
                (np.full((size, size, 3), i * 40)).astype(np.uint8)
            ).save(d / f"img_{i}.png")
    ds = CustomDataset(str(tmp_path / "lr"), str(tmp_path / "hr"))
    assert len(ds) == 3
    lr, hr = ds[1]
    assert lr.shape == (8, 8, 3) and hr.shape == (16, 16, 3)
    np.testing.assert_allclose(lr, 40 / 255.0, atol=1e-6)


def test_sampler_shards_cover_and_disjoint():
    ds = TensorDataset(np.arange(103))
    shards = []
    for r in range(4):
        s = DistributedSampler(ds, num_replicas=4, rank=r, shuffle=True, seed=7)
        idxs = list(s)
        assert len(idxs) == len(s) == 26  # ceil(103/4)
        shards.append(idxs)
    flat = [i for sh in shards for i in sh]
    assert set(flat) == set(range(103))  # covers all (with 1 pad repeat)
    assert len(flat) == 104


def test_sampler_set_epoch_reshuffles():
    ds = TensorDataset(np.arange(64))
    s = DistributedSampler(ds, num_replicas=2, rank=0, shuffle=True, seed=0)
    e0 = list(s)
    s.set_epoch(1)
    e1 = list(s)
    assert e0 != e1
    s.set_epoch(0)
    assert list(s) == e0
    # drop_last trims to equal shards
    s2 = DistributedSampler(ds, num_replicas=3, rank=0, drop_last=True)
    assert len(list(s2)) == 21


def test_loader_batches_and_drop_last():
    xs = np.arange(10, dtype=np.float32)[:, None]
    ys = xs * 2
    dl = DataLoader(TensorDataset(xs, ys), batch_size=4)
    batches = list(dl)
    assert [b[0].shape[0] for b in batches] == [4, 4, 2]
    dl = DataLoader(TensorDataset(xs, ys), batch_size=4, drop_last=True)
    assert [b[0].shape[0] for b in dl] == [4, 4]


def test_loader_threaded_matches_serial():
    ds = SyntheticSRDataset(n=12, lr_size=8, scale=2)
    serial = list(DataLoader(ds, batch_size=3))
    threaded = list(DataLoader(ds, batch_size=3, num_workers=4, prefetch=2))
    assert len(serial) == len(threaded) == 4
    for (a1, b1), (a2, b2) in zip(serial, threaded):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)


def test_loader_process_workers_match_serial():
    """``multiprocessing_context='spawn'`` is a real
    process pool (the GIL-bound-transform escape hatch, honoring the
    reference's spawn surface `Stoke-DDP.py:290`), not a no-op."""
    ds = SyntheticSRDataset(n=8, lr_size=8, scale=2)
    serial = list(DataLoader(ds, batch_size=2))
    procs = list(DataLoader(
        ds, batch_size=2, num_workers=2, prefetch=1,
        multiprocessing_context="spawn",
    ))
    assert len(serial) == len(procs) == 4
    for (a1, b1), (a2, b2) in zip(serial, procs):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)


def test_loader_persistent_process_workers_reused():
    """persistent_workers=True keeps one spawn pool across epochs (the
    per-epoch worker-startup cost the flag exists to amortize)."""
    ds = SyntheticSRDataset(n=6, lr_size=8, scale=2)
    dl = DataLoader(
        ds, batch_size=3, num_workers=2, prefetch=1,
        multiprocessing_context="spawn", persistent_workers=True,
    )
    try:
        e0 = list(dl)
        pool = dl._pool
        assert pool is not None
        e1 = list(dl)
        assert dl._pool is pool  # same executor, no respawn
        assert len(e0) == len(e1) == 2
        for (a1, _), (a2, _) in zip(e0, e1):
            np.testing.assert_array_equal(a1, a2)
    finally:
        dl.shutdown_workers()
    assert dl._pool is None


def test_loader_rejects_unknown_context():
    with pytest.raises(ValueError, match="multiprocessing_context"):
        DataLoader(TensorDataset(np.arange(4)), multiprocessing_context="greenlet")


def test_loader_worker_error_propagates():
    class Bad(TensorDataset):
        def __getitem__(self, idx):
            if idx == 5:
                raise RuntimeError("decode failed")
            return super().__getitem__(idx)

    dl = DataLoader(Bad(np.arange(8)), batch_size=2, num_workers=2)
    with pytest.raises(RuntimeError, match="decode failed"):
        list(dl)


def test_loader_auto_set_epoch_reshuffles():
    ds = TensorDataset(np.arange(32))
    s = DistributedSampler(ds, num_replicas=1, rank=0, shuffle=True, seed=0)
    dl = DataLoader(ds, batch_size=32, sampler=s)
    e0 = next(iter(dl))[0].tolist()
    e1 = next(iter(dl))[0].tolist()
    assert e0 != e1  # fixed: the reference never called set_epoch


def test_loader_device_put_sharded(mesh8):
    from jax.sharding import PartitionSpec as P

    ds = TensorDataset(np.arange(32, dtype=np.float32)[:, None])
    dl = DataLoader(ds, batch_size=16, mesh=mesh8, spec=P("dp"))
    (batch,) = next(iter(dl))
    assert batch.shape == (16, 1)
    assert batch.addressable_shards[0].data.shape == (2, 1)


def test_loader_arg_validation(mesh8):
    ds = TensorDataset(np.arange(4))
    with pytest.raises(ValueError, match="sampler or shuffle"):
        DataLoader(ds, shuffle=True, sampler=DistributedSampler(ds, 1, 0))
    with pytest.raises(ValueError, match="together"):
        DataLoader(ds, mesh=mesh8)


def test_sampler_more_replicas_than_samples():
    ds = TensorDataset(np.arange(3))
    shards = [
        list(DistributedSampler(ds, num_replicas=8, rank=r, shuffle=False))
        for r in range(8)
    ]
    assert all(len(s) == 1 for s in shards)
    assert {s[0] for s in shards} == {0, 1, 2}


def test_abandoned_threaded_iterator_does_not_leak_threads():
    import threading

    ds = SyntheticSRDataset(n=64, lr_size=8, scale=2)
    before = threading.active_count()
    for _ in range(5):
        it = iter(DataLoader(ds, batch_size=4, num_workers=2, prefetch=1))
        next(it)
        it.close()  # abandon mid-epoch
    # feeder threads must notice the stop event and exit
    import time

    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before + 1


def test_custom_dataset_stem_mismatch(tmp_path):
    from PIL import Image

    for sub, names in (("lr", ["a.png", "bx2.png"]), ("hr", ["a.png", "c.png"])):
        d = tmp_path / sub
        d.mkdir()
        for n in names:
            Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(d / n)
    with pytest.raises(ValueError, match="do not pair up"):
        CustomDataset(str(tmp_path / "lr"), str(tmp_path / "hr"))


def test_custom_dataset_scale_suffix_pairs(tmp_path):
    from PIL import Image

    for sub, names in (("lr", ["0801x2.png"]), ("hr", ["0801.png"])):
        d = tmp_path / sub
        d.mkdir()
        for n in names:
            Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(d / n)
    assert len(CustomDataset(str(tmp_path / "lr"), str(tmp_path / "hr"))) == 1


def test_loader_explicit_set_epoch_resets_auto_counter():
    ds = TensorDataset(np.arange(32))
    s = DistributedSampler(ds, num_replicas=1, rank=0, shuffle=True, seed=0)
    dl = DataLoader(ds, batch_size=32, sampler=s)
    dl.set_epoch(5)
    e5 = next(iter(dl))[0].tolist()
    dl.set_epoch(5)
    assert next(iter(dl))[0].tolist() == e5  # deterministic resume


def test_loader_auto_epoch_desync_warns_multiprocess(monkeypatch):
    """The iter-count shuffle hazard is a coded warning now, not a
    docstring note: multi-process + auto_set_epoch +
    no explicit set_epoch -> one-shot RuntimeWarning on the 2nd iter()."""
    import warnings

    import jax

    from pytorch_distributedtraining_tpu.runtime import dist as rdist

    ds = TensorDataset(np.arange(8))
    s = DistributedSampler(ds, num_replicas=2, rank=0, shuffle=True, seed=0)
    dl = DataLoader(ds, batch_size=4, sampler=s)
    monkeypatch.setattr(rdist, "process_count_if_initialized", lambda: 2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # 1st iter: no warning
        next(iter(dl))
    with pytest.warns(RuntimeWarning, match="desyncs the per-rank shards"):
        next(iter(dl))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # one-shot: 3rd iter stays quiet
        next(iter(dl))
    # epoch-independent ordering (no sampler, no shuffle) never warns
    dl2 = DataLoader(TensorDataset(np.arange(8)), batch_size=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        next(iter(dl2))
        next(iter(dl2))


def test_loader_auto_epoch_no_warning_with_explicit_set_epoch(monkeypatch):
    import warnings

    import jax

    from pytorch_distributedtraining_tpu.runtime import dist as rdist

    ds = TensorDataset(np.arange(8))
    s = DistributedSampler(ds, num_replicas=2, rank=0, shuffle=True, seed=0)
    dl = DataLoader(ds, batch_size=4, sampler=s)
    monkeypatch.setattr(rdist, "process_count_if_initialized", lambda: 2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for epoch in range(3):
            dl.set_epoch(epoch)
            next(iter(dl))


def test_plateau_min_factor_floor():
    """Factor-mode twin of the reference's min_lr=5e-5 floor
    (`/root/reference/Stoke-DDP.py:305`)."""
    from pytorch_distributedtraining_tpu.optim import ReduceLROnPlateau

    sched = ReduceLROnPlateau(
        mode="min", factor=0.2, patience=0, min_factor=0.05
    )
    sched.step(1.0)
    for worse in range(10):
        factor = sched.step(2.0 + worse)
    assert factor == pytest.approx(0.05)  # floored, not 0.2**10


def test_patch_store_build_and_matches_custom_dataset(tmp_path):
    """PatchStore.build decodes a CustomDataset folder pair once; samples
    then match the PIL path to u8 quantization and feed decode-free."""
    from PIL import Image

    from pytorch_distributedtraining_tpu.data import CustomDataset, PatchStore

    lr_dir, hr_dir = tmp_path / "lr", tmp_path / "hr"
    lr_dir.mkdir(), hr_dir.mkdir()
    rng = np.random.default_rng(0)
    for i in range(6):
        hr = (rng.random((16, 16, 3)) * 255).astype(np.uint8)
        lr = hr.reshape(8, 2, 8, 2, 3).mean(axis=(1, 3)).astype(np.uint8)
        Image.fromarray(hr).save(hr_dir / f"{i:03d}.png")
        Image.fromarray(lr).save(lr_dir / f"{i:03d}.png")

    store = PatchStore.build(str(lr_dir), str(hr_dir), str(tmp_path / "store"))
    ref = CustomDataset(str(lr_dir), str(hr_dir))
    assert len(store) == len(ref) == 6
    for i in (0, 3, 5):
        (sl, sh), (rl, rh) = store[i], ref[i]
        assert sl.dtype == np.float32 and sh.dtype == np.float32
        np.testing.assert_allclose(sl, rl, atol=1 / 254)
        np.testing.assert_allclose(sh, rh, atol=1 / 254)

    # reopening from disk (memmap) works without rebuild
    store2 = PatchStore(str(tmp_path / "store"))
    np.testing.assert_array_equal(store2[2][1], store[2][1])


def test_patch_store_missing_dir_raises(tmp_path):
    from pytorch_distributedtraining_tpu.data import PatchStore

    with pytest.raises(FileNotFoundError, match="patch store"):
        PatchStore(str(tmp_path / "nope"))
