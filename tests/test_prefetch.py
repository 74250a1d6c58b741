"""Device prefetch, overlap audit, and compile-cache knob tests.

CPU-runnable coverage for the overlap subsystem: DevicePrefetcher
ordering/depth/degradation, the loader.stage fault site, the
transfer-vs-compute probe, the HLO overlap audit, and the persistent
compile-cache wiring (ISSUE: "Overlap everything").
"""

import os
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributedtraining_tpu.data import (
    DataLoader,
    DevicePrefetcher,
    TensorDataset,
    place_on_mesh,
    stack_windows,
)
from pytorch_distributedtraining_tpu.observe import (
    collectives_schedulable,
    overlap_audit,
)
from pytorch_distributedtraining_tpu.resilience import (
    FaultPlan,
    InjectedFault,
    install_plan,
)
from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    install_plan(None)


def _pairs(n=32, dim=3):
    xs = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
    ys = xs * 2.0
    return xs, ys


# -- DevicePrefetcher core ---------------------------------------------------


def test_prefetch_matches_sync_order_and_values(mesh8):
    xs, ys = _pairs()
    spec = batch_spec(mesh8)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8, spec=spec)
    sync = [jax.tree.map(np.asarray, b) for b in dl]
    staged = list(dl.device_iter(depth=2))
    assert len(staged) == len(sync) == 4
    for s_host, s_dev in zip(sync, staged):
        for h, d in zip(jax.tree.leaves(s_host), jax.tree.leaves(s_dev)):
            assert not isinstance(d, np.ndarray)  # actually placed
            np.testing.assert_array_equal(h, np.asarray(d))


def test_prefetch_sharding_matches_spec(mesh8):
    xs, ys = _pairs()
    spec = batch_spec(mesh8)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8, spec=spec)
    b = next(iter(dl.device_iter(depth=1)))
    x = jax.tree.leaves(b)[0]
    assert x.sharding.spec == spec
    # batch dim split over the 8-way dp axis: one row per device shard
    assert len(x.sharding.device_set) == 8
    assert x.addressable_shards[0].data.shape[0] == 1


def test_prefetch_depth_bounds_lookahead(mesh8):
    """With a slow consumer the feeder stays <= depth+1 batches ahead
    (depth staged in the queue + one in flight)."""
    pulled = []

    def source():
        for i in range(8):
            pulled.append(i)
            yield np.full((8, 2), i, np.float32)

    pf = DevicePrefetcher(source(), mesh8, batch_spec(mesh8), depth=2)
    try:
        first = next(pf)
        time.sleep(0.3)  # let the feeder run as far ahead as it can
        assert len(pulled) <= 1 + (2 + 1)  # consumed + depth + in-flight
        rest = list(pf)
        assert len(rest) == 7
        np.testing.assert_array_equal(np.asarray(first), np.zeros((8, 2)))
    finally:
        pf.close()


def test_prefetch_depth_validation(mesh8):
    with pytest.raises(ValueError):
        DevicePrefetcher(iter([]), None, None)
    pf = DevicePrefetcher(iter([]), mesh8, batch_spec(mesh8), depth=-3)
    assert pf.depth == 1
    assert list(pf) == []


def test_prefetch_donation_safe(mesh8):
    """Staged batches survive a donating consumer: each yielded buffer is
    a fresh placement, never an alias of one the jit just consumed."""
    xs, ys = _pairs(n=32)
    spec = batch_spec(mesh8)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8, spec=spec)

    @jax.jit
    def consume(b):
        x, y = b
        return jnp.sum(x) + jnp.sum(y)

    donating = jax.jit(lambda b: jax.tree.map(lambda a: a * 0, b),
                       donate_argnums=0)
    totals = []
    for b in dl.device_iter(depth=3):
        totals.append(float(consume(b)))
        donating(b)  # invalidates THIS batch's buffers
    expected = [float(np.sum(xs[i:i + 8]) * 3) for i in range(0, 32, 8)]
    assert totals == pytest.approx(expected)


def test_prefetch_source_error_propagates(mesh8):
    def source():
        yield np.ones((8, 2), np.float32)
        raise RuntimeError("upstream decode failure")

    pf = DevicePrefetcher(source(), mesh8, batch_spec(mesh8), depth=2)
    next(pf)
    with pytest.raises(RuntimeError, match="upstream decode failure"):
        next(pf)


def test_prefetch_close_idempotent_and_stops_feeder(mesh8):
    def source():
        while True:
            yield np.ones((8, 2), np.float32)

    pf = DevicePrefetcher(source(), mesh8, batch_spec(mesh8), depth=2)
    next(pf)
    pf.close()
    pf.close()
    pf._thread.join(timeout=5)
    assert not pf._thread.is_alive()


# -- loader.stage fault: degrade, don't deadlock -----------------------------


@pytest.mark.parametrize("action", ["raise", "oserror"])
def test_stage_fault_degrades_to_synchronous(mesh8, action):
    """An injected staging failure flips the prefetcher to synchronous
    feeding: every batch still arrives, on-device, in order — no hang."""
    xs, ys = _pairs()
    spec = batch_spec(mesh8)
    install_plan(FaultPlan.from_json({"faults": [
        {"site": "loader.stage", "at": 2, "times": 0, "action": action},
    ]}))
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8, spec=spec)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        it = dl.device_iter(depth=2)  # feeder warns from its own thread
        got = list(it)
        it._thread.join(timeout=5)
    assert it.degraded
    assert any("degrading to synchronous" in str(w.message) for w in caught)
    assert len(got) == 4  # no dropped batch
    for i, b in enumerate(got):
        x = jax.tree.leaves(b)[0]
        assert not isinstance(x, np.ndarray)  # still placed (sync path)
        np.testing.assert_array_equal(np.asarray(x), xs[i * 8:(i + 1) * 8])


def test_stage_fault_first_batch(mesh8):
    """Degradation on the very first stage (nothing staged yet)."""
    xs, ys = _pairs(n=16)
    install_plan(FaultPlan.from_json({"faults": [
        {"site": "loader.stage", "at": 1, "times": 0, "action": "raise"},
    ]}))
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8,
                    mesh=mesh8, spec=batch_spec(mesh8))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        it = dl.device_iter(depth=2)
        got = list(it)
    assert it.degraded and len(got) == 2


def test_stage_fault_site_registered():
    from pytorch_distributedtraining_tpu.resilience.faults import SITES

    assert "loader.stage" in SITES


def test_real_stage_error_degrades_not_raises(mesh8):
    """A genuinely unstageable batch (ragged pytree) degrades the feeder;
    the consumer then surfaces the real error synchronously on its own
    stack — visible, not swallowed, not hung."""
    bad = object()  # np.asarray(object()) later fails loudly

    def source():
        yield bad

    pf = DevicePrefetcher(source(), mesh8, batch_spec(mesh8), depth=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(Exception):
            list(pf)
    assert pf.degraded


def test_prefetch_registers_epoch_race_feeder(mesh8):
    xs, ys = _pairs(n=16)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8,
                    mesh=mesh8, spec=batch_spec(mesh8))
    it = dl.device_iter(depth=1)
    assert it._thread in dl._feeders
    list(it)  # drain: the feeder no longer counts as an epoch hazard
    assert it._drained.is_set()


# -- loader/facade integration ----------------------------------------------


def test_loader_device_prefetch_ctor_path(mesh8):
    xs, ys = _pairs()
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8,
                    spec=batch_spec(mesh8), device_prefetch=2)
    got = list(dl)  # plain iteration rides the prefetcher
    assert len(got) == 4
    assert all(
        not isinstance(jax.tree.leaves(b)[0], np.ndarray) for b in got
    )


def test_loader_device_prefetch_requires_mesh():
    xs, ys = _pairs(n=8)
    with pytest.raises(ValueError, match="requires mesh"):
        DataLoader(TensorDataset(xs, ys), batch_size=8, device_prefetch=2)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8)
    with pytest.raises(ValueError, match="needs mesh"):
        dl.device_iter()


def test_multistep_feed_stacks_staged_windows(mesh8):
    """MultiStep.feed-shaped staging: stack_windows over a device_iter
    yields [k, B, ...] stacks with device leaves."""
    xs, ys = _pairs(n=32)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8,
                    spec=batch_spec(mesh8))
    it = dl.device_iter(depth=2)
    stacks = list(stack_windows(it, 2))
    assert len(stacks) == 2
    x = jax.tree.leaves(stacks[0])[0]
    assert x.shape == (2, 8, 3)
    np.testing.assert_array_equal(np.asarray(x)[0], xs[0:8])
    np.testing.assert_array_equal(np.asarray(x)[1], xs[8:16])


def test_place_on_mesh_pads_ragged_tail(mesh8):
    xs = np.arange(5 * 2, dtype=np.float32).reshape(5, 2)  # 5 % 8 != 0
    placed = place_on_mesh(xs, mesh8, batch_spec(mesh8))
    arr = np.asarray(placed)
    assert arr.shape[0] == 8  # padded up to the divisor
    np.testing.assert_array_equal(arr[:5], xs)
    np.testing.assert_array_equal(arr[5], xs[-1])  # repeat-last padding


# -- overlap fraction ----------------------------------------------------------


def test_prefetcher_overlap_fraction_bounds(mesh8):
    xs, ys = _pairs(n=16)
    dl = DataLoader(TensorDataset(xs, ys), batch_size=8, mesh=mesh8,
                    spec=batch_spec(mesh8))
    it = dl.device_iter(depth=2)
    t0 = time.perf_counter()
    for b in it:
        time.sleep(0.01)
    frac = it.overlap_fraction(time.perf_counter() - t0)
    assert frac is not None and 0.0 <= frac <= 1.0
    assert it.overlap_fraction(0.0) is None


# -- HLO overlap audit -------------------------------------------------------


_GOOD_HLO = """\
ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4] parameter(0)
  %ar-start = f32[8,4] all-reduce-start(%p0), replica_groups={}
  %mul = f32[8,4] multiply(%p0, %p0)
  %add = f32[8,4] add(%mul, %mul)
  %ar-done = f32[8,4] all-reduce-done(%ar-start)
  ROOT %out = f32[8,4] add(%ar-done, %add)
}
"""

_SYNC_HLO = """\
ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4] parameter(0)
  %ar = f32[8,4] all-reduce(%p0), replica_groups={}
  ROOT %out = f32[8,4] add(%ar, %ar)
}
"""

_EMPTY_PAIR_HLO = """\
ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4] parameter(0)
  %ar-start = f32[8,4] all-reduce-start(%p0), replica_groups={}
  %ar-done = f32[8,4] all-reduce-done(%ar-start)
  ROOT %out = f32[8,4] multiply(%ar-done, %ar-done)
}
"""


def test_overlap_audit_known_good():
    audit = overlap_audit(_GOOD_HLO)
    assert audit.total == 1
    f = audit.findings[0]
    assert f.kind == "all-reduce" and f.async_form
    assert f.hidden_ops == 2  # mul + add scheduled inside the window
    assert f.schedulable and audit.ok
    assert collectives_schedulable(_GOOD_HLO)


def test_overlap_audit_known_bad_sync():
    audit = overlap_audit(_SYNC_HLO)
    assert audit.total == 1
    f = audit.findings[0]
    assert not f.async_form and not f.schedulable
    assert audit.blocking == (f,)
    assert not collectives_schedulable(_SYNC_HLO)


def test_overlap_audit_known_bad_empty_window():
    """An async pair with NOTHING between start and done still blocks."""
    audit = overlap_audit(_EMPTY_PAIR_HLO)
    f = audit.findings[0]
    assert f.async_form and f.hidden_ops == 0 and not f.schedulable
    assert not audit.ok


def test_overlap_audit_no_collectives_vacuous():
    hlo = "ENTRY %m () -> f32[] {\n  ROOT %c = f32[] constant(0)\n}\n"
    assert overlap_audit(hlo).total == 0
    assert collectives_schedulable(hlo)


def test_overlap_audit_on_real_compiled_module(mesh8):
    """End-to-end on a real psum program: the audit parses whatever form
    XLA:CPU emits without crashing, and finds the all-reduce."""
    from jax.sharding import NamedSharding

    spec = batch_spec(mesh8)

    @jax.jit
    def f(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(mesh8, spec)
        ).sum()

    x = place_on_mesh(np.ones((8, 4), np.float32), mesh8, spec)
    hlo = f.lower(x).compile().as_text()
    audit = overlap_audit(hlo)  # must not raise on real HLO text
    assert audit.total >= 0


# -- latency-hiding scheduler + compile cache --------------------------------


def test_latency_hiding_flags_env_gate(monkeypatch):
    from pytorch_distributedtraining_tpu.runtime import dist

    monkeypatch.setenv("GRAFT_OVERLAP", "0")
    assert dist.enable_latency_hiding_scheduler() is False

    monkeypatch.delenv("GRAFT_OVERLAP", raising=False)
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    monkeypatch.setattr(dist, "backend_initialized", lambda: False)
    assert dist.enable_latency_hiding_scheduler() is True
    args = os.environ["LIBTPU_INIT_ARGS"]
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in args
    # idempotent: all flags present -> True without duplicating
    assert dist.enable_latency_hiding_scheduler() is True
    assert os.environ["LIBTPU_INIT_ARGS"].count(
        "latency_hiding_scheduler"
    ) == 1


def test_latency_hiding_flags_late_is_refused(monkeypatch):
    from pytorch_distributedtraining_tpu.runtime import dist

    monkeypatch.delenv("GRAFT_OVERLAP", raising=False)
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    monkeypatch.setattr(dist, "backend_initialized", lambda: True)
    assert dist.enable_latency_hiding_scheduler() is False
    assert "latency_hiding" not in os.environ.get("LIBTPU_INIT_ARGS", "")


def test_enable_compile_cache(tmp_path, monkeypatch):
    from pytorch_distributedtraining_tpu.runtime.cache import (
        cache_entry_count,
        enable_compile_cache,
    )

    # the variable names the directory; repo code then sets none itself
    target = tmp_path / "cc"
    target.mkdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    old = jax.config.jax_compilation_cache_dir
    path = enable_compile_cache()
    assert path == str(target)
    assert jax.config.jax_compilation_cache_dir == old
    assert cache_entry_count(path) == 0
    (target / "entry.bin").write_bytes(b"x")
    assert cache_entry_count(path) == 1
    assert cache_entry_count(None) == 0
    assert cache_entry_count(str(tmp_path / "missing")) == 0


def test_enable_compile_cache_disabled(monkeypatch):
    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    monkeypatch.setenv("GRAFT_COMPILE_CACHE", "0")
    try:
        assert enable_compile_cache() is None
        assert not jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.slow
def test_prefetch_bench_smoke(tmp_path):
    """benchmarks/prefetch_bench.py runs end-to-end and emits its four
    arm rows plus a summary line (tiny sizes; excluded from tier-1)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        GRAFT_PREFETCH_BENCH_STEPS="4",
        GRAFT_PREFETCH_BENCH_BATCH="4",
        GRAFT_PREFETCH_BENCH_DIM="32",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "prefetch_bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    arms = [r["arm"] for r in rows if "arm" in r]
    assert arms == ["sync", "prefetch1", "prefetch2", "prefetch3"]
    assert any("summary" in r for r in rows)


def test_abandoned_prefetcher_thread_exits(mesh8):
    """Dropping the last reference finalizes the prefetcher: the feeder is
    NOT kept alive as a GC root (module-level thread target, no bound
    method)."""
    import gc

    def source():
        while True:
            yield np.ones((8, 2), np.float32)

    pf = DevicePrefetcher(source(), mesh8, batch_spec(mesh8), depth=1)
    next(pf)
    t = pf._thread
    del pf
    gc.collect()
    t.join(timeout=5)
    assert not t.is_alive()
