"""Resilience subsystem: classifier/retry/breaker units + the chaos matrix.

Every recovery path in the stack existed before this suite — elastic
restarts, rendezvous retry, loader worker replacement, checkpoint-write
retry, preemption save — but none were ever exercised except by a real pool flap. Each chaos test injects the failure
deterministically (resilience.faults.FaultPlan) and asserts the recovery,
site by site:

==========================  =============================================
``dist.rendezvous``         rank dies in the handshake → elastic restart
``collective.barrier``      UNAVAILABLE at the barrier → elastic restart
``launch.worker``           monitor SIGKILLs a rank → elastic restart
``loader.fetch`` (thread)   crash surfaces cleanly; next epoch recovers
``loader.fetch`` (process)  dead worker → broken pool replaced
``checkpoint.write``        transient EIO → retried write lands
``train.preempt``           mid-step SIGTERM → forced durable save
==========================  =============================================
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from pytorch_distributedtraining_tpu.resilience import (
    CircuitBreaker,
    FaultPlan,
    InjectedFault,
    OutageClass,
    RetryPolicy,
    classify,
    classify_exception,
    install_plan,
)
from pytorch_distributedtraining_tpu.resilience.faults import fault_point

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# outage classifier
# ---------------------------------------------------------------------------


class TestClassifier:
    @pytest.mark.parametrize(
        "rc,expected",
        [
            (None, OutageClass.OUTAGE),   # killed a hung child
            (3, OutageClass.OUTAGE),      # probe CPU-fallback refusal
            (4, OutageClass.OUTAGE),      # child CPU-fallback refusal
            (124, OutageClass.OUTAGE),    # driver `timeout` expiry
            (-9, OutageClass.OUTAGE),     # SIGKILL — external termination
            (-15, OutageClass.OUTAGE),    # SIGTERM
            (137, OutageClass.OUTAGE),    # 128+9, shell convention
            (143, OutageClass.OUTAGE),    # 128+15
            (-11, OutageClass.UNKNOWN),   # SIGSEGV: maybe flaky, maybe ours
            (1, OutageClass.UNKNOWN),     # bare failure, no signature
            (2, OutageClass.DETERMINISTIC),
            (5, OutageClass.DETERMINISTIC),
        ],
    )
    def test_rc_matrix(self, rc, expected):
        assert classify(rc) is expected

    @pytest.mark.parametrize(
        "tail",
        [
            "UNAVAILABLE: TPU backend not found",
            "grpc error DEADLINE_EXCEEDED while polling",
            "Connection refused by coordinator",
            "connection reset by peer",
            "failed to connect to all addresses",
            "BrokenPipeError: broken pipe",
        ],
    )
    def test_outage_text_overrides_rc(self, tail):
        assert classify(1, tail) is OutageClass.OUTAGE
        assert classify(2, tail) is OutageClass.OUTAGE

    def test_grpc_sentinels_are_case_sensitive(self):
        # lowercase "unavailable" appears in ordinary prose ("service
        # unavailable" error pages) — only the canonical uppercase gRPC
        # token counts
        assert classify(1, "the server is unavailable") is OutageClass.UNKNOWN

    def test_exceptions(self):
        assert classify_exception(ConnectionError("x")) is OutageClass.OUTAGE
        assert classify_exception(TimeoutError()) is OutageClass.OUTAGE
        assert classify_exception(OSError(5, "I/O error")) is OutageClass.OUTAGE
        assert (
            classify_exception(RuntimeError("UNAVAILABLE: pool"))
            is OutageClass.OUTAGE
        )
        assert classify_exception(RuntimeError("boom")) is OutageClass.UNKNOWN


class TestRetryPolicy:
    def test_deterministic_schedule(self):
        p = RetryPolicy(attempts=4, base_delay_s=1.0, jitter_frac=0.0)
        assert list(p.delays()) == [1.0, 2.0, 4.0]
        # jitter is seeded: two instances replay the same schedule
        a = RetryPolicy(attempts=4, seed=7)
        assert list(a.delays()) == list(RetryPolicy(attempts=4, seed=7).delays())

    def test_max_delay_caps(self):
        p = RetryPolicy(
            attempts=6, base_delay_s=10.0, max_delay_s=15.0, jitter_frac=0.0
        )
        assert max(p.delays()) == 15.0

    def test_run_retries_then_succeeds(self):
        slept, calls = [], {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("connection refused")
            return "ok"

        p = RetryPolicy(attempts=3, base_delay_s=0.01, jitter_frac=0.0)
        assert p.run(flaky, sleep=slept.append) == "ok"
        assert calls["n"] == 3 and len(slept) == 2

    def test_run_exhausts_and_reraises(self):
        p = RetryPolicy(attempts=2, base_delay_s=0.0, jitter_frac=0.0)
        with pytest.raises(ConnectionError):
            p.run(lambda: (_ for _ in ()).throw(ConnectionError("x")),
                  sleep=lambda s: None)

    def test_retry_on_gates(self):
        calls = {"n": 0}

        def always():
            calls["n"] += 1
            raise ValueError("deterministic")

        p = RetryPolicy(attempts=5, base_delay_s=0.0)
        with pytest.raises(ValueError):
            p.run(always, retry_on=lambda e: not isinstance(e, ValueError),
                  sleep=lambda s: None)
        assert calls["n"] == 1  # not retried


class TestCircuitBreaker:
    def test_full_cycle(self):
        t = {"now": 0.0}
        br = CircuitBreaker(
            failure_threshold=2, reset_timeout_s=10.0,
            clock=lambda: t["now"],
        )
        assert br.allow() and br.state == br.CLOSED
        br.record_failure()
        assert br.state == br.CLOSED  # one below threshold
        br.record_failure()
        assert br.state == br.OPEN and not br.allow()
        t["now"] = 11.0
        assert br.state == br.HALF_OPEN
        assert br.allow()          # the single half-open probe
        assert not br.allow()      # second probe refused
        br.record_success()
        assert br.state == br.CLOSED and br.allow()

    def test_half_open_failure_reopens(self):
        t = {"now": 0.0}
        br = CircuitBreaker(
            failure_threshold=1, reset_timeout_s=5.0, clock=lambda: t["now"]
        )
        br.record_failure()
        t["now"] = 6.0
        assert br.allow()
        br.record_failure()  # trial failed
        assert br.state == br.OPEN and not br.allow()
        t["now"] = 10.0      # timeout restarted at 6.0, not elapsed yet
        assert br.state == br.OPEN


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_unknown_site_and_keys_fail_loudly(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            FaultPlan.from_json({"faults": [{"site": "nope.nope"}]})
        with pytest.raises(ValueError, match="unknown keys"):
            FaultPlan.from_json(
                {"faults": [{"site": "loader.fetch", "tiems": 2}]}
            )

    def test_at_times_counting(self):
        plan = FaultPlan.from_json(
            {"faults": [{"site": "loader.fetch", "at": 3, "times": 2}]}
        )
        fired = []
        for i in range(6):
            try:
                plan.point("loader.fetch")
                fired.append(False)
            except InjectedFault:
                fired.append(True)
        assert fired == [False, False, True, True, False, False]

    def test_times_zero_fires_forever(self):
        plan = FaultPlan.from_json(
            {"faults": [{"site": "ckpt.write", "times": 0}]}
        )
        for _ in range(5):
            with pytest.raises(InjectedFault):
                plan.point("ckpt.write")

    def test_rank_and_attempt_filters(self, monkeypatch):
        plan = FaultPlan.from_json({"faults": [
            {"site": "dist.rendezvous", "rank": 1, "attempt": 2},
        ]})
        monkeypatch.setenv("RANK", "0")
        monkeypatch.setenv("GRAFT_RESTART_ATTEMPT", "2")
        plan.point("dist.rendezvous")  # wrong rank: no fire
        monkeypatch.setenv("RANK", "1")
        monkeypatch.setenv("GRAFT_RESTART_ATTEMPT", "0")
        plan.point("dist.rendezvous")  # wrong attempt: no fire
        monkeypatch.setenv("GRAFT_RESTART_ATTEMPT", "2")
        with pytest.raises(InjectedFault):
            plan.point("dist.rendezvous")

    def test_match_context(self):
        plan = FaultPlan.from_json({"faults": [
            {"site": "train.preempt", "match": {"step": 3}},
        ]})
        plan.point("train.preempt", step=1)
        plan.point("train.preempt", step=2)
        with pytest.raises(InjectedFault):
            plan.point("train.preempt", step=3)

    def test_oserror_action(self):
        plan = FaultPlan.from_json({"faults": [
            {"site": "checkpoint.write", "action": "oserror",
             "message": "injected EIO"},
        ]})
        with pytest.raises(OSError) as ei:
            plan.point("checkpoint.write")
        assert ei.value.errno == 5

    def test_from_env_inline_and_file(self, tmp_path, monkeypatch):
        raw = '{"faults": [{"site": "ckpt.write"}]}'
        monkeypatch.setenv("GRAFT_FAULT_PLAN", raw)
        assert len(FaultPlan.from_env().rules) == 1
        f = tmp_path / "plan.json"
        f.write_text(raw)
        monkeypatch.setenv("GRAFT_FAULT_PLAN", str(f))
        assert len(FaultPlan.from_env().rules) == 1
        monkeypatch.setenv("GRAFT_FAULT_PLAN", "")
        assert FaultPlan.from_env() is None

    def test_install_plan_drives_fault_point(self):
        try:
            install_plan(FaultPlan.from_json(
                {"faults": [{"site": "ckpt.write", "message": "hi"}]}
            ))
            with pytest.raises(InjectedFault, match="hi"):
                fault_point("ckpt.write")
            fault_point("ckpt.write")  # exhausted: no-op
        finally:
            install_plan(None)
        fault_point("ckpt.write")  # cleared: no-op


# ---------------------------------------------------------------------------
# chaos: data loader (site loader.fetch)
# ---------------------------------------------------------------------------


def _square_ds():
    from pytorch_distributedtraining_tpu.data import TensorDataset

    xs = np.arange(12, dtype=np.float32)[:, None]
    return TensorDataset(xs, xs * 2)


def test_loader_thread_worker_crash_surfaces_and_recovers():
    from pytorch_distributedtraining_tpu.data import DataLoader

    ds = _square_ds()
    try:
        install_plan(FaultPlan.from_json({"faults": [
            {"site": "loader.fetch", "at": 3,
             "message": "injected decode crash"},
        ]}))
        with pytest.raises(InjectedFault, match="injected decode crash"):
            list(DataLoader(ds, batch_size=4, num_workers=2, prefetch=1))
    finally:
        install_plan(None)
    # rule consumed + plan cleared: the next epoch is clean
    batches = list(DataLoader(ds, batch_size=4, num_workers=2, prefetch=1))
    assert [b[0].shape[0] for b in batches] == [4, 4, 4]


def test_loader_process_worker_death_replaces_pool(monkeypatch):
    from concurrent.futures.process import BrokenProcessPool

    from pytorch_distributedtraining_tpu.data import DataLoader

    ds = _square_ds()
    dl = DataLoader(
        ds, batch_size=4, num_workers=1, prefetch=1,
        multiprocessing_context="spawn", persistent_workers=True,
    )
    try:
        # the plan rides the env across the spawn boundary; action=exit
        # kills the worker process mid-fetch (OOM-kill twin)
        monkeypatch.setenv("GRAFT_FAULT_PLAN", json.dumps({"faults": [
            {"site": "loader.fetch", "action": "exit", "arg": 1},
        ]}))
        with pytest.raises(BrokenProcessPool):
            list(dl)
        monkeypatch.delenv("GRAFT_FAULT_PLAN")
        # recovery: _get_pool notices the broken executor and replaces it
        batches = list(dl)
        assert [b[0].shape[0] for b in batches] == [4, 4, 4]
    finally:
        dl.shutdown_workers()


# ---------------------------------------------------------------------------
# chaos: checkpoint write (site checkpoint.write) + preemption
# ---------------------------------------------------------------------------


def _tiny_state():
    import jax.numpy as jnp

    return {"w": jnp.arange(8.0), "b": jnp.ones((2, 2))}


def test_checkpoint_transient_io_error_is_retried(tmp_path):
    from pytorch_distributedtraining_tpu.checkpoint_sharded import (
        restore_sharded,
        save_sharded,
    )

    state = _tiny_state()
    plan = FaultPlan.from_json({"faults": [
        {"site": "checkpoint.write", "action": "oserror",
         "message": "injected EIO on flaky mount"},
    ]})
    try:
        install_plan(plan)
        path = save_sharded(
            str(tmp_path / "ck"), state,
            retry=RetryPolicy(attempts=3, base_delay_s=0.01, jitter_frac=0.0),
        )
    finally:
        install_plan(None)
    assert plan.rules[0].hits == 2  # failed once, landed on the retry
    back = restore_sharded(path, state)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(8.0))


def test_checkpoint_deterministic_error_not_retried(tmp_path):
    from pytorch_distributedtraining_tpu.checkpoint_sharded import save_sharded

    plan = FaultPlan.from_json({"faults": [
        {"site": "checkpoint.write", "times": 3,
         "message": "injected logic bug"},
    ]})
    try:
        install_plan(plan)
        with pytest.raises(InjectedFault):
            save_sharded(
                str(tmp_path / "ck2"), _tiny_state(),
                retry=RetryPolicy(attempts=3, base_delay_s=0.01),
            )
    finally:
        install_plan(None)
    # UNKNOWN-class (no outage signature): one attempt, no retry burn
    assert plan.rules[0].hits == 1


def test_preemption_fault_forces_durable_save(tmp_path):
    from pytorch_distributedtraining_tpu.checkpoint_sharded import (
        CheckpointManager,
    )

    mgr = CheckpointManager(
        str(tmp_path / "pre"), save_every=10_000, keep=2
    )
    state = _tiny_state()
    try:
        install_plan(FaultPlan.from_json({"faults": [
            {"site": "train.preempt", "action": "sigterm",
             "match": {"step": 3}},
        ]}))
        assert mgr.maybe_save(1, state) is None
        assert mgr.maybe_save(2, state) is None
        # the injected SIGTERM lands inside maybe_save(step=3), before the
        # agreement point — the same path a real preemption takes
        path = mgr.maybe_save(3, state)
        assert path is not None and os.path.isdir(path)
        assert mgr.latest_step() == 3
        assert not mgr.preempted  # flag consumed by the save
        assert mgr.maybe_save(4, state) is None  # back to normal
    finally:
        install_plan(None)
        mgr.close()


# ---------------------------------------------------------------------------
# chaos: launcher (sites dist.rendezvous, collective.barrier, launch.worker)
# ---------------------------------------------------------------------------


def _launch(tmp_path, child_src, plan, nproc=2, max_restarts=2,
            extra_args=(), timeout_s=240):
    script = tmp_path / "child.py"
    script.write_text(child_src)
    marker = str(tmp_path / "done_")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MARKER"] = marker
    env["GRAFT_FAULT_PLAN"] = json.dumps({"faults": plan})
    env["GRAFT_RESTART_BACKOFF"] = "0.1"
    env.pop("JAX_PLATFORMS", None)  # children set their own backend env
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            f"--nproc_per_node={nproc}", f"--max_restarts={max_restarts}",
            *extra_args, str(script),
        ],
        env=env, capture_output=True, text=True, timeout=timeout_s, cwd=REPO,
    )
    return proc, marker


# marker name encodes (rank, generation): done_<rank>_<attempt>
_MARKER_CHILD = textwrap.dedent("""
    import os
    open(
        os.environ["MARKER"]
        + os.environ["RANK"] + "_" + os.environ["GRAFT_RESTART_ATTEMPT"],
        "w",
    ).write("ok")
""")


def test_launcher_rides_rendezvous_and_barrier_faults(tmp_path):
    """Generation 0: rank 1 dies in the rendezvous handshake. Generation
    1: rank 0 raises UNAVAILABLE at the coordination barrier. Generation
    2: clean. The launcher must classify both as restartable and deliver a
    complete world on the third try."""
    child = textwrap.dedent("""
        import os
        from pytorch_distributedtraining_tpu.runtime import dist
        dist.initialize()
        dist.coordination_barrier("chaos", timeout_s=120)
    """) + _MARKER_CHILD
    proc, marker = _launch(
        tmp_path, child,
        plan=[
            {"site": "dist.rendezvous", "attempt": 0, "rank": 1,
             "message": "injected rendezvous failure"},
            {"site": "collective.barrier", "attempt": 1, "rank": 0,
             "message": "UNAVAILABLE: coordination service (injected)"},
        ],
        max_restarts=2, extra_args=("--one_cpu_device_per_rank",),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    for rank in (0, 1):
        assert os.path.exists(f"{marker}{rank}_2"), proc.stderr[-2000:]
    # generation 0 never completed on the faulted rank
    assert not os.path.exists(f"{marker}1_0")
    # both failures were classified and restarted with backoff
    assert proc.stderr.count("[launch] world failed") == 2


def test_launcher_monitor_kills_worker_and_restarts(tmp_path):
    """site launch.worker: the launcher's own monitor SIGKILLs local rank
    1 mid-generation (preemption twin, jax-free children)."""
    child = textwrap.dedent("""
        import time
        time.sleep(1.5)
    """) + _MARKER_CHILD
    proc, marker = _launch(
        tmp_path, child,
        plan=[{"site": "launch.worker", "attempt": 0, "rank": 1,
               "after_s": 0.2}],
        max_restarts=1, timeout_s=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.path.exists(f"{marker}0_1")
    assert os.path.exists(f"{marker}1_1")
    assert not os.path.exists(f"{marker}1_0")  # the killed generation


def test_launcher_gives_up_on_deterministic_failure(tmp_path):
    """classify(rc=2) is DETERMINISTIC: restarting a usage error burns
    the restart budget for nothing — the launcher must fail fast."""
    child = textwrap.dedent("""
        import os, sys
        with open(os.environ["MARKER"] + "count", "a") as fh:
            fh.write("gen\\n")
        sys.exit(2)
    """)
    proc, marker = _launch(
        tmp_path, child, plan=[], nproc=1, max_restarts=3, timeout_s=60,
    )
    assert proc.returncode == 2
    assert "restarting cannot help" in proc.stderr
    with open(f"{marker}count") as fh:
        assert len(fh.readlines()) == 1  # exactly one generation ran


# ---------------------------------------------------------------------------
# shared-policy consumers (W&B sink)
# ---------------------------------------------------------------------------


class _FakeWandb:
    def __init__(self, fail_times):
        self.fail_times = fail_times
        self.calls = 0

    def init(self, **kw):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ConnectionError("connection refused")
        return object()


def test_wandb_sink_consumes_shared_retry_policy(monkeypatch):
    fake = _FakeWandb(fail_times=2)
    monkeypatch.setitem(sys.modules, "wandb", fake)
    from pytorch_distributedtraining_tpu.observe.sink import WandbSink

    sink = WandbSink(
        "proj",
        retry_policy=RetryPolicy(
            attempts=3, base_delay_s=0.0, jitter_frac=0.0
        ),
    )
    assert fake.calls == 3 and sink._run is not None


def test_wandb_sink_raises_after_exhaustion(monkeypatch):
    fake = _FakeWandb(fail_times=99)
    monkeypatch.setitem(sys.modules, "wandb", fake)
    from pytorch_distributedtraining_tpu.observe.sink import WandbSink

    with pytest.raises(RuntimeError, match="after 2 attempts"):
        WandbSink(
            "proj",
            retry_policy=RetryPolicy(
                attempts=2, base_delay_s=0.0, jitter_frac=0.0
            ),
        )
    assert fake.calls == 2
