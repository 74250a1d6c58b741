"""Elastic recovery: async checkpointing, crash consistency, N→M reshard,
shrink-to-survive launcher, and the recovery drill under it (ISSUE 8)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.checkpoint_sharded import (
    CheckpointManager,
    is_committed_dir,
    read_manifest,
    reshard_restore,
    restore_portable,
    runtime_stats,
    save_portable,
    save_sharded,
    snapshot_to_host,
)
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    TrainStep,
    ZeRO2,
    create_train_state,
)
from pytorch_distributedtraining_tpu.parallel.reshard import convert_layout
from pytorch_distributedtraining_tpu.resilience import FaultPlan, install_plan
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_state(devices, spec, policy_cls=ZeRO2):
    """Tiny Net + optimizer state on an arbitrary mesh shape."""
    mesh = make_mesh(spec, devices=devices)
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=1e-3, clip_grad_norm=1.0)
    policy = policy_cls(min_shard_size=1)

    def loss_fn(params, batch, rng, ms):
        lr_img, hr = batch
        out = model.apply({"params": params}, lr_img)
        return jnp.mean((out - hr) ** 2), {}

    state, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = TrainStep(
        loss_fn, tx, mesh, policy, state_shardings=sh, donate=False
    )
    rng = np.random.default_rng(0)
    hr = rng.random((8, 16, 16, 3)).astype(np.float32)
    lo = hr.reshape(8, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    return mesh, state, step, (lo, hr)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- fault plan plumbing ---------------------------------------------------


def test_fault_plan_accepts_ckpt_write_site():
    plan = FaultPlan.from_json(
        {"faults": [{"site": "ckpt.write", "action": "sleep", "arg": 0.01}]}
    )
    assert plan.rules_for("ckpt.write")
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan.from_json({"faults": [{"site": "ckpt.wrlte"}]})


# -- async checkpointing ---------------------------------------------------


class TestAsyncCheckpoint:
    def test_step_path_cost_under_20pct_of_sync_save(
        self, devices8, tmp_path
    ):
        """Acceptance: the async save's on-step-path cost (device→host
        snapshot) is < 20% of a synchronous ``save_sharded`` of the same
        state, and the background write overlaps a subsequent step."""
        mesh, state, step, batch = _make_state(devices8, MeshSpec.zero(8))
        with mesh:
            state, _ = step(state, batch)

        # median of 3: this box is a noisy 1-core CI machine
        sync_ts = []
        for i in range(3):
            t0 = time.perf_counter()
            save_sharded(str(tmp_path / f"sync{i}"), state)
            sync_ts.append(time.perf_counter() - t0)
        t_sync = sorted(sync_ts)[1]

        mgr = CheckpointManager(
            str(tmp_path / "async"), save_every=1, keep=3,
            handle_sigterm=False, async_save=True,
        )
        # wedge the background write briefly so the overlap is observable
        install_plan(FaultPlan.from_json({"faults": [
            {"site": "ckpt.write", "action": "sleep", "arg": 0.5},
        ]}))
        try:
            snap_ts = []
            for i in range(1, 4):
                mgr.wait()  # drain any previous write, off the clock
                t0 = time.perf_counter()
                mgr.save(i, state)
                dt = time.perf_counter() - t0
                if i == 1:
                    # write is wedged in the background; the train step
                    # still runs to completion on the main thread
                    assert mgr.in_flight
                    with mesh:
                        state2, m = step(state, batch)
                    assert np.isfinite(float(m["loss"]))
                    assert mgr.in_flight  # overlapped, not serialized
                    mgr.wait()
                    install_plan(None)
                else:
                    snap_ts.append(dt)
            t_step_path = sorted(snap_ts)[len(snap_ts) // 2]
            assert t_step_path < 0.2 * t_sync, (
                f"async on-step-path {t_step_path:.4f}s vs "
                f"sync {t_sync:.4f}s"
            )
            assert runtime_stats["last_snapshot_s"] is not None
            mgr.wait()
            assert mgr.all_steps() == [1, 2, 3]
        finally:
            install_plan(None)
            mgr.close()

    def test_donation_safety_snapshot_is_a_copy(self, devices8, tmp_path):
        """The snapshot must survive the source buffers being donated
        (mutated) right after save() returns."""
        mesh = make_mesh(MeshSpec.zero(8), devices=devices8)
        arr = jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8),
            NamedSharding(mesh, P("fsdp")),
        )
        snap = snapshot_to_host({"w": arr})
        want = np.arange(64, dtype=np.float32).reshape(8, 8)
        jax.block_until_ready(arr + 1.0)
        for pstr, _shape, _dtype, _spec, shards in snap.leaves:
            for index, piece in shards:
                idx = tuple(slice(a, b) for a, b in index)
                np.testing.assert_array_equal(piece, want[idx])


# -- crash consistency -----------------------------------------------------


class TestCrashConsistency:
    def test_torn_background_write_is_skipped_not_crashed_on(
        self, devices8, tmp_path
    ):
        """A ckpt.write fault inside the background writer leaves a torn
        ``.tmp`` dir; restore_latest provably skips it."""
        mesh, state, step, batch = _make_state(devices8, MeshSpec.zero(8))
        with mesh:
            state, _ = step(state, batch)
        root = tmp_path / "torn"
        mgr = CheckpointManager(
            str(root), save_every=1, keep=3,
            handle_sigterm=False, async_save=True,
        )
        install_plan(FaultPlan.from_json({"faults": [
            {"site": "ckpt.write", "action": "raise",
             "message": "injected mid-write crash"},
        ]}))
        try:
            mgr.save(1, state)
            mgr.wait()
        finally:
            install_plan(None)
        # the tear: a .tmp staging dir, no committed checkpoint
        assert os.path.isdir(str(root / "step_0000000001.tmp"))
        assert mgr.all_steps() == []
        assert "injected mid-write crash" in (
            runtime_stats["last_write_error"] or ""
        )
        assert mgr.restore_latest(jax.tree.map(lambda x: x, state)) is None

        # next save commits normally and becomes the resume source
        mgr.save(2, state)
        mgr.wait()
        assert mgr.all_steps() == [2]
        resumed = mgr.restore_latest(jax.tree.map(lambda x: x, state))
        assert resumed is not None and resumed[0] == 2
        _assert_trees_equal(resumed[1].params, state.params)
        # GC reaped the dead torn staging dir once a newer step committed
        assert not os.path.isdir(str(root / "step_0000000001.tmp"))
        mgr.close()

    def test_stale_staging_dir_never_pollutes_a_resave(
        self, devices8, tmp_path
    ):
        """A crashed earlier attempt leaves ``step_N.tmp`` full of shard
        payloads (possibly from a LARGER world). Re-saving the same step
        must clear them: the stale sidecars must neither satisfy the
        commit's rank count nor be merged into the restored state."""
        mesh, state, step, batch = _make_state(devices8, MeshSpec.zero(8))
        with mesh:
            state, _ = step(state, batch)
        root = tmp_path / "stale"
        mgr = CheckpointManager(
            str(root), save_every=1, keep=3, handle_sigterm=False,
            async_save=True,
        )
        # craft the torn leftovers of a prior 2-process attempt at step 1:
        # stale manifest (old nonce) + stale rank payloads, one of them
        # from a rank the current world does not even have
        torn = root / "step_0000000001.tmp"
        torn.mkdir(parents=True)
        (torn / "manifest.json").write_text(json.dumps(
            {"format": "graft-portable-ckpt", "version": 1, "step": 1,
             "world_size": 2, "nonce": "deadbeef" * 4, "leaves": {}}
        ))
        for r in (0, 1):
            np.savez(str(torn / f"shards_r{r}.npz"),
                     L0_S0=np.full((4,), 123.0, np.float32))
            (torn / f"shards_r{r}.json").write_text(json.dumps(
                {"rank": r, "nonce": "deadbeef" * 4, "entries": [
                    {"key": "L0_S0", "leaf": "['bogus']",
                     "index": [[0, 4]]},
                ]}
            ))
        try:
            mgr.save(1, state)
            mgr.wait()
            assert mgr.all_steps() == [1]
            committed = root / "step_0000000001"
            # the stale generation is gone, not renamed into the commit
            assert not (committed / "shards_r1.json").exists()
            man = json.loads((committed / "manifest.json").read_text())
            assert man["nonce"] != "deadbeef" * 4
            assert "['bogus']" not in man["leaves"]
            resumed = mgr.restore_latest(jax.tree.map(lambda x: x, state))
            assert resumed is not None and resumed[0] == 1
            _assert_trees_equal(resumed[1].params, state.params)
        finally:
            mgr.close()

    def test_over_budget_sync_fallback_still_gcs(self, devices8, tmp_path):
        """host_budget=0 forces every async save down the synchronous
        fallback; keep-last-k must still be enforced on that path."""
        mesh, state, step, batch = _make_state(devices8, MeshSpec.zero(8))
        mgr = CheckpointManager(
            str(tmp_path / "budget"), save_every=1, keep=1,
            handle_sigterm=False, async_save=True, host_budget_mb=0,
        )
        try:
            for s in (1, 2, 3):
                mgr.save(s, state)
            assert mgr.all_steps() == [3]
        finally:
            mgr.close()

    def test_markerless_dir_never_resume_source(self, devices8, tmp_path):
        """A portable dir with a manifest but no _COMMIT (kill between
        manifest write and commit) is not a checkpoint."""
        mesh, state, step, batch = _make_state(devices8, MeshSpec.zero(8))
        root = tmp_path / "ml"
        mgr = CheckpointManager(
            str(root), save_every=1, keep=3, handle_sigterm=False
        )
        mgr.save(3, state)
        assert mgr.all_steps() == [3]
        # craft the torn dir at a HIGHER step: the tempting-but-wrong one
        torn = root / "step_0000000009"
        torn.mkdir()
        (torn / "manifest.json").write_text(json.dumps(
            {"format": "graft-portable-ckpt", "version": 1, "step": 9,
             "world_size": 1, "leaves": {}}
        ))
        assert not is_committed_dir(str(torn))
        assert mgr.all_steps() == [3]
        resumed = mgr.restore_latest(jax.tree.map(lambda x: x, state))
        assert resumed is not None and resumed[0] == 3
        mgr.close()


# -- N -> M resharding -----------------------------------------------------


RESHARD_MATRIX = [
    # (save spec, save ndev, restore spec, restore ndev, policy)
    pytest.param(MeshSpec(dp=2), 2, MeshSpec(dp=4), 4, DDP, id="dp2->dp4"),
    pytest.param(
        MeshSpec(fsdp=4), 4, MeshSpec(fsdp=2), 2, ZeRO2, id="fsdp4->fsdp2"
    ),
    pytest.param(
        MeshSpec(dp=2, fsdp=2), 4, MeshSpec(fsdp=4), 4, ZeRO2,
        id="dpxfsdp->fsdp",
    ),
    pytest.param(
        MeshSpec(fsdp=2), 2, MeshSpec(dp=2, fsdp=4), 8, ZeRO2,
        id="fsdp2->dp2xfsdp4",
    ),
]


class TestReshardRestore:
    @pytest.mark.parametrize(
        "spec_a,n_a,spec_b,n_b,policy", RESHARD_MATRIX
    )
    def test_nm_reshard_bitwise(
        self, devices8, tmp_path, spec_a, n_a, spec_b, n_b, policy
    ):
        """Acceptance: a checkpoint saved on one mesh restores bitwise
        identically onto a different mesh shape — params AND optimizer
        moments — matching what a direct same-mesh restore gives."""
        mesh_a, state, step, batch = _make_state(
            devices8[:n_a], spec_a, policy_cls=policy
        )
        with mesh_a:
            for _ in range(2):
                state, _ = step(state, batch)
        path = save_portable(str(tmp_path / "ck"), state, step=2)
        assert read_manifest(path)["format"] == "graft-portable-ckpt"

        # direct restore (same mesh) — the bitwise reference
        direct = restore_portable(path, jax.tree.map(lambda x: x, state))
        _assert_trees_equal(direct, state)

        # resharded restore onto the other mesh shape
        mesh_b, fresh, step_b, _ = _make_state(
            devices8[:n_b], spec_b, policy_cls=policy
        )
        restored = reshard_restore(
            path, mesh_b, jax.tree.map(lambda x: x, fresh)
        )
        _assert_trees_equal(restored.params, state.params)
        _assert_trees_equal(restored.opt_state, state.opt_state)
        assert int(restored.step) == int(state.step)
        # the resharded state actually trains on the new mesh
        with mesh_b:
            cont, m = step_b(restored, batch)
        assert np.isfinite(float(m["loss"]))

    def test_pp_stacked_to_loop_and_back(self, devices8, tmp_path):
        """pp2→pp1: pp-stacked leaves ([L, ...] on a pp mesh) restore
        into a loop-layout template on a no-pp mesh, and vice versa —
        the host-side twin of scan_utils/pipeline stack conversion."""
        mesh_pp = make_mesh(MeshSpec(pp=2, fsdp=2), devices=devices8[:4])
        stacked = jax.device_put(
            np.arange(2 * 4 * 6, dtype=np.float32).reshape(2, 4, 6),
            NamedSharding(mesh_pp, P("pp", "fsdp")),
        )
        mu = jax.device_put(
            np.arange(2 * 4 * 6, dtype=np.float32).reshape(2, 4, 6) * 0.5,
            NamedSharding(mesh_pp, P("pp", "fsdp")),
        )
        state = {"params": {"h": stacked}, "mu": {"h": mu}}
        path = save_portable(str(tmp_path / "pp"), state, step=1)

        mesh1 = make_mesh(MeshSpec(fsdp=2), devices=devices8[:2])
        sds = lambda: jax.ShapeDtypeStruct(  # noqa: E731
            (4, 6), np.float32,
            sharding=NamedSharding(mesh1, P("fsdp")),
        )
        template = {
            "params": {"h_0": sds(), "h_1": sds()},
            "mu": {"h_0": sds(), "h_1": sds()},
        }
        loop = reshard_restore(path, None, template)
        want = np.asarray(stacked)
        for i in (0, 1):
            np.testing.assert_array_equal(
                np.asarray(loop["params"][f"h_{i}"]), want[i]
            )
            np.testing.assert_array_equal(
                np.asarray(loop["mu"][f"h_{i}"]), want[i] * 0.5
            )

        # and back: loop checkpoint -> stacked template (pp resume)
        path2 = save_portable(str(tmp_path / "loop"), loop, step=2)
        sds_stacked = jax.ShapeDtypeStruct(
            (2, 4, 6), np.float32,
            sharding=NamedSharding(mesh_pp, P("pp", "fsdp")),
        )
        template2 = {
            "params": {"h": sds_stacked}, "mu": {"h": sds_stacked},
        }
        restacked = reshard_restore(path2, None, template2)
        np.testing.assert_array_equal(
            np.asarray(restacked["params"]["h"]), want
        )
        np.testing.assert_array_equal(
            np.asarray(restacked["mu"]["h"]), want * 0.5
        )

    def test_indivisible_rehome_raises_named_leaf(self, devices8, tmp_path):
        """Re-homing a spec axis whose target mesh size does not divide
        the leaf's global dim is a clear, named-leaf reshard error (and
        recorded for graftcheck), not an opaque placement failure."""
        mesh2 = make_mesh(MeshSpec(fsdp=2), devices=devices8[:2])
        arr = jax.device_put(
            np.arange(6, dtype=np.float32), NamedSharding(mesh2, P("fsdp"))
        )
        path = save_portable(str(tmp_path / "indiv"), {"w": arr}, step=0)
        mesh4 = make_mesh(MeshSpec(fsdp=4), devices=devices8[:4])
        runtime_stats["manifest_mismatches"].clear()
        template = {"w": jax.ShapeDtypeStruct(
            (6,), np.float32, sharding=NamedSharding(mesh2, P("fsdp"))
        )}
        with pytest.raises(ValueError, match=r"\['w'\].*not divisible"):
            reshard_restore(path, mesh4, template)
        assert runtime_stats["manifest_mismatches"]
        runtime_stats["manifest_mismatches"].clear()

    def test_manifest_mismatch_raises_and_is_recorded(
        self, devices8, tmp_path
    ):
        mesh = make_mesh(MeshSpec.zero(2), devices=devices8[:2])
        arr = jax.device_put(
            np.ones((4, 4), np.float32), NamedSharding(mesh, P("fsdp"))
        )
        path = save_portable(str(tmp_path / "mm"), {"w": arr}, step=0)
        runtime_stats["manifest_mismatches"].clear()
        bad = {"w": jax.ShapeDtypeStruct(
            (5, 4), np.float32, sharding=NamedSharding(mesh, P("fsdp"))
        )}
        with pytest.raises(ValueError, match="disagrees with checkpoint"):
            reshard_restore(path, None, bad)
        assert runtime_stats["manifest_mismatches"]
        runtime_stats["manifest_mismatches"].clear()


def test_convert_layout_host_side():
    """parallel/reshard.py unit: unstack, stack, passthrough, absent."""
    host = {
        "['a']['h']": np.arange(12, dtype=np.float32).reshape(3, 4),
        "['b']['w_0']": np.zeros((2,), np.float32),
        "['b']['w_1']": np.ones((2,), np.float32),
        "['c']": np.full((5,), 7.0, np.float32),
    }
    targets = [
        "['a']['h_2']",        # unstack from ['a']['h']
        "['b']['w']",          # stack from w_0, w_1
        "['c']",               # passthrough
        "['d']['nope']",       # unconvertible -> absent
    ]
    want = {
        "['a']['h_2']": ((4,), np.float32),
        "['b']['w']": ((2, 2), np.float32),
        "['c']": ((5,), np.float32),
        "['d']['nope']": ((3,), np.float32),
    }
    out = convert_layout(host, targets, want)
    np.testing.assert_array_equal(out["['a']['h_2']"], host["['a']['h']"][2])
    np.testing.assert_array_equal(
        out["['b']['w']"],
        np.stack([host["['b']['w_0']"], host["['b']['w_1']"]]),
    )
    assert out["['c']"] is host["['c']"]
    assert "['d']['nope']" not in out


def test_scan_utils_host_numpy_stack():
    from pytorch_distributedtraining_tpu.models.scan_utils import (
        stack_layer_params,
        unstack_layer_params,
    )

    params = {
        "h_0": {"k": np.zeros((2, 2), np.float32)},
        "h_1": {"k": np.ones((2, 2), np.float32)},
        "head": np.ones((3,), np.float32),
    }
    stacked = stack_layer_params(params, "h_", 2, "h", xp=np)
    assert isinstance(stacked["h"]["k"], np.ndarray)
    assert stacked["h"]["k"].shape == (2, 2, 2)
    back = unstack_layer_params(stacked, "h", "h_", 2)
    np.testing.assert_array_equal(back["h_1"]["k"], params["h_1"]["k"])


# -- graftcheck runtime rules ----------------------------------------------


class TestGraftcheckRules:
    def _run(self):
        from pytorch_distributedtraining_tpu.analyze.registry import (
            AnalysisContext,
            run_rules,
        )

        return run_rules(AnalysisContext(), planes=("runtime",))

    def test_commits_silent_warns(self):
        saved = dict(runtime_stats)
        try:
            runtime_stats.update(
                save_every=100, saves_initiated=3, commits_observed=0,
                last_write_error="OSError: disk full",
            )
            report = self._run()
            names = [f.rule for f in report.findings]
            assert "ckpt-commits-silent" in names
            f = next(
                f for f in report.findings
                if f.rule == "ckpt-commits-silent"
            )
            assert "disk full" in f.evidence
            # a commit landing clears the condition
            runtime_stats["commits_observed"] = 1
            report = self._run()
            assert "ckpt-commits-silent" not in [
                f.rule for f in report.findings
            ]
        finally:
            runtime_stats.update(saved)

    def test_commits_silent_only_fires_on_rank_zero(self):
        """Only rank 0 runs the commit, so commits_observed==0 on a
        non-zero rank is the healthy steady state, not a dead writer."""
        saved = dict(runtime_stats)
        try:
            runtime_stats.update(
                save_every=100, saves_initiated=3, commits_observed=0,
                process_index=1,
            )
            report = self._run()
            assert "ckpt-commits-silent" not in [
                f.rule for f in report.findings
            ]
            runtime_stats["process_index"] = 0
            report = self._run()
            assert "ckpt-commits-silent" in [
                f.rule for f in report.findings
            ]
        finally:
            runtime_stats.update(saved)

    def test_manifest_mismatch_errors(self):
        from pytorch_distributedtraining_tpu.analyze.findings import (
            Severity,
        )

        saved = list(runtime_stats["manifest_mismatches"])
        try:
            runtime_stats["manifest_mismatches"].append(
                "['params']['w']: checkpoint (4, 4)/float32 vs template "
                "(5, 4)/float32"
            )
            report = self._run()
            f = next(
                f for f in report.findings
                if f.rule == "ckpt-manifest-mismatch"
            )
            assert f.severity is Severity.ERROR
            assert "(5, 4)" in f.evidence
        finally:
            runtime_stats["manifest_mismatches"][:] = saved


# -- elastic launcher ------------------------------------------------------


ELASTIC_SCRIPT = textwrap.dedent("""
    import os, signal, sys, time
    rank = int(os.environ.get("RANK", "0"))
    world = int(os.environ.get("WORLD_SIZE", "1"))
    attempt = int(os.environ.get("GRAFT_RESTART_ATTEMPT", "0"))
    mode = os.environ.get("GRAFT_RECOVERY_MODE", "-")
    with open(os.environ["OUT"], "a") as fh:
        fh.write(f"attempt={attempt} rank={rank} world={world} "
                 f"mode={mode}\\n")
    FAIL = os.environ.get("FAIL_HOW", "kill")
    if attempt == 0 and rank == 1:
        time.sleep(0.3)
        if FAIL == "kill":
            os.kill(os.getpid(), signal.SIGKILL)  # external preemption
        sys.exit(1)  # own crash: not an external termination
    time.sleep(0.6)
""")


def _run_elastic(tmp_path, *, fail_how: str, extra_args=()):
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT)
    out = tmp_path / "out.txt"
    env = dict(os.environ)
    env.update(
        OUT=str(out), FAIL_HOW=fail_how, GRAFT_RESTART_BACKOFF="0.05",
        GRAFT_LAUNCH_ESCALATE_S="3",
    )
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--max_restarts=2", "--elastic",
            "--min_world=1", *extra_args, str(script),
        ],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    lines = out.read_text().splitlines() if out.exists() else []
    return proc, lines


class TestElasticLauncher:
    def test_external_kill_shrinks_world(self, tmp_path):
        proc, lines = _run_elastic(tmp_path, fail_how="kill")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "elastic: shrinking world 2 -> 1" in proc.stderr
        gen1 = [l for l in lines if l.startswith("attempt=1")]
        assert gen1 == ["attempt=1 rank=0 world=1 mode=shrink"]

    def test_own_crash_retries_same_size(self, tmp_path):
        proc, lines = _run_elastic(tmp_path, fail_how="exit")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "shrinking" not in proc.stderr
        gen1 = sorted(l for l in lines if l.startswith("attempt=1"))
        assert gen1 == [
            "attempt=1 rank=0 world=2 mode=retry",
            "attempt=1 rank=1 world=2 mode=retry",
        ]

    def test_elastic_flag_validation(self, tmp_path):
        script = tmp_path / "noop.py"
        script.write_text("")
        for args, expect in (
            (["--nproc_per_node=2", "--elastic", str(script)],
             "--max_restarts"),
            # --min_world is validated against the TOTAL elastic world:
            # 3 > 1*2 rejects single-node...
            (["--nproc_per_node=2", "--max_restarts=1", "--elastic",
              "--min_world=3", str(script)], "--min_world"),
            # ...and 5 > 2*2 rejects multi-node, with the computed total
            # named in the error (not one node's nproc_per_node)
            (["--nnodes=2", "--node_rank=0", "--master_port=29573",
              "--nproc_per_node=2", "--max_restarts=1", "--elastic",
              f"--membership-dir={tmp_path / 'ms'}", "--min_world=5",
              str(script)], "nnodes*nproc_per_node=4"),
        ):
            proc = subprocess.run(
                [
                    sys.executable, "-m",
                    "pytorch_distributedtraining_tpu.runtime.launch",
                    *args,
                ],
                capture_output=True, text=True, timeout=60, cwd=REPO,
            )
            assert proc.returncode == 2, proc.stderr[-500:]
            assert expect in proc.stderr, (expect, proc.stderr[-500:])

    def test_stale_recovery_mode_env_never_inherited(self, tmp_path):
        """A stale GRAFT_RECOVERY_MODE in the LAUNCHER's own environment
        (a previous shrink's export, an outer launcher, a test harness)
        must not leak into generation-0 children: a generation launched
        without an explicit mode decision reports no mode at all."""
        script = tmp_path / "mode.py"
        script.write_text(ELASTIC_SCRIPT)
        out = tmp_path / "out.txt"
        env = dict(os.environ)
        env.update(OUT=str(out), GRAFT_RECOVERY_MODE="shrink")
        proc = subprocess.run(
            [
                sys.executable, "-m",
                "pytorch_distributedtraining_tpu.runtime.launch",
                "--nproc_per_node=1", str(script),
            ],
            env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert out.read_text().splitlines() == [
            "attempt=0 rank=0 world=1 mode=-"
        ]


# -- the recovery drill under the elastic launcher (end to end) -------------


def _drill_under_launcher(tmp_path, extra_faults=(), *, grow=False,
                          crash_step=4, timeout=300):
    """Run ``runtime/recovery_drill.py`` under the elastic launcher with a
    fault plan that (a) wedges the step-(K-1) checkpoint write inside the
    background writer, leaving a torn, uncommitted ``.tmp`` step dir, and
    (b) SIGKILLs rank 0 at step K's ``maybe_save``. Returns the drill's
    JSONL events (its own clock); skips where no multiprocess CPU world
    can be built."""
    from pytorch_distributedtraining_tpu.runtime import recovery_drill

    out = tmp_path / "events.jsonl"
    plan = {
        "faults": [
            {"site": "ckpt.write", "action": "sleep", "arg": 600,
             "rank": 0, "attempt": 0, "match": {"step": crash_step - 1}},
            {"site": "train.preempt", "action": "kill",
             "rank": 0, "attempt": 0, "match": {"step": crash_step}},
            *extra_faults,
        ]
    }
    plan_path = tmp_path / "fault_plan.json"
    plan_path.write_text(json.dumps(plan))
    env = dict(os.environ)
    env.update(
        GRAFT_FAULT_PLAN=str(plan_path),
        GRAFT_DRILL_OUT=str(out),
        GRAFT_DRILL_CKPT=str(tmp_path / "ckpt"),
        GRAFT_DRILL_STEPS=str(crash_step + 2),
        GRAFT_LAUNCH_ESCALATE_S="5",
        GRAFT_RESTART_BACKOFF="0.1",
        JAX_PLATFORMS="cpu",
        PYTHONUNBUFFERED="1",
    )
    if grow:
        # the shrunken generation dawdles so the launcher's capacity
        # probes can fire, then takes the graceful teardown
        env.update(
            GRAFT_DRILL_GROW="1",
            GRAFT_DRILL_STEP_SLEEP_S="0.25",
            GRAFT_DRILL_STEPS=str(crash_step + 12),
            GRAFT_GROW_PROBES="2",
            GRAFT_GROW_PROBE_INTERVAL_S="0.3",
            GRAFT_GROW_MIN_INTERVAL_S="3",
        )
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--max_restarts=2",
            "--elastic", "--min_world=1", *(["--grow"] if grow else []),
            recovery_drill.__file__,
        ],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    events = [json.loads(l) for l in out.read_text().splitlines() if l.strip()]
    if any(e["event"] == "skip" for e in events):
        pytest.skip("no multiprocess CPU world here")
    return events


def _shrink_facts(events, crash_step=4):
    """The shrink generation's resume, held to the crash-consistency
    contract: world 2 -> 1, mesh 4 -> 2, resumed from the last COMMITTED
    step, below every torn directory. Returns (resume event, seconds from
    the last pre-crash trained step to the first one after the resume)."""
    steps0 = [e for e in events if e["event"] == "step" and e["attempt"] == 0]
    resume = next(e for e in events if e["event"] == "resume")
    first_back = next(
        e for e in events
        if e["event"] == "step" and e["attempt"] == resume["attempt"]
    )
    assert resume["mode"] == "shrink"
    assert steps0[0]["world"] == 2 and resume["world"] == 1
    assert steps0[0]["fsdp"] == 4 and resume["fsdp"] == 2
    # torn step dir never became the resume source: the drill resumed
    # from the last COMMITTED step, two below the crash step
    assert resume["torn_dirs"], resume
    torn_steps = [
        int(d.split("_")[1].split(".")[0]) for d in resume["torn_dirs"]
    ]
    assert resume["step"] < min(torn_steps)
    assert resume["step"] == crash_step - 2
    return resume, first_back["t"] - max(e["t"] for e in steps0)


def test_recovery_drill_resumes_from_committed_step_end_to_end(tmp_path):
    """Acceptance: train.preempt kills rank 0, the elastic launcher resumes
    at the surviving world size from the latest COMMITTED checkpoint, and
    time_to_recover_s (first post-resume trained step minus last pre-crash
    one) is > 0 — with the torn dir provably not the resume source."""
    events = _drill_under_launcher(tmp_path, timeout=480)
    _, time_to_recover_s = _shrink_facts(events)
    assert time_to_recover_s > 0
    assert any(e["event"] == "done" for e in events)


# -- elastic grow-back + multi-node membership (ISSUE 11) -------------------


@pytest.mark.slow
def test_recovery_drill_grows_back_end_to_end(tmp_path):
    """Acceptance: the grow drill shrinks 2→1 on the preemption, the
    controller's capacity probes fire the hysteresis gate, the world is
    torn down gracefully (forced portable save) and relaunched at 2 with
    GRAFT_RECOVERY_MODE=grow — and the grown state is BITWISE equal to an
    independent single-device read of the same checkpoint."""
    events = _drill_under_launcher(tmp_path, grow=True, timeout=600)
    resume, _ = _shrink_facts(events)
    g_resume = next(
        e for e in events if e["event"] == "resume" and e["mode"] == "grow"
    )
    pre_grow = [
        e for e in events
        if e["event"] in ("step", "preempt_exit")
        and 0 < e["attempt"] < g_resume["attempt"]
    ]
    first_grown = next(
        e for e in events
        if e["event"] == "step" and e["attempt"] == g_resume["attempt"]
    )
    time_to_grow_s = first_grown["t"] - max(e["t"] for e in pre_grow)
    assert time_to_grow_s > 0
    assert g_resume["world"] == 2 and g_resume["fsdp"] == 4
    bit = next(e for e in events if e["event"] == "grow_bitwise")
    assert bit["ok"] is True
    # the grow generation resumed at (or past) the shrink generation's
    # resume point — a grow must never lose committed progress
    assert g_resume["step"] >= resume["step"]


@pytest.mark.slow
def test_kill_during_pre_grow_save_leaves_committed_checkpoint(tmp_path):
    """Chaos: SIGKILL the trainer INSIDE its first attempt-1 checkpoint
    write (which — depending on when the grow teardown lands — is either
    the pre-grow forced save or the last scheduled save before it). The
    torn .tmp must never become a resume source: whichever generation
    comes next resumes from the last COMMITTED step, and the run still
    grows back to the full world with a bitwise-clean reshard."""
    events = _drill_under_launcher(
        tmp_path,
        # the rule under test: the shrunken generation's FIRST save dies
        # mid-write, leaving a second torn .tmp behind
        [{"site": "ckpt.write", "action": "kill",
          "rank": 0, "attempt": 1, "at": 1}],
        grow=True,
    )
    # some generation saw the torn attempt-1 write and still resumed from
    # the last committed step BELOW it (step 2: steps 1,2 committed in
    # gen 0; step 3's writes were torn in both gen 0 and gen 1)
    resumes = [e for e in events if e["event"] == "resume"]
    torn_resume = next(
        e for e in resumes
        if any("0000000003" in d for d in e["torn_dirs"])
    )
    assert torn_resume["step"] == 2
    # and the run still grew back to the full world, bitwise-clean
    grow_resume = next(e for e in resumes if e["mode"] == "grow")
    assert grow_resume["world"] == 2 and grow_resume["fsdp"] == 4
    bit = next(e for e in events if e["event"] == "grow_bitwise")
    assert bit["ok"] is True
    assert events[-1]["event"] == "done"


MULTINODE_SCRIPT = textwrap.dedent("""
    import os, signal, sys, time
    attempt = int(os.environ.get("GRAFT_RESTART_ATTEMPT", "0"))
    node = os.environ.get("GRAFT_NODE_RANK", "?")
    rank = os.environ.get("RANK", "?")
    world = os.environ.get("WORLD_SIZE", "?")
    mode = os.environ.get("GRAFT_RECOVERY_MODE", "-")
    with open(os.environ["OUT"], "a") as fh:
        fh.write(f"attempt={attempt} node={node} rank={rank} "
                 f"world={world} mode={mode}\\n")
    if node == "1" and attempt == 0:
        time.sleep(0.4)
        os.kill(os.getpid(), signal.SIGSEGV)  # the HOST's fault
    time.sleep(2.5 if attempt else 25)
""")


def _launch_node(node_rank, script, tmp_path, extra_env, port):
    env = dict(os.environ)
    env.update(
        OUT=str(tmp_path / "out.txt"),
        GRAFT_RESTART_BACKOFF="0.05",
        GRAFT_LAUNCH_ESCALATE_S="3",
        GRAFT_MEMBERSHIP_RESULT_GRACE_S="10",
        GRAFT_MEMBERSHIP_GEN_TIMEOUT_S="60",
        **extra_env,
    )
    return subprocess.Popen(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nnodes=2", f"--node_rank={node_rank}",
            "--master_addr=127.0.0.1", f"--master_port={port}",
            "--nproc_per_node=1", "--max_restarts=2",
            "--elastic", "--grow", "--min_world=1",
            f"--membership-dir={tmp_path / 'ms'}",
            str(script),
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO,
    )


@pytest.mark.slow
def test_multinode_quarantine_excludes_host_across_grow_probes(tmp_path):
    """Two launchers share one membership store. Node 1's rank SIGSEGVs —
    a host-attributed fault — so the controller quarantines node1, shrinks
    the world onto node0, and across every subsequent grow probe node1
    stays excluded: it is never re-admitted before its backoff expires."""
    script = tmp_path / "work.py"
    script.write_text(MULTINODE_SCRIPT)
    extra = {
        "GRAFT_QUARANTINE_BASE_S": "120",
        "GRAFT_GROW_PROBES": "2",
        "GRAFT_GROW_PROBE_INTERVAL_S": "0.3",
        "GRAFT_GROW_MIN_INTERVAL_S": "5",
    }
    p0 = _launch_node(0, script, tmp_path, extra, port=29571)
    p1 = _launch_node(1, script, tmp_path, extra, port=29571)
    try:
        out0 = p0.communicate(timeout=120)
        out1 = p1.communicate(timeout=120)
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
    assert p0.returncode == 0, out0[1][-3000:]
    # node1's launcher exits 0 too: shrunk out, it idled until the
    # controller published the terminal generation
    assert p1.returncode == 0, out1[1][-3000:]
    assert "elastic: shrinking world 2 -> 1" in out0[1]
    assert "membership: quarantine host=node1" in out0[1]
    lines = (tmp_path / "out.txt").read_text().splitlines()
    # the quarantined host never ran a rank again after generation 0
    assert [l for l in lines if "node=1" in l and "attempt=0" not in l] == []
    assert "attempt=1 node=0 rank=0 world=1 mode=shrink" in lines
    # ...and was excluded from >= 2 capacity probes while quarantined
    trans = [
        json.loads(l)
        for l in (tmp_path / "ms" / "transitions.jsonl").read_text().splitlines()
    ]
    probes = [
        t for t in trans
        if t["kind"] == "grow_probe" and "node1" in t["excluded"]
    ]
    assert len(probes) >= 2, trans
    quarantines = [t for t in trans if t["kind"] == "quarantine"]
    assert [q["host"] for q in quarantines] == ["node1"]
    assert quarantines[0]["rc"] == -11


@pytest.mark.slow
def test_multinode_min_world_above_one_node_accepted(tmp_path):
    """--min_world may legitimately exceed one node's nproc_per_node (the
    floor is on the TOTAL world): 3 ranks over 2 nodes x 2 procs parses
    and launches. Only node 0 runs here — its local share exits 0, so the
    controller publishes the terminal generation and returns 0."""
    script = tmp_path / "ok.py"
    script.write_text(textwrap.dedent("""
        import os
        with open(os.environ["OUT"], "a") as fh:
            fh.write(f"rank={os.environ['RANK']} "
                     f"world={os.environ['WORLD_SIZE']}\\n")
    """))
    env = dict(os.environ)
    env.update(OUT=str(tmp_path / "out.txt"))
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nnodes=2", "--node_rank=0",
            "--master_addr=127.0.0.1", "--master_port=29572",
            "--nproc_per_node=2", "--max_restarts=1",
            "--elastic", "--min_world=3",
            f"--membership-dir={tmp_path / 'ms'}",
            str(script),
        ],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = sorted((tmp_path / "out.txt").read_text().splitlines())
    assert lines == ["rank=0 world=4", "rank=1 world=4"]
