"""Launcher shim: real multi-process rendezvous on localhost (2 ranks).

End-to-end twin of the reference's own integration test — mp.spawn over
gloo ranks on 127.0.0.1 (`/root/reference/Fairscale-DDP.py:112-133`): here
the launch CLI forks 2 python processes, each with a single virtual CPU
device, which rendezvous through `runtime.dist.initialize` (env contract)
and run a cross-process allgather.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import os
import jax

from pytorch_distributedtraining_tpu.runtime.cache import enable_compile_cache

enable_compile_cache()

from pytorch_distributedtraining_tpu.runtime import dist

dist.initialize()
assert jax.process_count() == int(os.environ["WORLD_SIZE"]), jax.process_count()

import jax.numpy as jnp
from jax.experimental import multihost_utils

ranks = multihost_utils.process_allgather(jnp.array([jax.process_index()]))
assert sorted(int(r) for r in ranks.ravel()) == [0, 1], ranks

open(os.environ["MARKER"] + os.environ["RANK"], "w").write("ok")
"""


def test_launch_cli_two_ranks(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    marker = str(tmp_path / "done_")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MARKER"] = marker
    env.pop("JAX_PLATFORMS", None)  # children set their own backend env
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--one_cpu_device_per_rank",
            str(script),
        ],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert os.path.exists(marker + "0") and os.path.exists(marker + "1")


def test_ranks_refused_on_a_host_with_chips(monkeypatch, capsys):
    """A chip belongs to one process: several local ranks that would all
    open the host's TPU are refused with a message, not left to hang."""
    import pytest

    from pytorch_distributedtraining_tpu.runtime import launch

    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert launch.shared_chip_refusal(1, False) is None  # one process: fine
    assert launch.shared_chip_refusal(4, True) is None  # ranks held to CPU
    assert "one process" in launch.shared_chip_refusal(4, False)
    with pytest.raises(SystemExit) as exit_info:
        launch.main(["--nproc_per_node=2", "child.py"])
    assert exit_info.value.code == 2
    assert "--one_cpu_device_per_rank" in capsys.readouterr().err
    with pytest.raises(RuntimeError, match="chip"):
        launch.spawn(print, nprocs=2, one_cpu_device=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # inherited: never the chip
    assert launch.shared_chip_refusal(4, False) is None
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert launch.shared_chip_refusal(4, False) is None  # no chips here


def test_launch_elastic_restart(tmp_path):
    """--max_restarts relaunches the whole world after a rank failure
    (elastic twin of torchrun --max-restarts): attempt 0 crashes rank 1,
    attempt 1 succeeds; every rank sees GRAFT_RESTART_ATTEMPT."""
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "attempt = int(os.environ['GRAFT_RESTART_ATTEMPT'])\n"
        "rank = int(os.environ['RANK'])\n"
        "if attempt == 0 and rank == 1:\n"
        "    sys.exit(3)\n"
        "open(os.environ['MARKER'] + f'{attempt}_{rank}', 'w').write('ok')\n"
    )
    env = dict(os.environ)
    env["MARKER"] = str(tmp_path / "done_")
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--max_restarts=2",
            "--one_cpu_device_per_rank", str(script),
        ],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "restart 1/2" in proc.stderr
    # generation 1 completed on both ranks
    assert os.path.exists(str(tmp_path / "done_1_0"))
    assert os.path.exists(str(tmp_path / "done_1_1"))


def test_launch_elastic_exhausted(tmp_path):
    """A world that always fails exhausts its restart budget and reports
    the child's exit code. rc=1 is UNKNOWN-class (no outage signature,
    but also no proof the failure is permanent), so the launcher keeps
    restarting; a DETERMINISTIC rc would fail fast instead — see
    test_resilience.py::test_launcher_gives_up_on_deterministic_failure."""
    script = tmp_path / "dead.py"
    script.write_text("import sys; sys.exit(1)\n")
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--max_restarts=1",
            "--one_cpu_device_per_rank", str(script),
        ],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "GRAFT_RESTART_BACKOFF": "0.1"},
    )
    assert proc.returncode == 1
    assert "restart 1/1" in proc.stderr


def test_elastic_restart_resumes_from_checkpoint(tmp_path):
    """The full recovery story: rank 1 crashes mid-training on attempt 0,
    the launcher relaunches the world, and attempt 1 restores the saved
    train state and continues from the crash step (torchrun-elastic +
    preemption-checkpoint integration, SURVEY §5 failure handling)."""
    script = tmp_path / "resumable.py"
    script.write_text(
        "import os, sys\n"
        "import numpy as np\n"
        "import jax\n"
        "from pytorch_distributedtraining_tpu.runtime.cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "from pytorch_distributedtraining_tpu.runtime import dist\n"
        "dist.initialize()\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import multihost_utils\n"
        "from jax.sharding import PartitionSpec as P\n"
        "from pytorch_distributedtraining_tpu import checkpoint_sharded, optim\n"
        "from pytorch_distributedtraining_tpu.losses import mse_loss\n"
        "from pytorch_distributedtraining_tpu.models import Net\n"
        "from pytorch_distributedtraining_tpu.parallel import (\n"
        "    DDP, TrainStep, create_train_state)\n"
        "from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh\n"
        "attempt = int(os.environ['GRAFT_RESTART_ATTEMPT'])\n"
        "rank = dist.process_index()\n"
        "mesh = make_mesh(MeshSpec(dp=2))\n"
        "model = Net(upscale_factor=2)\n"
        "tx = optim.adamw(lr=3e-3)\n"
        "def loss_fn(p, b, r, ms):\n"
        "    li, hi = b\n"
        "    return mse_loss(model.apply({'params': p}, li), hi), {}\n"
        "state, sh = create_train_state(\n"
        "    init_fn=lambda r: (model.init(r, jnp.zeros((1, 8, 8, 3)))['params'], {}),\n"
        "    tx=tx, mesh=mesh, policy=DDP())\n"
        "ckpt = os.environ['CKPT_DIR']\n"
        "start = 0\n"
        "if attempt > 0 and os.path.isdir(ckpt):\n"
        "    state = checkpoint_sharded.restore_sharded(ckpt, state)\n"
        "    start = int(state.step)\n"
        "    assert start == 2, start  # resumed exactly at the crash point\n"
        "step = TrainStep(loss_fn, tx, mesh, DDP(), state_shardings=sh,\n"
        "                 donate=False)\n"
        "rng = np.random.default_rng(0)\n"
        "hr = rng.random((8, 16, 16, 3)).astype(np.float32)\n"
        "lr = hr.reshape(8, 8, 2, 8, 2, 3).mean(axis=(2, 4))\n"
        "batch = tuple(multihost_utils.host_local_array_to_global_array(\n"
        "    x[rank * 4:(rank + 1) * 4], mesh, P('dp')) for x in (lr, hr))\n"
        "step.precompile(state, batch)\n"
        "dist.coordination_barrier('compiled')\n"
        "with mesh:\n"
        "    for i in range(start, 5):\n"
        "        state, m = step(state, batch)\n"
        "        if i == 1:\n"
        "            checkpoint_sharded.save_sharded(ckpt, state, force=True)\n"
        "            if attempt == 0 and rank == 1:\n"
        "                os._exit(17)  # hard preemption: no teardown\n"
        "assert int(state.step) == 5, int(state.step)\n"
        "open(os.environ['MARKER'] + f'{attempt}_{rank}', 'w').write(\n"
        "    str(float(m['loss'])))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MARKER"] = str(tmp_path / "done_")
    env["CKPT_DIR"] = str(tmp_path / "ckpt")
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "pytorch_distributedtraining_tpu.runtime.launch",
            "--nproc_per_node=2", "--max_restarts=1",
            "--one_cpu_device_per_rank", str(script),
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    assert "restart 1/1" in proc.stderr
    for r in range(2):
        assert os.path.exists(str(tmp_path / f"done_1_{r}"))
