"""Two-launcher multi-node simulation.

The reference's launch line is one `torch.distributed.launch` per node
(`/root/reference/Stoke-DDP.py:1-2`); multi-node rendezvous is two
launcher instances pointed at one MASTER_ADDR:MASTER_PORT. The twin is
exercised the same way real DCN can't be here: two
`runtime.launch` CLIs on localhost — ``--nnodes=2 --nproc_per_node=2
--node_rank={0,1}`` with a pinned port — forming one 4-rank world.

Covers: global-rank math (rank = node_rank * nproc_per_node +
local_rank), cross-launcher rendezvous, one real DDP train step over the
4-rank mesh, and fate-sharing when a rank on one node dies (local
sibling killed by its launcher; the peer node's ranks unblock via the
coordination-barrier timeout instead of hanging in the dead collective).
"""

import os
import subprocess
import sys

from pytorch_distributedtraining_tpu.runtime.dist import find_free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_CHILD = """
import os
import numpy as np
import jax

from pytorch_distributedtraining_tpu.runtime.cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from pytorch_distributedtraining_tpu.runtime import dist

# global-rank math: the launcher must have derived RANK from
# node_rank * nproc_per_node + local_rank
node_rank = int(os.environ["GRAFT_NODE_RANK"])
local_rank = int(os.environ["LOCAL_RANK"])
assert int(os.environ["RANK"]) == node_rank * 2 + local_rank, os.environ["RANK"]
assert int(os.environ["WORLD_SIZE"]) == 4

dist.initialize()
assert jax.process_count() == 4, jax.process_count()

import jax.numpy as jnp
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as P

ranks = multihost_utils.process_allgather(jnp.array([jax.process_index()]))
assert sorted(int(r) for r in ranks.ravel()) == [0, 1, 2, 3], ranks

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.parallel import DDP, TrainStep, create_train_state
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

rank = dist.process_index()
mesh = make_mesh(MeshSpec(dp=4))
model = Net(upscale_factor=2)
tx = optim.adamw(lr=3e-3)

def loss_fn(p, b, r, ms):
    li, hi = b
    return mse_loss(model.apply({"params": p}, li), hi), {}

state, sh = create_train_state(
    init_fn=lambda r: (model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {}),
    tx=tx, mesh=mesh, policy=DDP(),
)
step = TrainStep(loss_fn, tx, mesh, DDP(), state_shardings=sh, donate=False)
rng = np.random.default_rng(0)
hr = rng.random((8, 16, 16, 3)).astype(np.float32)
lr = hr.reshape(8, 8, 2, 8, 2, 3).mean(axis=(2, 4))
batch = tuple(
    multihost_utils.host_local_array_to_global_array(
        x[rank * 2:(rank + 1) * 2], mesh, P("dp")
    )
    for x in (lr, hr)
)
step.precompile(state, batch)
dist.coordination_barrier("compiled")
with mesh:
    state, m = step(state, batch)
assert int(state.step) == 1
open(os.environ["MARKER"] + os.environ["RANK"], "w").write(
    str(float(m["loss"]))
)
"""

FATE_CHILD = """
import os
import jax

from pytorch_distributedtraining_tpu.runtime.cache import enable_compile_cache
enable_compile_cache()

from pytorch_distributedtraining_tpu.runtime import dist

dist.initialize()
open(os.environ["MARKER"] + "up_" + os.environ["RANK"], "w").write("ok")
if int(os.environ["RANK"]) == 3:
    os._exit(7)  # induced hard failure on node 1
# survivors must not hang in the dead world: the barrier deadline
# converts the missing rank into a clean failure on BOTH launchers.
# Every rank has already written its "up" marker, so the deadline only
# needs to outlast rank-3's exit skew. os._exit on failure skips the
# coordination-service atexit teardown, which would otherwise wait
# ~100 s for the dead rank's shutdown call that can never come.
try:
    dist.coordination_barrier("never-forms", timeout_s=15.0)
except Exception:
    os._exit(1)
os._exit(0)
"""


def _run_two_launchers(script_path, marker, extra_env=None, timeout=420):
    """Start node-0 and node-1 launcher CLIs concurrently; return procs."""
    port = find_free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MARKER"] = marker
    env.pop("JAX_PLATFORMS", None)  # children set their own backend env
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    procs = []
    for node_rank in range(2):
        node_env = dict(env)
        node_env["GRAFT_NODE_RANK"] = str(node_rank)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m",
                    "pytorch_distributedtraining_tpu.runtime.launch",
                    "--nnodes=2", "--nproc_per_node=2",
                    f"--node_rank={node_rank}",
                    f"--master_port={port}",
                    "--one_cpu_device_per_rank",
                    str(script_path),
                ],
                env=node_env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    return outs


def test_two_launchers_form_one_world(tmp_path):
    """2 nodes x 2 ranks on localhost: rendezvous across launcher
    instances, rank math, 4-rank allgather, one DDP train step."""
    script = tmp_path / "child.py"
    script.write_text(TRAIN_CHILD)
    marker = str(tmp_path / "done_")
    results = _run_two_launchers(script, marker)
    for rc, out, err in results:
        assert rc == 0, (rc, err[-3000:])
    losses = set()
    for r in range(4):
        assert os.path.exists(marker + str(r)), f"rank {r} never finished"
        losses.add(open(marker + str(r)).read())
    assert len(losses) == 1, f"ranks disagree on the step loss: {losses}"


def test_two_launchers_fate_sharing(tmp_path):
    """Induced failure on node 1 (global rank 3): its launcher kills the
    local sibling and exits with the child's code; node 0's ranks escape
    the dead world via the barrier deadline, failing that launcher too —
    neither launcher hangs."""
    script = tmp_path / "fate.py"
    script.write_text(FATE_CHILD)
    marker = str(tmp_path / "fate_")
    results = _run_two_launchers(script, marker, timeout=420)
    (rc0, _, err0), (rc1, _, err1) = results
    # all four ranks reached the rendezvous before the induced failure
    for r in range(4):
        assert os.path.exists(marker + f"up_{r}"), f"rank {r} never joined"
    assert rc1 == 7, (rc1, err1[-2000:])  # node 1: the induced exit code
    assert rc0 != 0, (rc0, err0[-2000:])  # node 0: barrier deadline, not a hang
