"""Test harness: fake an 8-device TPU-shaped mesh on host CPU.

TPU-native analogue of the reference's "gloo CPU backend + mp.spawn +
localhost rendezvous" trick for testing multi-rank without a cluster
(`/root/reference/Fairscale-DDP.py:27,122-133`): one process, 8 virtual XLA
CPU devices via ``--xla_force_host_platform_device_count``, so every sharding
/ collective path compiles and runs exactly as it would across chips.

Must run BEFORE jax initializes a backend, hence env mutation at import time.
"""

import os

# Force CPU even when the environment points JAX at a real TPU (tests always
# exercise the virtual 8-device mesh; chip_smoke.py uses the real chip).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent compilation cache: repeated suite runs (and xdist workers after
# the first run) skip XLA recompiles of identical programs — the single
# biggest contributor to suite wall time.
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from pytorch_distributedtraining_tpu.runtime.cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

# Tests exercise correctness, not runtime speed: skipping XLA's optimization
# pipeline cuts compile time (the dominant suite cost on this 1-core box).
jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def mesh8(devices8):
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(dp=8), devices=devices8)


@pytest.fixture()
def zero_mesh8(devices8):
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(fsdp=8), devices=devices8)
