"""Example harness: repo-root import path + device setup.

``python examples/<script>.py`` puts ``examples/`` (this directory) on
``sys.path[0]`` but not the repo root, so ``import _bootstrap`` from any
example both resolves this module and, on import, prepends the root.

:func:`setup` pins the example to host CPU (optionally with N virtual
devices, the same trick ``tests/conftest.py`` uses) unless
``EXAMPLE_PLATFORM=tpu`` asks for real hardware.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def setup(n_devices: int = 1) -> None:
    """Call before any other jax-importing code in the example."""
    if os.environ.get("EXAMPLE_PLATFORM", "cpu") != "cpu":
        return  # run on whatever accelerator JAX finds
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    # XLA:CPU's AOT loader logs a spurious "machine features don't match"
    # ERROR on warm cache loads even on the machine that wrote the cache
    # (see __graft_entry__.py); must be set before jax is imported
    os.environ["TF_CPP_MIN_LOG_LEVEL"] = "3"
    import jax

    from pytorch_distributedtraining_tpu.runtime.dist import force_platform

    force_platform("cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    # persistent compile cache: repeat runs start fast
    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
