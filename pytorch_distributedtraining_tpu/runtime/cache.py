"""Where the persistent XLA compile cache lives — one decision, one place.

If ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of it stands
and nothing here names another directory. Otherwise the cache is
``.jax_cache/`` at the root of the checkout (git-ignored): a fixed path, so
two runs from the same checkout — or two processes of one run — share it.

jax keys every entry by the program, the jaxlib version and the backend's
platform, version and device kinds (``jax/_src/cache_key.py``), so entries a
CPU run wrote are never loaded for a TPU program that shares the directory.

This module imports jax only inside :func:`enable_compile_cache`, so
jax-free parents can ask for the path.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DISABLE_VAR = "GRAFT_COMPILE_CACHE"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
# jax's default (1 s) skips most programs of a CPU test run
MIN_COMPILE_SECS = 0.5

__all__ = [
    "cache_dir", "cache_disabled", "enable_compile_cache",
    "cache_entry_count", "jit_cache_size", "ENV_VAR", "DISABLE_VAR",
    "DEFAULT_DIR",
]


def cache_dir() -> str:
    """The compile-cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else the fixed in-checkout :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def cache_disabled() -> bool:
    """``GRAFT_COMPILE_CACHE=0`` (or ``off``/``false``) turns persistence off."""
    return os.environ.get(DISABLE_VAR, "").strip().lower() in (
        "0", "off", "false",
    )


def enable_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache; return its directory
    (None when :func:`cache_disabled`). Call before the first compile."""
    import jax

    if cache_disabled():
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECS
    )
    return path


def cache_entry_count(path: str | None) -> int:
    """Number of files under a compile-cache dir (0 for None/missing).

    Counting before and after a compile distinguishes a cache hit (count
    unchanged) from a miss (new entries) — jax has no public hit counter.
    """
    if not path:
        return 0
    try:
        return sum(len(files) for _, _, files in os.walk(path))
    except OSError:
        return 0


def jit_cache_size(*jitted) -> int:
    """Total compiled programs across jitted callables.

    The in-process twin of :func:`cache_entry_count`: snapshotting the sum
    before and after a steady-state window detects mid-run retraces even
    when the persistent cache is disabled (a serving engine asserts this
    stays flat once its buckets are warm).
    """
    return sum(int(fn._cache_size()) for fn in jitted)
