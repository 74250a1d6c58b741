"""ctypes bindings for the native fastpipe host kernels (fastpipe.cpp).

Builds ``_fastpipe.so`` with g++ on first import (cached next to the
source; rebuilt when the .cpp is newer). pybind11 is not in this image, so
the binding layer is a plain C ABI + ctypes — zero-copy in both directions
(numpy owns the buffers; C++ only reads/writes through raw pointers).

Every entry point has a numpy fallback, so the package works without a
toolchain; ``available()`` reports which path is live, and a build that
fails says so (a warning naming the compiler's error) before the numpy path
takes over. ``rebuild()`` discards whatever library is on disk and builds
the source again, raising on failure — chip_smoke.py's guard against a
stale or foreign ``_fastpipe.so`` riding along in a copied tree.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading
import warnings

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fastpipe.cpp")
_LIB_PATH = os.path.join(_DIR, "_fastpipe.so")
_lock = threading.Lock()
_lib = None
_tried = False


class BuildError(RuntimeError):
    """g++ could not build fastpipe.cpp (message carries its stderr)."""


def _build() -> str:
    if os.path.exists(_LIB_PATH) and os.path.getmtime(_LIB_PATH) >= os.path.getmtime(_SRC):
        return _LIB_PATH
    # build into a temp file then atomically rename (parallel-import safe)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
        return _LIB_PATH
    except (OSError, subprocess.SubprocessError) as e:
        if os.path.exists(tmp):
            os.unlink(tmp)
        stderr = getattr(e, "stderr", b"") or b""
        raise BuildError(
            f"{' '.join(cmd[:-2])}: {e}\n{stderr.decode(errors='replace')}"
        ) from e


def _bind(path: str):
    lib = ctypes.CDLL(path)
    lib.fp_stack.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.fp_normalize_u8.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
    ]
    lib.fp_stack_strided.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.fp_version.restype = ctypes.c_int
    return lib


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            _lib = _bind(_build())
        except (BuildError, OSError) as e:
            warnings.warn(
                f"native fastpipe unavailable, numpy fallback is live: {e}",
                RuntimeWarning,
                stacklevel=3,
            )
        return _lib


def rebuild() -> bool:
    """Discard any ``_fastpipe.so`` on disk, build the source as it stands
    and load the result. Raises :class:`BuildError` / ``OSError`` instead
    of falling back. Returns whether a library had to be discarded."""
    global _lib, _tried
    with _lock:
        found = os.path.exists(_LIB_PATH)
        if found:
            os.unlink(_LIB_PATH)
        _lib, _tried = None, True
        _lib = _bind(_build())
        return found


def available() -> bool:
    """True when the native library is (or can be) loaded."""
    return _load() is not None


def _default_threads() -> int:
    return min(8, os.cpu_count() or 1)


def fast_stack(arrays, n_threads: int | None = None) -> np.ndarray:
    """np.stack(arrays) with parallel memcpy; numpy fallback.

    All arrays must share shape and dtype (the collate hot path).
    """
    lib = _load()
    first = np.asarray(arrays[0])
    if (
        lib is None
        or len(arrays) < 2
        or first.dtype == object
        or first.nbytes < 4096  # pointer marshalling beats tiny memcpys
    ):
        return np.stack([np.asarray(a) for a in arrays])
    arrs = [np.ascontiguousarray(a) for a in arrays]
    if any(a.shape != first.shape or a.dtype != first.dtype for a in arrs):
        return np.stack(arrs)
    out = np.empty((len(arrs),) + first.shape, first.dtype)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *(a.ctypes.data for a in arrs)
    )
    lib.fp_stack(
        ptrs, len(arrs), first.nbytes, out.ctypes.data,
        n_threads or _default_threads(),
    )
    return out


def fast_stack_strided(arrays, n_threads: int | None = None) -> np.ndarray:
    """Stack row-strided views (e.g. crops of decoded images) into one
    contiguous batch without per-sample ``ascontiguousarray`` copies.

    Each array must share shape/dtype and be contiguous within a row
    (``strides[1:]`` C-order); only the leading-dim pitch may differ.
    Falls back to ``np.stack`` when the layout doesn't qualify.
    """
    lib = _load()
    first = np.asarray(arrays[0])
    row_shape = first.shape[1:]
    row_bytes = int(np.prod(row_shape, dtype=np.int64)) * first.itemsize
    c_row_strides = np.zeros(row_shape, first.dtype).strides

    def qualifies(a):
        return (
            a.shape == first.shape
            and a.dtype == first.dtype
            and a.strides[1:] == c_row_strides
            and a.strides[0] >= row_bytes
        )

    arrs = [np.asarray(a) for a in arrays]
    if lib is None or first.ndim < 2 or not all(qualifies(a) for a in arrs):
        return np.stack(arrs)
    pitches = {a.strides[0] for a in arrs}
    if len(pitches) != 1:
        return np.stack(arrs)
    out = np.empty((len(arrs),) + first.shape, first.dtype)
    ptrs = (ctypes.c_void_p * len(arrs))(*(a.ctypes.data for a in arrs))
    lib.fp_stack_strided(
        ptrs, len(arrs), first.shape[0], row_bytes, pitches.pop(),
        out.ctypes.data, n_threads or _default_threads(),
    )
    return out


def normalize_u8(
    batch: np.ndarray,
    mean=(0.485, 0.456, 0.406),
    std=(0.229, 0.224, 0.225),
    n_threads: int | None = None,
) -> np.ndarray:
    """(u8 [..., C] / 255 - mean) / std -> f32, fused + threaded."""
    batch = np.ascontiguousarray(batch, dtype=np.uint8)
    c = batch.shape[-1]
    mean = np.asarray(mean, np.float32).reshape(-1)
    std = np.asarray(std, np.float32).reshape(-1)
    if mean.size == 1:
        mean = np.repeat(mean, c)
        std = np.repeat(std, c)
    if mean.size != c or std.size != c:
        raise ValueError(f"mean/std size {mean.size} != channels {c}")
    lib = _load()
    if lib is None:
        return ((batch.astype(np.float32) / 255.0) - mean) / std
    out = np.empty(batch.shape, np.float32)
    lib.fp_normalize_u8(
        batch.ctypes.data, out.ctypes.data, batch.size // c, c,
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_threads or _default_threads(),
    )
    return out


__all__ = ["available", "fast_stack", "fast_stack_strided", "normalize_u8"]
