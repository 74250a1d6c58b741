"""Async device prefetch: stage sharded batches ahead of the running step.

The loader's ``mesh``/``spec`` path places each batch synchronously at
yield time, so the H2D transfer (and on a multihost mesh, the per-process
slice layout) serializes with the step dispatch — the consumer pays the
copy on its own clock. :class:`DevicePrefetcher` moves that placement to a
feeder thread that keeps up to ``depth`` batches already resident as
global ``jax.Array``\\ s (``NamedSharding(mesh, spec)`` via
``jax.make_array_from_process_local_data``) ahead of the consumer, so the
transfer overlaps the previous step's compute. ``DataLoader.device_iter``
is the public entry point.

Buffer rotation is donation-safe: every staged batch is a freshly created
device array (no ring reuse), the queue drops its reference at dequeue,
and the feeder drops its own handle the moment a batch is enqueued — a
consumer may donate any yielded batch into a jitted step while later
batches are still staging.

Chaos site ``loader.stage`` (``resilience/faults.py``) fires before each
placement; on an injected (or real) staging failure the prefetcher
degrades to synchronous feeding — the failed batch and all later ones are
handed to the consumer as host data and placed in the consumer thread —
so a staging fault can neither hang the loop nor drop a batch, and a real
placement error still surfaces with a full traceback.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings

import numpy as np

from ..observe import trace as telemetry
from ..resilience.faults import fault_point

__all__ = ["DevicePrefetcher", "place_on_mesh"]


def place_on_mesh(batch, mesh, spec):
    """Place a host pytree batch as global sharded ``jax.Array``\\ s.

    Each leaf becomes ``jax.make_array_from_process_local_data(
    NamedSharding(mesh, spec), leaf)`` — this process's data is its slice
    of the global batch (multihost-correct). Already-placed leaves pass
    through untouched. A ragged batch dim (``drop_last=False`` tails) is
    padded by repeating the last sample up to the data-axis divisibility,
    same contract as the loader's synchronous path.
    """
    import jax
    from jax.sharding import NamedSharding

    # only the batch dim (spec[0]) can be padded; other dims are fixed by
    # the model and must already divide their mesh axes
    div = 1
    batch_ax = spec[0] if spec else None
    if batch_ax is not None:
        names = batch_ax if isinstance(batch_ax, (tuple, list)) else (batch_ax,)
        for n in names:
            div *= mesh.shape.get(n, 1)
    sharding = NamedSharding(mesh, spec)

    def place(a):
        if hasattr(a, "sharding") and not isinstance(a, np.ndarray):
            return a  # already a device array
        a = np.asarray(a)
        if div > 1 and a.shape[0] % div:
            pad = div - (a.shape[0] % div)
            a = np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
        return jax.make_array_from_process_local_data(sharding, a)

    return jax.tree.map(place, batch)


class _StageStats:
    """Counters shared between the feeder thread and the consumer."""

    __slots__ = ("staged", "degraded")

    def __init__(self):
        self.staged = 0
        self.degraded = False


# The feeder is a module-level function over plain state, NOT a bound
# method: a running thread is a GC root, so a method target would keep the
# prefetcher alive forever and an abandoned iterator could never be
# finalized — its feeder would park on the full queue until process exit.
# With only (source, queue, events, stats) referenced, dropping the last
# consumer reference triggers __del__ → close() → the feeder exits.
def _feed(source, mesh, spec, q, stop, drained, stats):
    t_start = time.perf_counter()  # the thread's start

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    try:
        for i, batch in enumerate(source):
            if stop.is_set():
                return
            if not stats.degraded:
                try:
                    fault_point("loader.stage", index=i)
                    item = ("dev", place_on_mesh(batch, mesh, spec))
                    stats.staged += 1
                    if i == 0:  # thread start to the first batch placed
                        telemetry.add_span(
                            "prefetch.start", "startup", t_start,
                            time.perf_counter() - t_start,
                            {"depth": q.maxsize},
                        )
                except Exception as e:
                    # degrade, don't drop: THIS batch (and all later ones)
                    # go to the consumer as host data for synchronous
                    # placement — a real persistent placement error then
                    # re-raises there, on the consumer's stack
                    stats.degraded = True
                    warnings.warn(
                        f"device prefetch staging failed "
                        f"({type(e).__name__}: {e}); degrading to "
                        "synchronous feeding",
                        RuntimeWarning,
                    )
                    item = ("host", batch)
            else:
                item = ("host", batch)
            if not put(item):
                return
            item = None  # drop the staged handle: consumer may donate it
        drained.set()
        put(("end", None))
    except BaseException as e:  # source iterator error → consumer
        drained.set()
        put(("err", e))


class DevicePrefetcher:
    """Iterator staging up to ``depth`` sharded batches ahead of the step.

    Wraps an iterator of host (or already-placed) pytree batches; see the
    module docstring for the overlap/donation/degrade contracts. Exposes
    its own wait accounting:

    - ``wait_s``  — cumulative consumer time blocked on the next batch
      (unhidden transfer + host pipeline time),
    - ``staged`` / ``yielded`` / ``degraded`` — staging telemetry,
    - :meth:`overlap_fraction` — ``1 - wait_s/elapsed`` over a timed loop.
    """

    def __init__(self, source, mesh, spec, depth: int = 2):
        if mesh is None or spec is None:
            raise ValueError("DevicePrefetcher needs both mesh and spec")
        self.mesh = mesh
        self.spec = spec
        self.depth = max(1, int(depth))
        self.wait_s = 0.0
        self.yielded = 0
        self._stats = _StageStats()
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._thread = threading.Thread(
            target=_feed,
            args=(
                iter(source), mesh, spec, self._q, self._stop,
                self._drained, self._stats,
            ),
            name="graft-device-prefetch",
            daemon=True,
        )
        # the loader's epoch-race guard reads this (see _feeder_live)
        self._thread.graft_drained = self._drained
        self._thread.start()

    @property
    def staged(self) -> int:
        return self._stats.staged

    @property
    def degraded(self) -> bool:
        return self._stats.degraded

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        # the wait IS the unhidden input time (goodput input_wait bucket)
        # — a consumer-side span, so it never double-bills the feeder
        # thread's overlapped staging
        with telemetry.span(
            "input.wait", "input", n=self.yielded, queued=self._q.qsize()
        ):
            while True:
                try:
                    kind, payload = self._q.get(timeout=0.5)
                    break
                except queue.Empty:
                    if not self._thread.is_alive():
                        # feeder hard-killed without a terminal item (action
                        # "exit"/"kill" fires os-level): surface, don't spin
                        self._drained.set()
                        raise StopIteration
            if kind == "end":
                raise StopIteration
            if kind == "err":
                raise payload
            if kind == "host":  # degraded path: place synchronously, no drop
                payload = place_on_mesh(payload, self.mesh, self.spec)
        self.wait_s += time.perf_counter() - t0
        self.yielded += 1
        return payload

    def overlap_fraction(self, elapsed_s: float) -> float | None:
        """Share of a timed consumer window NOT spent blocked on staging.

        1.0 = the input pipeline hid entirely behind compute; lower values
        measure unhidden transfer/fetch time. None before any batch.
        """
        if elapsed_s <= 0 or self.yielded == 0:
            return None
        return max(0.0, min(1.0, 1.0 - self.wait_s / elapsed_s))

    def close(self) -> None:
        """Stop the feeder and drop staged buffers (idempotent)."""
        self._stop.set()
        self._drained.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
