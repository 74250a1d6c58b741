"""Prefetching DataLoader: host threads feeding the device mesh.

Twin of torch's multi-worker ``DataLoader`` as the reference drives it
(`/root/reference/Stoke-DDP.py:286-298` — spawn context, 16 workers;
`Fairscale-DDP.py:59-64` — pin_memory, drop_last). TPU-native differences:

- worker **threads**, not processes: decode (PIL) releases the GIL and the
  heavy math lives on-device, so threads give the parallelism without the
  spawn/pickle tax the reference pays (`torch/utils/data/worker.py:244`);
- "pin memory + H2D copy" becomes `jax.make_array_from_process_local_data`
  with a `NamedSharding`, which places each per-device slice directly and
  composes with multi-host meshes (each process contributes its slice of the
  global batch);
- `set_epoch` is driven automatically each epoch, fixing the reference's
  never-called-set_epoch shuffling bug (SURVEY §2.1).
"""

from __future__ import annotations

import multiprocessing
import queue
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

import numpy as np

from ..observe import trace as telemetry
from ..resilience.faults import fault_point
from .sampler import DistributedSampler

# Process-worker state: the dataset is shipped ONCE per worker via the
# executor initializer (torch ships it once per worker the same way,
# `torch/utils/data/_utils/worker.py`), then looked up per fetch. Module
# level because spawn pickles by reference to importable names.
_WORKER_DATASET = None


def _process_worker_init(dataset):
    global _WORKER_DATASET
    _WORKER_DATASET = dataset


def _process_worker_fetch(i):
    """``(sample, seconds)``: the worker's own fetch time rides back with
    the sample (summed per batch into the arguments of ``loader.collect``
    and ``loader.collate``)."""
    t0 = time.perf_counter()
    # chaos site: the plan crosses the spawn boundary via GRAFT_FAULT_PLAN
    # in the inherited env, so worker-crash drills work on real workers
    fault_point("loader.fetch", index=i)
    sample = _WORKER_DATASET[i]
    return sample, time.perf_counter() - t0


def stack_windows(batches, k: int):
    """Group an iterable of batches into ``[k, B, ...]`` stacks.

    The feed for :class:`~..parallel.MultiStep` (K train steps per
    dispatch): yields one stacked pytree per K consecutive batches; a
    trailing partial window is dropped (same contract as
    ``drop_last=True`` — MultiStep is compiled for a fixed K).

    ::

        multi = MultiStep(step, k=8)
        for stacked in stack_windows(loader, 8):
            state, metrics = multi(state, stacked)
    """
    if k < 1:  # validate NOW, not at first iteration of the generator
        raise ValueError(f"k must be >= 1, got {k}")

    def gen():
        import jax
        import jax.numpy as jnp

        def stack(*xs):
            # device-placed (possibly multi-host global) batches stack as
            # an XLA op — np.stack would pull them to host (crashing on
            # arrays spanning non-addressable devices, and round-tripping
            # otherwise)
            if hasattr(xs[0], "sharding"):
                return jnp.stack(xs)
            return np.stack(xs)

        window = []
        for b in batches:
            window.append(b)
            if len(window) == k:
                yield jax.tree.map(stack, *window)
                window = []

    return gen()


def default_collate(samples):
    """Stack a list of samples; tuples/lists/namedtuples collate per-field.

    Leaf stacking goes through the native fastpipe collate (csrc/: parallel
    memcpy across samples — the torch C++ collate/pin-memory twin) when the
    extension is built, else numpy.
    """
    first = samples[0]
    if isinstance(first, tuple) and hasattr(first, "_fields"):  # namedtuple
        return type(first)(
            *(default_collate([s[i] for s in samples]) for i in range(len(first)))
        )
    if isinstance(first, (tuple, list)):
        return type(first)(
            default_collate([s[i] for s in samples]) for i in range(len(first))
        )
    if isinstance(first, dict):
        return {k: default_collate([s[k] for s in samples]) for k in first}
    from .. import csrc

    return csrc.fast_stack(samples)


class DataLoader:
    """Iterates `(batch, ...)` pytrees of numpy (or sharded jax) arrays.

    Args mirror the torch surface the reference uses; ``pin_memory`` is
    accepted for parity and ignored (the TPU runtime has no
    pageable/pinned distinction on this path).

    Workers default to **threads** (PIL decode releases the GIL; no
    spawn/pickle tax). ``multiprocessing_context="spawn"|"fork"|
    "forkserver"`` switches to real worker **processes** — the escape
    hatch for GIL-bound user transforms (numpy-heavy augmentation in
    Python loops), honoring the reference's spawn surface
    (`Stoke-DDP.py:290,296`). The dataset must be picklable; it ships to
    each worker once. ``persistent_workers=True`` keeps the process pool
    alive across epochs (spawn startup is ~1 s/worker, once per
    ``__iter__`` otherwise). As with torch's spawn context, the entry
    script must be import-safe (``if __name__ == "__main__"`` guard) —
    spawn workers re-import it.

    If ``mesh`` and ``spec`` are given, each batch is returned as a global
    jax.Array laid out by ``NamedSharding(mesh, spec)`` — this process's
    batch is treated as its per-process slice of the global batch.
    """

    def __init__(
        self,
        dataset,
        batch_size: int = 1,
        shuffle: bool = False,
        sampler: DistributedSampler | None = None,
        num_workers: int = 0,
        drop_last: bool = False,
        collate_fn=None,
        prefetch: int = 2,
        seed: int = 0,
        mesh=None,
        spec=None,
        pin_memory: bool = False,  # parity no-op
        persistent_workers: bool = False,
        multiprocessing_context=None,  # None/"thread" -> threads
        auto_set_epoch: bool = True,
        device_prefetch: int = 0,
    ):
        if sampler is not None and shuffle:
            raise ValueError("provide either sampler or shuffle, not both")
        if (mesh is None) != (spec is None):
            raise ValueError("mesh and spec must be given together")
        if device_prefetch and mesh is None:
            raise ValueError("device_prefetch requires mesh and spec")
        ctx = multiprocessing_context
        if ctx is not None and not isinstance(ctx, str):
            # torch also accepts a context object; keep its start method
            ctx = getattr(ctx, "get_start_method", lambda: None)() or str(ctx)
        if ctx not in (None, "thread", "spawn", "fork", "forkserver"):
            raise ValueError(
                f"multiprocessing_context={multiprocessing_context!r}: "
                "expected None/'thread' (worker threads) or "
                "'spawn'/'fork'/'forkserver' (worker processes)"
            )
        self._mp_context = None if ctx == "thread" else ctx
        self.persistent_workers = bool(persistent_workers)
        self._pool = None  # live persistent executor, if any
        self._forwarded_epoch = None  # last epoch pushed to the transform
        self._feeders: list = []  # live prefetch feeders (epoch-race guard)
        self._warned_live_epoch = False
        self._pool_built_epoch = None  # transform epoch a live pool pickled
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.sampler = sampler
        self.num_workers = num_workers
        self.drop_last = drop_last
        self.collate_fn = collate_fn or default_collate
        self.prefetch = max(1, prefetch)
        self.seed = seed
        self.mesh = mesh
        self.spec = spec
        self.device_prefetch = max(0, int(device_prefetch))
        self.auto_set_epoch = auto_set_epoch
        # batches produced so far, counted by the one thread that produces
        # them: the ``n`` argument of the loader's spans
        self._batches = 0
        self._epoch = 0
        self._explicit_epoch = False  # set_epoch() ever called by the user
        self._iter_count = 0
        self._warned_desync = False

    def __len__(self) -> int:
        n = len(self.sampler) if self.sampler is not None else len(self.dataset)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        self._explicit_epoch = True
        if self.sampler is not None:
            self.sampler.set_epoch(epoch)
        self._sync_transform_epoch()

    def _sync_transform_epoch(self) -> None:
        """Forward the loader's epoch to an epoch-aware dataset transform.

        The sampler's forgotten-``set_epoch`` bug class applies equally to
        augmentation (`data/transforms.py`): without this plumbing every
        epoch replays epoch-0 crops. A persistent process pool pickled the
        dataset (transform included) at pool creation, so when the epoch
        moved, the pool restarts at the next build — correctness over
        worker reuse, and only when an epoch-aware transform is present.
        """
        tf = getattr(self.dataset, "transform", None)
        if tf is None or not hasattr(tf, "set_epoch"):
            return
        # The transform's epoch is LIVE state shared with fetch workers —
        # unlike the sampler order, which __iter__ snapshots. Moving it
        # while a previous iteration's prefetch is still in flight applies
        # the new epoch's augmentation to the old epoch's trailing
        # batches. Detect and warn (once): drain or abandon the previous
        # iterator before calling set_epoch()/iter(). (ADVICE r4.)
        self._feeders = [t for t in self._feeders if self._feeder_live(t)]
        if (
            self._feeders
            and self._forwarded_epoch is not None
            and self._forwarded_epoch != self._epoch
            and not self._warned_live_epoch
        ):
            self._warned_live_epoch = True
            import warnings

            warnings.warn(
                f"transform epoch moved {self._forwarded_epoch} -> "
                f"{self._epoch} while a previous iteration's prefetch is "
                "still in flight; its trailing fetches will use the new "
                "epoch's augmentation (sampler order is snapshotted per "
                "iteration, transform state is not). Exhaust or drop the "
                "previous iterator before set_epoch()/iter().",
                RuntimeWarning,
                stacklevel=3,
            )
        tf.set_epoch(self._epoch)
        self._forwarded_epoch = self._epoch
        if self._pool is not None and self._pool_built_epoch != self._epoch:
            self.shutdown_workers()

    @staticmethod
    def _feeder_live(t) -> bool:
        """A feeder is a hazard only while fetches can still run: alive
        AND not yet fully drained (the drained flag is set before _END,
        so a consumer that just finished list(loader) never counts)."""
        return t.is_alive() and not t.graft_drained.is_set()

    def _index_batches(self):
        if self.sampler is not None:
            order = list(self.sampler)
        elif self.shuffle:
            order = np.random.default_rng(self.seed + self._epoch).permutation(
                len(self.dataset)
            ).tolist()
        else:
            order = list(range(len(self.dataset)))
        for i in range(0, len(order), self.batch_size):
            batch = order[i : i + self.batch_size]
            if self.drop_last and len(batch) < self.batch_size:
                return
            yield batch

    def _to_device(self, batch):
        if self.mesh is None:
            return batch
        from .prefetch import place_on_mesh

        # ragged-tail padding + per-process global placement live in
        # prefetch.place_on_mesh — one implementation shared by this
        # synchronous path and the staged device_iter path
        return place_on_mesh(batch, self.mesh, self.spec)

    def _begin_epoch(self) -> list:
        """Shared iteration prologue: epoch sync + index-order snapshot."""
        # the transform must see THIS epoch before the auto bump below
        # (fetches run lazily, after the bump has already moved _epoch)
        self._sync_transform_epoch()
        # snapshot the index order NOW (generators run lazily; the epoch
        # bump below must not leak into this epoch's shuffle)
        batches = list(self._index_batches())
        self._iter_count += 1
        if self.auto_set_epoch:
            # fixes the reference's never-called-set_epoch bug; NOTE this
            # makes shuffles depend on iter() count — in multi-process
            # training either keep iter() calls symmetric across ranks or
            # call set_epoch(e) explicitly each epoch (which resets the
            # counter, restoring determinism for resume)
            self._maybe_warn_iter_count_hazard()
            self._epoch += 1
            if self.sampler is not None:
                self.sampler.set_epoch(self._epoch)
        return batches

    def __iter__(self):
        if self.device_prefetch > 0:
            return self.device_iter(depth=self.device_prefetch)
        return self._make_iter(self._begin_epoch())

    def device_iter(self, mesh=None, spec=None, depth: int = 2):
        """Iterate device-staged batches: a :class:`~.prefetch
        .DevicePrefetcher` keeps up to ``depth`` sharded global batches
        placed on the mesh ahead of the consumer, so the H2D transfer
        overlaps the running step instead of serializing with it.

        ``mesh``/``spec`` default to the loader's own. On a
        ``loader.stage`` fault (or a real staging failure) the iterator
        degrades to synchronous feeding — no hang, no dropped batch.
        """
        from .prefetch import DevicePrefetcher

        mesh = self.mesh if mesh is None else mesh
        spec = self.spec if spec is None else spec
        if mesh is None or spec is None:
            raise ValueError(
                "device_iter needs mesh and spec (constructor or call)"
            )
        pf = DevicePrefetcher(
            self._make_iter(self._begin_epoch(), to_device=False),
            mesh, spec, depth=depth,
        )
        # the prefetcher's feeder pulls fetches ahead of the consumer, so
        # it is an epoch-race hazard exactly like a pooled feeder — even
        # on the num_workers=0 path, which is otherwise fully lazy
        self._feeders = [th for th in self._feeders if self._feeder_live(th)]
        self._feeders.append(pf._thread)
        return pf

    def _maybe_warn_iter_count_hazard(self):
        """One-shot warning for the auto_set_epoch desync hazard.

        With ``auto_set_epoch`` the shuffle seed follows the number of
        ``iter()`` calls on this process; in multi-process training an
        asymmetric ``iter()`` (one rank re-creating an iterator, or a
        mid-epoch resume) silently desyncs the shards across ranks. Warn
        once, on the second auto-bumped epoch of a multi-process run where
        the user never called ``set_epoch`` explicitly.
        """
        if self._warned_desync or self._explicit_epoch or self._iter_count < 2:
            return
        if self.sampler is None and not self.shuffle:
            return  # ordering is epoch-independent; no desync possible
        if self.sampler is not None and not getattr(self.sampler, "shuffle", True):
            return  # unshuffled sampler ignores the epoch entirely
        from ..runtime.dist import process_count_if_initialized

        # no jax.process_count() here: that would init a backend (and on
        # this image possibly hang on a TPU claim) from a warning check
        if process_count_if_initialized() <= 1:
            return
        self._warned_desync = True
        import warnings

        warnings.warn(
            "DataLoader.auto_set_epoch ties the shuffle epoch to the number "
            "of iter() calls on this process; with multiple processes an "
            "asymmetric iter() (or mid-epoch resume) silently desyncs the "
            "per-rank shards. Call loader.set_epoch(epoch) explicitly each "
            "epoch to pin the shuffle (this also restores determinism for "
            "resume).",
            RuntimeWarning,
            stacklevel=3,
        )

    def _get_pool(self):
        """Executor + fetch fn: threads by default, processes when a
        multiprocessing context was requested (the GIL escape hatch)."""
        if self._mp_context is None:
            def _thread_fetch(i):
                t0 = time.perf_counter()
                fault_point("loader.fetch", index=i)
                sample = self.dataset[i]
                return sample, time.perf_counter() - t0

            return (
                ThreadPoolExecutor(max_workers=self.num_workers),
                _thread_fetch,
                False,
            )
        if self._pool is not None:
            if getattr(self._pool, "_broken", False):
                # a worker died (OOM-kill, segfault): a broken executor
                # fails every submit forever — replace it, don't cache it
                self._pool.shutdown(wait=False, cancel_futures=True)
                self._pool = None
            else:
                return self._pool, _process_worker_fetch, True
        pool = ProcessPoolExecutor(
            max_workers=self.num_workers,
            mp_context=multiprocessing.get_context(self._mp_context),
            initializer=_process_worker_init,
            initargs=(self.dataset,),
        )
        if self.persistent_workers:
            self._pool = pool
            self._pool_built_epoch = self._forwarded_epoch
        return pool, _process_worker_fetch, self.persistent_workers

    def shutdown_workers(self):
        """Tear down a persistent process pool (idempotent)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None

    def __del__(self):
        try:
            self.shutdown_workers()
        except Exception:
            pass

    def _make_iter(self, batches, to_device: bool = True):
        # to_device=False yields host batches for the DevicePrefetcher,
        # which stages them asynchronously instead
        if self.num_workers <= 0:
            for idxs in batches:
                # synchronous fetch+collate = unoverlapped input time
                with telemetry.span("input.fetch", "input", n=self._batches):
                    item = self.collate_fn([self.dataset[i] for i in idxs])
                self._batches += 1
                yield self._to_device(item) if to_device else item
            return

        # pooled fetch: workers load samples, a feeder thread keeps
        # `prefetch` collated batches in flight ahead of the consumer
        t_pool, had = time.perf_counter(), self._pool
        pool, fetch, keep_pool = self._get_pool()
        fresh = pool is not had  # workers to start, not a pool kept up
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        _END, _ERR = object(), object()

        def put(item) -> bool:
            # bounded put that aborts when the consumer abandoned the
            # iterator — otherwise the feeder blocks on a full queue forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        drained = threading.Event()  # set BEFORE _END: no fetch can
        # still be in flight, so the epoch-race guard must not count a
        # fully-drained feeder whose thread is merely not yet reaped
        # (is_alive() alone races with the consumer seeing _END)

        def produce(futs):
            """One batch on the feeder thread: ``loader.collect`` is
            blocked in the workers' ``Future.result()``, ``loader.collate``
            holds the GIL the dispatch thread also needs. The workers' own
            fetch seconds are known once collected: they ride on the ring's
            ``collect`` record and, in a profile (an annotation's arguments
            are fixed when it opens), on the same batch's ``collate``."""
            n = self._batches
            with telemetry.span("loader.collect", "input", n=n) as collect:
                fetched = [f.result() for f in futs]
                worker_s = sum(seconds for _, seconds in fetched)
                collect.set(worker_s=worker_s)
            with telemetry.span(
                "loader.collate", "input", n=n, worker_s=worker_s
            ):
                item = self.collate_fn([sample for sample, _ in fetched])
            self._batches = n + 1
            return item

        def feeder():
            try:
                from collections import deque

                pending = deque()
                lookahead = self.prefetch + 1
                for idxs in batches:
                    if stop.is_set():
                        return
                    pending.append([pool.submit(fetch, i) for i in idxs])
                    if len(pending) >= lookahead:
                        if not put(produce(pending.popleft())):
                            return
                while pending:
                    if not put(produce(pending.popleft())):
                        return
                drained.set()
                put(_END)
            except BaseException as e:  # propagate to consumer
                put((_ERR, e))

        t = threading.Thread(target=feeder, daemon=True)
        t.graft_drained = drained
        self._feeders = [th for th in self._feeders if self._feeder_live(th)]
        self._feeders.append(t)
        t.start()
        try:
            while True:
                # consumer blocked on the feeder = input_wait bucket
                with telemetry.span("input.wait", "input", queued=q.qsize()):
                    item = q.get()
                if fresh:  # pool creation to the workers' first batch
                    fresh = False
                    telemetry.add_span(
                        "loader.start_workers", "startup", t_pool,
                        time.perf_counter() - t_pool, {
                            "workers": self.num_workers,
                            "context": self._mp_context or "thread",
                        },
                    )
                if item is _END:
                    return
                if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                    raise item[1]
                yield self._to_device(item) if to_device else item
        finally:
            stop.set()
            if not keep_pool:
                pool.shutdown(wait=False, cancel_futures=True)
