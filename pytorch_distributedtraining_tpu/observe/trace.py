"""Structured span telemetry: the shared event model under every timer.

The repo's observability grew as point tools — step timers, HLO audits,
JSONL sinks — none of which share an event vocabulary, so a bench record
can say *how fast* a run was but not *where the time went*. This module
is the substrate they now all feed:

- :func:`span` — a context manager that names a duration of the program.
  Every span is a ``jax.profiler.TraceAnnotation("graft/<name>")``,
  always, with no knob: whenever anyone takes a profile (the benchmark's
  ``--trace 1``, ``OnDemandProfiler``, an operator's
  ``jax.profiler.trace``) the program's spans are in it, on the
  profiler's clock, nested per thread, with their attributes (the
  optimizer-step number ``step=n`` on dispatch and facade spans) as
  arguments. With no profiler session an annotation is a flag test.
  With telemetry ON the span is also recorded, categorized, into a
  thread-safe bounded ring buffer. Nesting is tracked per-thread
  (``depth``), so ledgers can account top-level time without double
  counting children.
- :func:`instant` — zero-duration events (fault injections, recompiles,
  preemption signals) on the same timeline.
- :func:`export_chrome_trace` — the buffer as Chrome trace-event JSON
  (``ph: X/i/M``), loadable in Perfetto / ``chrome://tracing`` and
  summarizable by ``benchmarks/trace_summary.py`` alongside
  ``jax.profiler`` traces.
- the crash **flight recorder** — the last N records flushed to a
  per-process file under :func:`run_dir` on an unhandled exception or a
  fault-site trip, so the launcher's restart gate can name what the
  dying step was doing (``runtime/launch.py`` reads these files).

Stdlib-only by contract: the bench parent and the launcher (both jax-free)
may import this, and package import must not touch a backend
(``tests/test_import_hygiene.py``). ``TraceAnnotation`` is found through
``sys.modules`` at first use; while jax is not loaded a span is the null
span. Telemetry-off cost is one annotation object per span (under half a
microsecond) — cheap enough to leave the instrumentation in production
code paths.

The span names of the program (``graft/<name>`` in a profile):
``TrainStep|MultiStep|EvalStep|PipelineStep|CompressedGradStep|
HierGradStep.dispatch`` (``.compile+dispatch`` the first time);
``facade.model|loss|backward|step|detach_and_sync_loss|fused_step`` with
``facade.backward.grad``, ``facade.step.flush_micros|materialize_lazies|
lr|apply|fused``, ``facade.note_loss``, ``facade.shard_batch``,
``facade.forward``, ``facade.loss.compute``, ``facade.loss_fetch.flush``;
``input.fetch``, ``input.wait``, ``loader.collect``, ``loader.collate``;
``checkpoint.write|snapshot|wait``, ``preempt.agreement``;
``serve.prefill|decode|spec_verify|tile.dispatch``.

Env knobs (mirrored by ``TPUConfig.telemetry`` / ``TPUConfig.trace_dir``
through the stoke facade, and by both drivers' ``--trace``):

- ``GRAFT_TELEMETRY`` = 1/0 — enable the ring buffer (span collection,
  Chrome export) + crash handler. Annotations need no knob.
- ``GRAFT_TRACE`` = a directory — implies telemetry, and names where
  the Chrome trace JSON is exported.
- ``GRAFT_RUN_DIR`` — run-scoped scratch directory (default
  ``<tempfile.gettempdir()>/graft-runs/<pid>``, so it follows ``TMPDIR``)
  shared by metric sinks, flight-recorder files and per-rank step logs.
"""

from __future__ import annotations

import collections
import json
import os
import socket
import sys
import tempfile
import threading
import time
import traceback

__all__ = [
    "Tracer",
    "span",
    "instant",
    "add_span",
    "dispatch_span",
    "bucket_dispatch_span",
    "note_recompile",
    "enable",
    "disable",
    "enabled",
    "configure_from_env",
    "records",
    "clear",
    "export_chrome_trace",
    "run_dir",
    "flight_record_path",
    "flush_flight_record",
    "install_crash_handler",
    "read_flight_records",
    "CATEGORIES",
]

_TRUTHY = ("1", "true", "on", "yes")

# the span categories the goodput ledger knows how to bucket; span() accepts
# any string, but sticking to these keeps time_breakdown exhaustive
CATEGORIES = (
    "step",        # compiled-step dispatch + device sync -> productive
    "compile",     # trace/lower/compile, warmup first-calls
    "input",       # blocked on the input pipeline
    "checkpoint",  # checkpoint write windows
    "collective",  # explicit cross-process sync (barriers, agreements)
    "outage",      # riding a pool outage / retry backoff
    "fault",       # injected-fault instants (resilience/faults.py)
    "membership",  # elastic membership transitions (runtime/membership.py)
    "other",
)


def run_dir() -> str:
    """The run-scoped scratch directory, created on first use.

    ``GRAFT_RUN_DIR`` names it explicitly (the launcher exports one shared
    dir to every rank so rank-0 aggregation and the restart gate see all
    processes); the default is per-process, ``graft-runs/<pid>`` under the
    system's temporary directory (``tempfile.gettempdir()``, which follows
    ``TMPDIR``), so library defaults never litter the repo checkout (the
    committed ``metrics.jsonl`` bug) and a harness with a ``TMPDIR`` of
    its own keeps them.
    """
    path = os.environ.get("GRAFT_RUN_DIR") or os.path.join(
        tempfile.gettempdir(), "graft-runs", str(os.getpid())
    )
    os.makedirs(path, exist_ok=True)
    return path


def _rank() -> int:
    """Best-effort process rank WITHOUT touching jax (no backend init)."""
    for var in ("GRAFT_RANK", "JAX_PROCESS_ID", "RANK"):
        raw = os.environ.get(var)
        if raw is not None:
            try:
                return int(raw)
            except ValueError:
                pass
    return 0


def _host() -> str:
    """Best-effort host identity, matching the launcher's membership ids:
    ``GRAFT_HOST_ID`` explicit, else ``node<GRAFT_NODE_RANK>`` (what
    ``dist.initialize`` writes into the membership store), else the
    hostname — so a merged fleet trace's lanes line up with the
    membership store's health/quarantine records by name."""
    explicit = os.environ.get("GRAFT_HOST_ID")
    if explicit:
        return explicit
    node = os.environ.get("GRAFT_NODE_RANK")
    if node is not None:
        return f"node{node}"
    try:
        return socket.gethostname() or "host?"
    except OSError:
        return "host?"


class Tracer:
    """Thread-safe bounded span/event recorder.

    Records are plain dicts (json-ready):

    - span:  ``{"name", "cat", "t0", "dur", "tid", "depth", "attrs"}``
    - event: ``{"name", "cat", "t0", "dur": 0.0, "tid", "depth",
      "attrs", "instant": True}``

    ``t0`` is ``time.perf_counter()`` — monotonic, comparable across the
    process's own timestamps (ledger windows use the same clock). The
    export maps it onto the trace's own zero.
    """

    def __init__(self, capacity: int = 8192):
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.enabled = False
        self.capacity = capacity
        self.dropped = 0  # records evicted by the ring bound

    # -- recording -----------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _append(self, rec: dict) -> None:
        with self._lock:
            if len(self._buf) == self.capacity:
                self.dropped += 1
            self._buf.append(rec)

    def add_span(
        self, name: str, cat: str, t0: float, dur: float,
        attrs: dict | None = None, depth: int | None = None,
    ) -> None:
        """Record an externally-timed span into the ring (it cannot be
        an annotation after the fact: the program's own sites use
        ``with span(...)``)."""
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat, "t0": t0, "dur": max(0.0, dur),
            "tid": threading.get_ident(),
            "depth": len(self._stack()) if depth is None else depth,
            "attrs": dict(attrs) if attrs else {},
        })

    def instant(self, name: str, cat: str = "other", **attrs) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat, "t0": time.perf_counter(),
            "dur": 0.0, "tid": threading.get_ident(),
            "depth": len(self._stack()), "attrs": attrs, "instant": True,
        })

    def span(self, name: str, cat: str = "other", **attrs):
        """Context manager recording one duration span (an annotation
        only while this tracer is off)."""
        return _open(self, name, cat, attrs)

    # -- inspection ----------------------------------------------------

    def records(self) -> list:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    def open_spans(self) -> list:
        """The current thread's in-flight span frames, innermost last."""
        return [
            {"name": s.name, "cat": s.cat, "t0": s.t0, "attrs": s.attrs}
            for s in self._stack()
        ]

    # -- export --------------------------------------------------------

    def chrome_events(self, process_name: str = "graft-telemetry") -> list:
        """The buffer as Chrome trace-event dicts (ts/dur in µs).

        Timestamps are re-zeroed to the earliest record so Perfetto opens
        at the data; ``pid`` is the OS pid and every recording thread gets
        a named lane, matching what ``benchmarks/trace_summary.py``
        expects from any ``*.trace.json``.
        """
        recs = self.records()
        pid = os.getpid()
        # host + rank ride in the process metadata so merged fleet traces
        # (observe/fleet.py) can lane by identity instead of colliding on
        # whatever pids two hosts happened to hand out
        events = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {
                "name": f"{process_name} (rank {_rank()})",
                "host": _host(), "rank": _rank(),
            },
        }]
        if not recs:
            return events
        base = min(r["t0"] for r in recs)
        tids = {}
        for r in recs:
            tid = tids.setdefault(r["tid"], len(tids))
        for raw, tid in tids.items():
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": f"thread-{raw}"},
            })
        for r in recs:
            ev = {
                "name": r["name"], "cat": r["cat"], "pid": pid,
                "tid": tids[r["tid"]],
                "ts": round((r["t0"] - base) * 1e6, 3),
                "args": {k: _jsonable(v) for k, v in r["attrs"].items()},
            }
            if r.get("instant"):
                ev["ph"] = "i"
                ev["s"] = "t"  # thread-scoped instant
            else:
                ev["ph"] = "X"
                ev["dur"] = round(r["dur"] * 1e6, 3)
                # nesting depth survives the export (viewers ignore the
                # unknown key) so fleet.lane_ledgers can rebuild the
                # top-level-only goodput billing from a merged trace
                ev["depth"] = int(r.get("depth", 0))
            events.append(ev)
        return events

    def export_chrome_trace(self, path: str) -> str:
        """Write the buffer as a Chrome trace-event JSON file.

        ``graftMeta`` anchors the trace for the fleet merge: record
        timestamps are perf_counter-based and re-zeroed, so ``wall_t0``
        stamps what this host's wall clock read at the trace's zero —
        the hook the clock-offset re-basing needs.
        """
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        recs = self.records()
        base = min((r["t0"] for r in recs), default=time.perf_counter())
        wall_t0 = time.time() - (time.perf_counter() - base)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({
                "traceEvents": self.chrome_events(),
                "displayTimeUnit": "ms",
                "graftMeta": {
                    "host": _host(), "rank": _rank(), "pid": os.getpid(),
                    "wall_t0": wall_t0,
                },
            }, fh)
        return path


class _NullSpanType:
    """A span while jax is not loaded: one shared no-op context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):  # parity with _LiveSpan
        return self


_NULL_SPAN = _NullSpanType()

ANNOTATION_PREFIX = "graft/"  # the program's spans in a profile
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_type():
    """``jax.profiler.TraceAnnotation`` with a span's ``set``, found
    through ``sys.modules`` (this module imports no jax) and resolved
    once; None while jax is not loaded (the launcher, the bench parent)."""
    global _ANNOTATION
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None

    class _Annotation(profiler.TraceAnnotation):
        __slots__ = ()

        def set(self, **attrs):  # arguments are fixed at construction
            return self

    _ANNOTATION = _Annotation
    return _Annotation


def _annotate(name: str, attrs: dict):
    """The span as a trace annotation only: ``graft/<name>`` with
    ``attrs`` as its arguments. A flag test while no profile is taken."""
    cls = _ANNOTATION or _annotation_type()
    if cls is None:
        return _NULL_SPAN
    return cls(ANNOTATION_PREFIX + name, **attrs)


def _open(tracer: "Tracer", name: str, cat: str, attrs: dict):
    """A span: an annotation only while ``tracer`` is off, a ring record
    (with its annotation inside) while it is on."""
    if not tracer.enabled:
        return _annotate(name, attrs)
    return _LiveSpan(tracer, name, cat, attrs)


class _LiveSpan:
    """A span on both clocks: the ring's (``perf_counter``) and, through
    its annotation, the profiler's."""

    __slots__ = ("tracer", "name", "cat", "attrs", "t0", "_depth", "_ann")

    def __init__(self, tracer: Tracer, name: str, cat: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.t0 = 0.0

    def set(self, **attrs):
        """Attach attrs discovered mid-span (e.g. a batch shape)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = self.tracer._stack()
        self._depth = len(stack)
        stack.append(self)
        self._ann = _annotate(self.name, self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # mis-nested exit (generator teardown)
            stack.remove(self)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.tracer.add_span(
            self.name, self.cat, self.t0, dur, self.attrs, depth=self._depth
        )
        return False


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# -- module-level default tracer ---------------------------------------

_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    return _TRACER.enabled


def enable(capacity: int | None = None, crash_handler: bool = True) -> Tracer:
    """Turn span collection on (idempotent). ``capacity`` resizes the
    ring buffer; the crash handler hooks ``sys.excepthook`` so a dying
    process leaves a flight record."""
    if capacity is not None and capacity != _TRACER.capacity:
        with _TRACER._lock:
            _TRACER._buf = collections.deque(_TRACER._buf, maxlen=capacity)
            _TRACER.capacity = capacity
    _TRACER.enabled = True
    if crash_handler:
        install_crash_handler()
    return _TRACER


def disable() -> None:
    _TRACER.enabled = False


def configure_from_env(env: dict | None = None) -> bool:
    """Resolve GRAFT_TELEMETRY / GRAFT_TRACE; returns whether enabled.

    ``GRAFT_TRACE`` (an export directory) implies telemetry; a bare
    ``GRAFT_TELEMETRY=1`` collects spans without exporting. Explicit
    ``GRAFT_TELEMETRY=0`` wins over both (the opt-out).
    """
    e = os.environ if env is None else env
    tele = (e.get("GRAFT_TELEMETRY") or "").strip().lower()
    if tele and tele not in _TRUTHY:
        disable()
        return False
    if tele in _TRUTHY or (e.get("GRAFT_TRACE") or "").strip():
        enable()
        return True
    return _TRACER.enabled


def span(name: str, cat: str = "other", **attrs):
    """``with span("step.dispatch", "step", n=i): ...`` on the default
    tracer: always a trace annotation ``graft/<name>`` with ``attrs`` as
    its arguments, and a ring record when telemetry is on."""
    return _open(_TRACER, name, cat, attrs)


def instant(name: str, cat: str = "other", **attrs) -> None:
    _TRACER.instant(name, cat, **attrs)


def dispatch_span(owner, kind: str):
    """Span for one compiled-step dispatch (TrainStep / PipelineStep /
    CompressedGradStep / MultiStep ``__call__``).

    The owner's FIRST dispatch traces+compiles (or deserializes the
    cache artifact), so it lands in the ``compile`` bucket; steady-state
    dispatches are ``step``/productive. State lives on the owner object
    (``_telemetry_dispatches``, the count of its dispatches so far), not
    the tracer, so two steps in one process each get their own compile
    span. That count is the span's ``step`` argument: the identifier a
    step's spans share, a Python integer kept on the host (never
    ``state.step`` or any device value).
    """
    n = getattr(owner, "_telemetry_dispatches", 0)
    owner._telemetry_dispatches = n + 1
    return _dispatch(kind, bool(n), {"kind": kind, "step": n})


def _dispatch(kind: str, warm: bool, attrs: dict):
    if warm:
        return _open(_TRACER, f"{kind}.dispatch", "step", attrs)
    return _open(_TRACER, f"{kind}.compile+dispatch", "compile", attrs)


def bucket_dispatch_span(owner, kind: str, bucket):
    """:func:`dispatch_span` for shape-bucketed dispatch families.

    A serving engine runs one compiled program *per bucket shape*
    (``serve.prefill`` at each chunk bucket, ``serve.decode`` at the slot
    batch), so warmth is per ``(kind, bucket)``, not per owner: the first
    dispatch of EACH bucket is a ``compile`` span, every later one is
    ``step``/productive. The bucket rides on the span attrs so the SLO
    bench can attribute p99 excursions to a cold bucket.
    """
    warm = getattr(owner, "_telemetry_warm_buckets", None)
    if warm is None:
        warm = owner._telemetry_warm_buckets = set()
    key = (kind, bucket)
    was_warm = key in warm
    warm.add(key)
    return _dispatch(kind, was_warm, {"kind": kind, "bucket": bucket})


def note_recompile(owner, jitted, kind: str) -> None:
    """Emit a ``recompile`` instant when a jitted callable's cache grew
    after the owner's warm point (a mid-run retrace — shape drift).
    No-op when the runtime doesn't expose ``_cache_size``."""
    if not _TRACER.enabled:
        return
    try:
        size = jitted._cache_size()
    except Exception:  # noqa: BLE001 — introspection, version-dependent
        return
    seen = getattr(owner, "_telemetry_cache_seen", None)
    owner._telemetry_cache_seen = size
    if seen is not None and size > seen:
        _TRACER.instant(
            f"{kind}.recompile", "compile", kind=kind,
            cache_entries=size,
        )


def add_span(name, cat, t0, dur, attrs=None, depth=None) -> None:
    _TRACER.add_span(name, cat, t0, dur, attrs, depth=depth)


def records() -> list:
    return _TRACER.records()


def clear() -> None:
    _TRACER.clear()


def export_chrome_trace(path: str | None = None) -> str:
    """Export the default tracer; default path is
    ``$GRAFT_TRACE/telemetry-<pid>.trace.json`` (or under run_dir)."""
    if path is None:
        base = (os.environ.get("GRAFT_TRACE") or "").strip() or run_dir()
        path = os.path.join(base, f"telemetry-{os.getpid()}.trace.json")
    return _TRACER.export_chrome_trace(path)


# -- crash flight recorder ---------------------------------------------

FLIGHT_RECORD_KEEP = 64  # last N records in a flight file


def flight_record_path(pid: int | None = None) -> str:
    return os.path.join(
        run_dir(), f"flightrec-{os.getpid() if pid is None else pid}.json"
    )


def flush_flight_record(
    reason: str, exc: BaseException | None = None, path: str | None = None,
) -> str | None:
    """Write the last N spans/events + the in-flight span stack to a
    per-process file. Called on unhandled exceptions (crash handler) and
    on fault-site trips (resilience/faults.py); safe to call repeatedly —
    last writer wins, which is the record closest to death."""
    try:
        recs = _TRACER.records()[-FLIGHT_RECORD_KEEP:]
        open_spans = _TRACER.open_spans()
        now = time.perf_counter()
        doc = {
            "reason": reason,
            "pid": os.getpid(),
            "rank": _rank(),
            "wall_time": time.time(),
            "telemetry_enabled": _TRACER.enabled,
            # innermost open span = what the process was doing when it died
            "in_flight": [
                dict(s, age_s=round(now - s["t0"], 6)) for s in open_spans
            ],
            "recent": recs,
            "dropped": _TRACER.dropped,
        }
        # the serving half: which requests were in flight, and in what
        # lifecycle phase, when the process died. sys.modules lookup, not
        # an import — the SLO ledger is only consulted when the serve
        # plane is actually live in this process
        slo_mod = sys.modules.get(
            "pytorch_distributedtraining_tpu.observe.slo"
        )
        if slo_mod is not None:
            serve_inflight = slo_mod.inflight_requests()
            if serve_inflight:
                doc["serve_in_flight"] = serve_inflight
        # the numerics half: grad-norm / non-finite blame / watchdog
        # verdicts at the moment of death — a crash mid-divergence keeps
        # its numerics story. Same sys.modules contract as above.
        num_mod = sys.modules.get(
            "pytorch_distributedtraining_tpu.observe.numerics"
        )
        if num_mod is not None:
            num_snap = num_mod.snapshot()
            if num_snap.get("steps_observed"):
                doc["numerics"] = num_snap
        # the memory half: HBM budget + high-water at the moment of
        # death (a crash mid-OOM keeps its memory story). Same
        # sys.modules contract — observe.memory imports jax, and a
        # flight flush must never be the thing that initializes it.
        mem_mod = sys.modules.get(
            "pytorch_distributedtraining_tpu.observe.memory"
        )
        mem_stats = getattr(mem_mod, "runtime_stats", None)
        if mem_stats and any(v is not None for v in mem_stats.values()):
            doc["memory"] = dict(mem_stats)
        if exc is not None:
            doc["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc)[:500],
                "traceback": traceback.format_exception(
                    type(exc), exc, exc.__traceback__
                )[-10:],
            }
        path = path or flight_record_path()
        tmp = f"{path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        os.replace(tmp, path)  # atomic: the restart gate never reads half
        return path
    except Exception:  # noqa: BLE001 — a recorder must never mask the crash
        return None


_prev_excepthook = None


def install_crash_handler() -> None:
    """Chain a flight-record flush into ``sys.excepthook`` (idempotent)."""
    global _prev_excepthook
    if _prev_excepthook is not None:
        return

    prev = sys.excepthook

    def _hook(exc_type, exc, tb):
        flush_flight_record("unhandled-exception", exc=exc)
        prev(exc_type, exc, tb)

    _prev_excepthook = prev
    sys.excepthook = _hook


def read_flight_records(directory: str | None = None) -> list:
    """Parse every flightrec-*.json under a run dir (launcher restart
    gate). Unreadable/partial files are skipped, never raised."""
    directory = directory or run_dir()
    out = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for n in names:
        if not (n.startswith("flightrec-") and n.endswith(".json")):
            continue
        try:
            with open(os.path.join(directory, n), encoding="utf-8") as fh:
                out.append(json.load(fh))
        except (OSError, json.JSONDecodeError):
            continue
    return out


def describe_flight_record(doc: dict) -> str:
    """One line for the restart gate: who died doing what."""
    exc = doc.get("exception") or {}
    inflight = doc.get("in_flight") or []
    doing = (
        f"in span '{inflight[-1]['name']}' ({inflight[-1]['cat']})"
        if inflight else "between spans"
    )
    serve = doc.get("serve_in_flight") or []
    if serve:
        phases = ", ".join(
            f"{r.get('rid', '?')}:{r.get('phase', '?')}" for r in serve[:4]
        )
        more = f" +{len(serve) - 4} more" if len(serve) > 4 else ""
        doing += (
            f" with {len(serve)} serve request(s) in flight "
            f"({phases}{more})"
        )
    num = doc.get("numerics") or {}
    if num.get("nonfinite_steps_total"):
        blame = num.get("last_nonfinite") or {}
        doing += (
            f"; numerics: {num['nonfinite_steps_total']} non-finite "
            f"step(s), last blame {blame.get('leaf', '?')}"
        )
    cause = f" [{exc['type']}: {exc['message']}]" if exc else ""
    return (
        f"rank {doc.get('rank', '?')} pid {doc.get('pid', '?')} "
        f"({doc.get('reason', '?')}) was {doing}{cause}"
    )
