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
- the **start-up ledger** — spans of category ``compile`` and ``startup``
  happen once a process or once a program, so they are kept with no
  knob, in a bounded list of their own (``LEDGER_CAPACITY`` records, the
  FIRST that many; ``ledger_dropped`` counts the rest), and in the ring
  too while it is on. jax's own compile events join them by program name
  (one ``jax.monitoring`` listener, registered when the first span opens
  with jax loaded, never at import): ``compile.trace``,
  ``compile.lower``, ``compile.cache_read`` (the persistent cache hit)
  or ``compile.xla`` (it missed), each with ``fun_name``, placed on
  ``perf_counter`` at the callback less the duration, nested by
  containment, with the innermost open program span's name and ``step``
  (``program``, ``step``). The listener fires on compile paths only; a
  steady dispatch never reaches it. :func:`startup_report` reads the
  ledger: the phases before the first steady step in order, compile
  seconds as unions and as self time (a jitted function traced inside a
  jitted step counts once), the gaps under no program span by their
  neighbours, and what compiled after the end, by ``step``.

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
``serve.prefill|decode|spec_verify|tile.dispatch``. Category
``startup``, kept in the ledger: ``runtime.initialize`` (the ledger's
origin; carries the ``perf_counter`` / ``time_ns`` pair and the process's
age), ``mesh.make``, ``state.create``, ``facade.construct``,
``facade.init_state``, ``facade.program.compile+dispatch`` (category
``compile``: a facade program's first call, ``program`` its attribute),
``loader.start_workers``, ``prefetch.start``; and jax's
``compile.trace|lower|cache_read|xla``.

Env knobs (mirrored by ``TPUConfig.telemetry`` / ``TPUConfig.trace_dir``
through the stoke facade, and by both drivers' ``--trace``):

- ``GRAFT_TELEMETRY`` = 1/0 — enable the ring buffer (span collection,
  Chrome export) + crash handler. Annotations and the start-up ledger
  need no knob.
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
    "startup_records",
    "startup_report",
    "describe_startup",
    "clock_anchor",
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
    "startup",     # once-a-process phases before the first steady step
    "input",       # blocked on the input pipeline
    "checkpoint",  # checkpoint write windows
    "collective",  # explicit cross-process sync (barriers, agreements)
    "outage",      # riding a pool outage / retry backoff
    "fault",       # injected-fault instants (resilience/faults.py)
    "membership",  # elastic membership transitions (runtime/membership.py)
    "other",
)

# kept in the start-up ledger with no knob: once a process or once a program
LEDGER_CATEGORIES = frozenset(("compile", "startup"))
LEDGER_CAPACITY = 512  # the first that many; the rest are counted


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
        # the start-up ledger: the first LEDGER_CAPACITY records of
        # LEDGER_CATEGORIES, kept whether or not the ring is on
        self._ledger: list = []
        self.ledger_dropped = 0  # records past the ledger's bound
        self.steady_at: float | None = None  # the first warm dispatch
        self.small_traces = [0, 0.0]  # traces under TRACE_FLOOR_S: count, sum

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
        """Record an externally-timed span: into the ring while it is on,
        into the start-up ledger where ``cat`` is one of
        ``LEDGER_CATEGORIES`` (it cannot be an annotation after the fact:
        the program's own sites use ``with span(...)``). With no ``depth``
        the span is placed by containment: under the spans open on this
        thread, and over the ledger's records that it holds."""
        kept = cat in LEDGER_CATEGORIES
        if not (kept or self.enabled):
            return
        rec = {
            "name": name, "cat": cat, "t0": t0, "dur": max(0.0, dur),
            "tid": threading.get_ident(),
            "depth": len(self._stack()) if depth is None else depth,
            "attrs": dict(attrs) if attrs else {},
        }
        if kept:
            self._keep(rec, contains=depth is None)
        if self.enabled:
            self._append(rec)

    def _keep(self, rec: dict, contains: bool) -> None:
        """One record into the ledger, the bound counted. A record timed
        from outside closes after what it holds: those records (this
        thread's, started inside it) go one level down."""
        with self._lock:
            if contains:
                for held in reversed(self._ledger):
                    if held["tid"] != rec["tid"]:
                        continue
                    if held["t0"] < rec["t0"]:
                        break
                    held["depth"] += 1
            if len(self._ledger) < LEDGER_CAPACITY:
                self._ledger.append(rec)
            else:
                self.ledger_dropped += 1

    def note_steady(self) -> None:
        """Stamp the first warm dispatch: where start-up ends."""
        if self.steady_at is None:
            self.steady_at = time.perf_counter()

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

    def startup_records(self) -> list:
        """The start-up ledger, in the order its records closed."""
        with self._lock:
            return list(self._ledger)

    def clear_startup(self) -> None:
        with self._lock:
            self._ledger.clear()
            self.ledger_dropped = 0
            self.steady_at = None
            self.small_traces = [0, 0.0]

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
    once; None while jax is not loaded (the launcher, the bench parent).
    The ledger's compile listener is registered at the same moment."""
    global _ANNOTATION
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    _listen_to_compiles()

    class _Annotation(profiler.TraceAnnotation):
        __slots__ = ()

        def set(self, **attrs):  # arguments are fixed at construction
            return self

    _ANNOTATION = _Annotation
    return _Annotation


# -- jax's compile events, into the ledger ---------------------------------

_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_JAX_BACKEND = "/jax/core/compile/backend_compile_duration"
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
COMPILE_EVENTS = (
    "compile.trace", "compile.lower", "compile.cache_read", "compile.xla",
)
# Every jnp function called while a program is traced is a jitted function
# with a trace event of its own, ten thousand to a model and most under a
# millisecond, each inside the trace of what called it. Those shorter than
# this are counted (``Tracer.small_traces``) and not kept.
TRACE_FLOOR_S = 0.005
# what numbers the dispatches of an owner, and how far the dispatch in
# flight is behind it: ``dispatch_span``'s count, the facade's steps
_STEP_COUNTS = (("_telemetry_dispatches", -1), ("_opt_steps", 0))
_LISTENING = False


def _listen_to_compiles() -> None:
    """Register the ledger's two listeners with ``jax.monitoring``, once.
    Called where jax is known to be loaded (``_annotation_type``)."""
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    import jax.monitoring as monitoring  # jax is loaded: a dict lookup

    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_event(event: str, **_) -> None:
    if event == _JAX_CACHE_HIT:  # its backend duration follows, same thread
        _TRACER._tls.cache_hit = True


def _on_jax_duration(event: str, duration: float, fun_name="", **_) -> None:
    """One ledger record per trace, lowering and executable, by program
    name. jax calls this at the event's end, on the thread that compiled,
    and on compile paths only."""
    if event == _JAX_TRACE:
        if duration < TRACE_FLOOR_S:
            small = _TRACER.small_traces
            small[0] += 1
            small[1] += duration
            return
        name = "compile.trace"
    elif event == _JAX_LOWER:
        name = "compile.lower"
    elif event == _JAX_BACKEND:
        tls = _TRACER._tls
        hit, tls.cache_hit = getattr(tls, "cache_hit", False), False
        name = "compile.cache_read" if hit else "compile.xla"
    else:
        return
    now = time.perf_counter()
    attrs = {"fun_name": str(fun_name)}
    stack = _TRACER._stack()
    if stack:  # a cold dispatch, a phase of start-up
        attrs["program"] = stack[-1].name
        if "step" in stack[-1].attrs:
            attrs["step"] = stack[-1].attrs["step"]
    else:  # a warm dispatch is an annotation: ask the thread's frames
        attrs.update(_dispatch_in_flight())
    _TRACER.add_span(name, "compile", now - duration, duration, attrs)


def _dispatch_in_flight() -> dict:
    """``program`` and ``step`` of the dispatch that a compile event fell
    in, read off the calling thread's frames: the innermost ``self`` that
    counts its dispatches (``_STEP_COUNTS``). A warm dispatch leaves
    nothing on the ring's stack, and this runs only when something
    compiled, so the steady path pays nothing for it."""
    frame = sys._getframe(2)
    try:
        while frame is not None:
            owner = frame.f_locals.get("self")
            counts = getattr(owner, "__dict__", None)
            if isinstance(counts, dict):
                for key, behind in _STEP_COUNTS:
                    n = counts.get(key)
                    if isinstance(n, int):
                        return {
                            "program": type(owner).__name__,
                            "step": n + behind,
                        }
            frame = frame.f_back
    except Exception:  # noqa: BLE001 — a listener must never fail a compile
        pass
    return {}


def _annotate(name: str, attrs: dict):
    """The span as a trace annotation only: ``graft/<name>`` with
    ``attrs`` as its arguments. A flag test while no profile is taken."""
    cls = _ANNOTATION or _annotation_type()
    if cls is None:
        return _NULL_SPAN
    return cls(ANNOTATION_PREFIX + name, **attrs)


def _open(tracer: "Tracer", name: str, cat: str, attrs: dict):
    """A span: an annotation only while ``tracer`` is off, a ring record
    (with its annotation inside) while it is on; one of
    ``LEDGER_CATEGORIES`` is a record either way, for the ledger, once
    jax is loaded (a process without jax compiles nothing)."""
    if tracer.enabled or (
        cat in LEDGER_CATEGORIES and (_ANNOTATION or _annotation_type())
    ):
        return _LiveSpan(tracer, name, cat, attrs)
    return _annotate(name, attrs)


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
    if n == 1:  # the first warm dispatch: where start-up ends
        _TRACER.note_steady()
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


def add_span(name, cat, t0, dur, attrs=None, depth=None) -> None:
    _TRACER.add_span(name, cat, t0, dur, attrs, depth=depth)


def records() -> list:
    return _TRACER.records()


def clear() -> None:
    _TRACER.clear()


# -- the start-up ledger's reader ---------------------------------------

ORIGIN_SPAN = "runtime.initialize"  # the ledger's origin, where it is there
REPORT_TOP = 10  # the costliest (event, fun_name) pairs a report names
REPORT_AFTER_END = 20  # the compile events past the end a report lists


def clock_anchor() -> dict:
    """What aligns ``perf_counter`` (the ledger's clock) with a wall clock
    and so with a profile taken later: both read at one moment, and the
    process's age then, where ``/proc/self/stat`` gives it (everything
    before the ledger's origin: the interpreter's start, the imports)."""
    anchor = {"perf_counter": time.perf_counter(), "time_ns": time.time_ns()}
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # field 22, counted past the parenthesised command name
            started = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        anchor["process_age_s"] = uptime - started / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    return anchor


def startup_records() -> list:
    return _TRACER.startup_records()


def _union(spans) -> list:
    """Sorted, disjoint ``(lo, hi)`` covering what ``spans`` cover."""
    out: list = []
    for lo, hi in sorted(s for s in spans if s[1] > s[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _total(spans) -> float:
    return sum(hi - lo for lo, hi in spans)


def _covered(spans, by) -> float:
    """Seconds of the disjoint ``spans`` inside the disjoint ``by``."""
    return sum(
        max(0.0, min(hi, b_hi) - max(lo, b_lo))
        for lo, hi in spans for b_lo, b_hi in by
    )


def _minus(spans, by) -> list:
    """The disjoint ``spans`` less the disjoint, sorted ``by``."""
    out = []
    for lo, hi in spans:
        for b_lo, b_hi in by:
            if b_hi <= lo or b_lo >= hi:
                continue
            if b_lo > lo:
                out.append((lo, b_lo))
            lo = max(lo, b_hi)
        if hi > lo:
            out.append((lo, hi))
    return out


def _clip(rec: dict, lo: float, hi: float) -> tuple:
    lo, hi = max(lo, rec["t0"]), min(hi, rec["t0"] + rec["dur"])
    return lo, max(lo, hi)


def _nest(recs: list, lo: float, hi: float) -> list:
    """``(record, interval, own, top)`` of one thread's records, nested by
    containment: a record is inside the last one before it that has not
    ended when it starts. ``interval`` is clipped to ``[lo, hi]`` and to
    the record it is inside; ``own`` is the interval less the records
    directly inside it, as disjoint intervals; ``top`` says that it is
    inside none."""
    rows, stack = [], []  # a row: [rec, interval, intervals inside, top]
    for rec in sorted(recs, key=lambda r: (r["t0"], -r["dur"])):
        while stack and rec["t0"] >= stack[-1][0]["t0"] + stack[-1][0]["dur"]:
            stack.pop()
        span = _clip(rec, *(stack[-1][1] if stack else (lo, hi)))
        if stack:
            stack[-1][2].append(span)
        row = [rec, span, [], not stack]
        rows.append(row)
        stack.append(row)
    return [
        (rec, span, _minus([span], _union(inside)), top)
        for rec, span, inside, top in rows
    ]


def startup_report(until: float | None = None) -> dict:
    """The start-up ledger, read: where the time went between the origin
    and ``until`` (a ``perf_counter`` stamp; by default the first warm
    dispatch of a step, or now where there has been none). The origin is
    the process's start where the ledger's first record
    (``runtime.initialize``, else the earliest) carries the process's age,
    so that the interpreter's start and the imports are the first gap,
    ``process start`` to ``runtime.initialize``; else that record's start.

    ``phases`` are the program's top-level spans on the origin's thread in
    order (``seconds``; ``self_seconds`` less the spans and the compile
    events inside; ``compile_seconds`` the union of the compile events
    inside), ``gaps`` what lies under no program span of that thread, each
    named by the spans on either side (``background_seconds`` of a gap
    are under a top-level span of another thread: the loader's workers
    starting while this thread waits; ``outside_program_s`` is the gaps
    less those). ``seconds`` is the phases' seconds plus the gaps', and
    ``split`` divides the same seconds into the program's own
    (``program_s``), jax's compile events wherever they fell on that
    thread (``compile_s``) and the rest of the gaps (``outside_s``).
    ``trace_s``, ``lower_s``, ``cache_read_s`` and ``xla_s`` are UNIONS
    per thread (a jitted function traced inside a jitted step counts
    once), summed over threads; ``costliest`` ranks ``(event, fun_name)``
    by self seconds, with counts (a name traced twice shows). ``by_name``
    has every span name's union, self and compile seconds, ``background``
    the top-level spans of other threads, ``after_end`` the compile events
    that began past the end, with the ``step`` of the dispatch they fell
    in: which step recompiled."""
    recs = _TRACER.startup_records()
    first = next(
        (r for r in recs if r["name"] == ORIGIN_SPAN),
        min(recs, key=lambda r: r["t0"], default=None),
    )
    now = time.perf_counter()
    clock = {
        k: first["attrs"][k]
        for k in ("perf_counter", "time_ns", "process_age_s")
        if first and k in first["attrs"]
    }
    born = "process_age_s" in clock  # the timeline starts with the process
    if born:
        origin = clock["perf_counter"] - clock["process_age_s"]
    else:
        origin = first["t0"] if first else now
    end = until if until is not None else (_TRACER.steady_at or now)
    end = max(end, origin)
    main = first["tid"] if first else None
    events = [r for r in recs if r["name"] in COMPILE_EVENTS]
    before = [r for r in recs if r["t0"] < end]
    threads = sorted({r["tid"] for r in before}, key=lambda t: t != main)

    unions = dict.fromkeys(COMPILE_EVENTS, 0.0)
    costs: dict = {}  # (event, fun_name) -> [self seconds, count]
    phases, background, by_name = [], [], {}
    program_s, main_busy = 0.0, []  # of the origin's thread
    for tid in threads:
        mine = [r for r in before if r["tid"] == tid]
        happened = [r for r in mine if r["name"] in COMPILE_EVENTS]
        for kind in COMPILE_EVENTS:
            unions[kind] += _total(_union(
                _clip(r, origin, end) for r in happened if r["name"] == kind
            ))
        busy = _union(_clip(r, origin, end) for r in happened)
        if tid == main:
            main_busy = busy
        for rec, _, own, _ in _nest(happened, origin, end):
            cost = costs.setdefault(
                (rec["name"], rec["attrs"].get("fun_name", "")), [0.0, 0]
            )
            cost[0] += _total(own)
            cost[1] += 1
        named: dict = {}  # this thread's spans, by name
        for rec, span, own, top in _nest(
            [r for r in mine if r["name"] not in COMPILE_EVENTS], origin, end
        ):
            self_s = _total(own) - _covered(own, busy)
            entry = named.setdefault(rec["name"], [0, [], 0.0])
            entry[0] += 1
            entry[1].append(span)
            entry[2] += self_s
            if tid == main:
                program_s += self_s
            if top:
                row = {
                    "name": rec["name"], "at": span[0] - origin,
                    "seconds": span[1] - span[0], "self_seconds": self_s,
                    "compile_seconds": _covered([span], busy),
                    "attrs": {
                        k: _jsonable(v) for k, v in rec["attrs"].items()
                    },
                }
                if tid == main:
                    phases.append(row)
                else:
                    background.append(dict(row, thread=tid))
        for name, (count, spans, self_s) in named.items():
            spans = _union(spans)
            entry = by_name.setdefault(name, {
                "count": 0, "seconds": 0.0, "self_seconds": 0.0,
                "compile_seconds": 0.0,
            })
            entry["count"] += count
            entry["seconds"] += _total(spans)
            entry["self_seconds"] += self_s
            entry["compile_seconds"] += _covered(spans, busy)

    busy = main_busy
    elsewhere = [
        (origin + row["at"], origin + row["at"] + row["seconds"], row["name"])
        for row in background
    ]
    gaps, edge, after = [], origin, "process start" if born else "origin"
    for row in phases + [{"name": "end", "at": end - origin, "seconds": 0.0}]:
        lo = origin + row["at"]
        if lo > edge:
            gaps.append({
                "after": after, "before": row["name"], "at": edge - origin,
                "seconds": lo - edge,
                "compile_seconds": _covered([(edge, lo)], busy),
                # what the program did meanwhile on its other threads
                "background_seconds": _covered([(edge, lo)], _union(
                    span[:2] for span in elsewhere
                )),
                "background": sorted({
                    name for b_lo, b_hi, name in elsewhere
                    if b_lo < lo and b_hi > edge
                }),
            })
        edge, after = max(edge, lo + row["seconds"]), row["name"]
    outside_program_s = sum(
        g["seconds"] - g["background_seconds"] for g in gaps
    )
    late = sorted(
        (r for r in events if r["t0"] >= end), key=lambda r: r["t0"]
    )
    return {
        "origin": origin, "end": end, "seconds": end - origin,
        "clock": clock or clock_anchor(), "records": len(recs),
        "dropped": _TRACER.ledger_dropped,
        # a SUM, most of it inside the traces that are kept
        "small_traces": dict(
            zip(("count", "seconds"), _TRACER.small_traces)
        ),
        "phases": phases, "gaps": gaps,
        "outside_program_s": outside_program_s,
        "split": {
            "program_s": program_s, "compile_s": _total(busy),
            "outside_s": sum(
                g["seconds"] - g["compile_seconds"] for g in gaps
            ),
        },
        **{k.split(".", 1)[1] + "_s": v for k, v in unions.items()},
        "costliest": [
            {"event": event, "fun_name": fun, "self_seconds": s, "count": n}
            for (event, fun), (s, n) in sorted(
                costs.items(), key=lambda kv: -kv[1][0]
            )[:REPORT_TOP]
        ],
        "by_name": by_name, "background": background,
        "after_end": [
            {"event": r["name"], "at": r["t0"] - origin, "seconds": r["dur"],
             **r["attrs"]}
            for r in late[:REPORT_AFTER_END]
        ],
        "after_end_count": len(late),
    }


def describe_startup(report: dict) -> str:
    """One line for a driver's log at its first steady step: the six
    longest phases and gaps of a :func:`startup_report`, the compile
    unions, and the costliest program name."""
    parts = sorted(
        [(p["seconds"], p["name"]) for p in report["phases"]]
        + [(g["seconds"], f"{g['after']}..{g['before']}")
           for g in report["gaps"]],
        reverse=True,
    )[:6]
    line = (
        f"start-up {report['seconds']:.2f} s to the first steady step: "
        + ", ".join(f"{name} {seconds:.2f}" for seconds, name in parts)
        + "; compiling "
        + ", ".join(
            f"{kind} {report[kind + '_s']:.2f}"
            for kind in ("trace", "lower", "cache_read", "xla")
        )
    )
    if report["costliest"]:
        top = report["costliest"][0]
        line += (
            f"; costliest {top['event']} of {top['fun_name']} "
            f"{top['self_seconds']:.2f} s"
        )
    if report["after_end_count"]:
        line += f"; {report['after_end_count']} compile events since"
    return line


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
