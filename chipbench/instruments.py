"""What the harness measures with: compile meter, memory, host spans, the
profiler window. All state lives in objects the harness creates and passes.

``CompileMeter`` is copied from ``chip_smoke.py`` (sound there, ran on the
chip in PR 21); its ``memory`` is corrected here.
"""

from __future__ import annotations

import contextlib
import glob
import os
import time

_NULL = contextlib.nullcontext()


class CompileMeter:
    """What jax itself reports about compiling (``jax.monitoring``):
    backend-compile seconds (XLA, or the read of a cached executable),
    tracing and lowering seconds, and the persistent cache's requests, hits
    and misses. ``take()`` gives the totals since the last call."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _TRACING = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )
    _COUNTED = ("compile_requests_use_cache", "cache_hits", "cache_misses")

    def __init__(self):
        import jax.monitoring

        self.totals = self._zero()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _zero(self) -> dict:
        return {"compile_s": 0.0, "trace_lower_s": 0.0, "compiles": 0,
                **dict.fromkeys(self._COUNTED, 0)}

    def _duration(self, event, duration, **_):
        if event == self._BACKEND:
            self.totals["compile_s"] += duration
            self.totals["compiles"] += 1
        elif event in self._TRACING:
            self.totals["trace_lower_s"] += duration

    def _event(self, event, **_):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in self._COUNTED:
            self.totals[key] += 1

    def take(self) -> dict:
        out, self.totals = self.totals, self._zero()
        return out


def memory_peaks(devices) -> list:
    """Per device, the allocator's two high-water marks since the process
    started. ``peak_bytes_reserved`` is what running programs reserved for
    their temporaries (it matches ``memory_analysis().temp_size_in_bytes``);
    ``peak_bytes_in_use`` is live arrays. Neither holds the other (a process
    that only queues arrays shows 15.0 GB in use and 0.2 GB reserved; my chip
    run, PR 22)."""
    keys = ("peak_bytes_reserved", "peak_bytes_in_use")
    stats = [dev.memory_stats() or {} for dev in devices]
    return [{k: s.get(k) or 0 for k in keys} for s in stats]


def memory_peak_bytes(peaks: list) -> int:
    """The fullest chip's peak from ``memory_peaks``: temporaries plus
    arrays, each at its own high-water mark, so an upper bound of the true
    peak, and close to it where the training state is resident while the
    step runs."""
    return max((sum(p.values()) for p in peaks), default=0)


class Spans:
    """The benchmark's own host spans, on two clocks at once: the host's
    (kept here, every span of the window) and the profiler's
    (``jax.profiler.TraceAnnotation``, so that idle gaps of the device can
    be named). Off in an untraced run: ``span()`` then costs a function call.
    """

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.seconds: dict = {}

    def span(self, name: str):
        return _Span(self, name) if self.enabled else _NULL

    def total(self, *names: str) -> float:
        return sum(self.seconds.get(n, 0.0) for n in names)


class _Span:
    __slots__ = ("spans", "name", "t0", "annotation")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation("cb/" + self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        seconds = self.spans.seconds
        seconds[self.name] = seconds.get(self.name, 0.0) + dt


class Tracer:
    """Takes one profiler trace of the last ``length_s`` seconds of the
    window. The loop calls ``poll(now)`` between steps and ``stop()`` after
    its closing fence: ``stop_trace`` blocks the host for seconds while it
    writes (4 s for 2 s of GPT-2 125M, my chip run, PR 22), and inside the
    window that would drain the device. Nothing happens in an untraced run.
    """

    def __init__(self, enabled: bool, out_dir: str, length_s: float):
        self.out_dir, self.length_s = out_dir, length_s
        self.start_at = None
        self.state = "armed" if enabled else "off"

    def arm(self, t_open: float, window_s: float) -> None:
        self.start_at = t_open + max(0.0, window_s - self.length_s)

    def poll(self, now: float) -> None:
        if self.state == "armed" and now >= self.start_at:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the benchmark's spans only
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            self.state = "tracing"

    def stop(self) -> None:
        if self.state == "tracing":
            import jax

            jax.profiler.stop_trace()
            self.state = "done"

    def xplane_path(self) -> str | None:
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"
        )))
        return found[-1] if self.state == "done" and found else None
