"""Shared outage classifier + retry policy + circuit breaker.

Extracted from an ad-hoc probe-failure classification (round 5): every
layer that has to decide "is this failure the shared pool flapping, or is
my code broken?" now asks the same question of
the same classifier. The sentinel set is deliberately broad (ADVICE r5 #4):
the round-1..5 capture failures surfaced as ``UNAVAILABLE`` raises, rc=124
driver timeouts, connection-refused text *without* the literal UNAVAILABLE,
and silent hangs — a classifier that only knows one signature reintroduces
the capture-failure mode this module exists to end.

Stdlib-only: the launcher (jax-free by contract) imports this.
"""

from __future__ import annotations

import enum
import random
import time
from dataclasses import dataclass
from typing import Callable, Iterator


class OutageClass(enum.Enum):
    """What a failed probe/attempt says about the world.

    OUTAGE          — the shared pool / network is down; waiting helps.
    DETERMINISTIC   — the failure is ours (ImportError, typoed platform,
                      usage error); retrying the same thing cannot help.
    UNKNOWN         — a generic failure (rc=1, no recognizable signature).
                      Callers should ride it as outage-class until the
                      fast-fail window has consumed a couple of probe
                      intervals (ADVICE r5 #4), then treat it as
                      deterministic.
    """

    OUTAGE = "outage"
    DETERMINISTIC = "deterministic"
    UNKNOWN = "unknown"


# gRPC status names the TPU runtime raises during pool outages — matched
# case-sensitively, they are uppercase canonical tokens.
_GRPC_SENTINELS = ("UNAVAILABLE", "DEADLINE_EXCEEDED")

# transport-level phrases — matched case-insensitively; connection text
# varies by layer ("Connection refused", "connection reset by peer", ...)
_CONNECTION_SENTINELS = (
    "connection refused",
    "connection reset",
    "connection closed",
    "connection aborted",
    "failed to connect",
    "broken pipe",
    "socket closed",
    "transport closed",
    "host unreachable",
)

# return codes that are outage-class by construction:
#   None — the caller killed a hung child (pool claim wedged)
#   3, 4 — a child's refusal to fall back to the CPU (pool dropped
#          before or after it was probed)
#   124  — coreutils `timeout` expiry (driver-side kill of a hung capture)
_OUTAGE_RCS = frozenset({3, 4, 124})


def is_outage_text(text: str) -> bool:
    """True when ``text`` carries a recognized outage signature."""
    if any(s in text for s in _GRPC_SENTINELS):
        return True
    low = text.lower()
    return any(s in low for s in _CONNECTION_SENTINELS)


def classify(rc: int | None, tail: str = "") -> OutageClass:
    """Classify one failed probe/attempt from its return code + output tail.

    ``rc`` is the child's return code (None = killed on timeout); ``tail``
    is whatever diagnostic text survived (the informative last lines).
    """
    if rc is None or rc in _OUTAGE_RCS:
        return OutageClass.OUTAGE
    if rc in (-9, -15, 137, 143):
        # killed by SIGKILL/SIGTERM (subprocess negative convention or the
        # 128+N shell convention): an *external* termination — preemption,
        # OOM-killer, driver timeout — is outage-class, not a code bug
        return OutageClass.OUTAGE
    if tail and is_outage_text(tail):
        return OutageClass.OUTAGE
    if rc is not None and rc < 0:
        # some other signal (SIGSEGV, SIGILL): could be a flaky backend or
        # a real crash — ride briefly, like a bare rc=1
        return OutageClass.UNKNOWN
    if rc == 1:
        # a bare interpreter-level failure with no recognizable signature:
        # could be either (pool errors sometimes lose their text to a
        # truncated tail) — let the caller's fast-fail window decide
        return OutageClass.UNKNOWN
    # rc=2 (usage), ImportError-style startup rc, or any other distinct
    # code with no outage text: deterministic, retrying cannot help
    return OutageClass.DETERMINISTIC


def external_termination(rc: int | None) -> bool:
    """True when a rank's exit looks like the WORKER WAS TAKEN AWAY —
    SIGKILL/SIGTERM (negative subprocess convention or the 128+N shell
    convention) or a kill-on-timeout (rc None) — rather than the program
    failing on its own. This is the elastic launcher's shrink-vs-retry
    discriminator: a preempted/OOM-killed/timed-out rank is *gone*, so
    the surviving world relaunches smaller (shrink-to-survive); any other
    outage-class failure (rendezvous flake, transient I/O) retries at the
    same world size first.
    """
    return rc is None or rc in (-9, -15, 124, 137, 143)


# crash signatures that point at the HOST rather than the code or the
# pool: memory/bus faults and illegal instructions are the classic
# bad-DIMM / cooked-chip ways a machine eats a rank, and a hardware
# sentinel in the tail is the driver saying so outright
_HOST_FAULT_RCS = frozenset({-11, -7, -4, -8, 139, 135, 132, 136})
_HOST_FAULT_SENTINELS = (
    "uncorrectable ecc",
    "hbm error",
    "device failure",
    "hardware error",
    "machine check",
    "bus error",
    "segmentation fault",
)


def attributes_to_host(rc: int | None, tail: str = "") -> bool:
    """True when a rank's failure is plausibly the HOST's fault — the
    elastic launcher's quarantine discriminator.

    An external termination (preemption/OOM-kill/timeout) says the pool
    took the worker: the host is innocent and stays admissible for
    grow-back. A SIGSEGV/SIGBUS/SIGILL/SIGFPE death, or a hardware
    sentinel in the diagnostic tail, says the machine itself ate the
    rank — growing back onto it would just crash the next generation,
    so it enters quarantine with exponential backoff instead.
    """
    if rc is not None and rc in _HOST_FAULT_RCS:
        return True
    if external_termination(rc):
        return False
    low = tail.lower()
    return any(s in low for s in _HOST_FAULT_SENTINELS)


def classify_exception(exc: BaseException) -> OutageClass:
    """:func:`classify` for in-process exceptions (rendezvous, W&B, I/O).

    Transient-by-nature exception types (connection/timeout/IO) classify as
    OUTAGE even without sentinel text; everything else falls back to the
    message scan.
    """
    if isinstance(exc, (ConnectionError, TimeoutError, InterruptedError)):
        return OutageClass.OUTAGE
    if is_outage_text(f"{type(exc).__name__}: {exc}"):
        return OutageClass.OUTAGE
    if isinstance(exc, OSError):
        # a transient filesystem/network hiccup (EIO on a flaky NFS
        # checkpoint dir, ENOSPC races) — worth one backoff cycle
        return OutageClass.OUTAGE
    return OutageClass.UNKNOWN


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff with deterministic jitter.

    One policy object describes *how* to retry; the decision *whether* a
    failure is retryable belongs to :func:`classify` /
    :func:`classify_exception` (or the caller's ``retry_on``). Jitter is
    seeded so chaos tests replay identical schedules.

    ``attempts`` counts total tries (first call included), matching the
    W&B sink's historical ``max_retries`` semantics.
    """

    attempts: int = 3
    base_delay_s: float = 1.0
    max_delay_s: float = 60.0
    multiplier: float = 2.0
    jitter_frac: float = 0.1
    seed: int = 0

    def delays(self) -> Iterator[float]:
        """The backoff schedule: one delay per retry (attempts - 1 of them)."""
        rng = random.Random(self.seed)
        delay = self.base_delay_s
        for _ in range(max(0, self.attempts - 1)):
            jitter = delay * self.jitter_frac
            yield max(0.0, min(self.max_delay_s, delay)
                      + rng.uniform(-jitter, jitter))
            delay *= self.multiplier

    def run(
        self,
        fn: Callable,
        *,
        retry_on: Callable[[BaseException], bool] | None = None,
        sleep: Callable[[float], None] = time.sleep,
        on_retry: Callable[[int, BaseException, float], None] | None = None,
    ):
        """Call ``fn()`` with this policy; re-raise the last failure.

        ``retry_on`` gates which exceptions are worth another attempt
        (default: anything the shared classifier does not call
        DETERMINISTIC). ``on_retry(attempt_index, exc, delay_s)`` observes
        each scheduled retry.
        """
        if retry_on is None:
            retry_on = (
                lambda e: classify_exception(e) is not OutageClass.DETERMINISTIC
            )
        delays = self.delays()
        for attempt in range(self.attempts):
            try:
                return fn()
            except Exception as e:  # noqa: BLE001 — gated by retry_on below
                delay = next(delays, None)
                if delay is None or not retry_on(e):
                    raise
                if on_retry is not None:
                    on_retry(attempt, e, delay)
                sleep(delay)
        raise AssertionError("unreachable: loop either returns or raises")


class CircuitBreaker:
    """Classic three-state breaker with half-open probes.

    CLOSED — calls flow; ``failure_threshold`` consecutive failures open it.
    OPEN   — calls are refused (``allow()`` is False) until
             ``reset_timeout_s`` has elapsed.
    HALF_OPEN — up to ``half_open_probes`` trial calls are allowed; one
             success closes the breaker, one failure re-opens it (and
             restarts the timeout).

    ``clock`` is injectable so tests advance time deterministically.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(
        self,
        failure_threshold: int = 3,
        reset_timeout_s: float = 60.0,
        half_open_probes: int = 1,
        clock: Callable[[], float] = time.monotonic,
    ):
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        self.failure_threshold = failure_threshold
        self.reset_timeout_s = reset_timeout_s
        self.half_open_probes = max(1, half_open_probes)
        self._clock = clock
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at: float | None = None
        self._probes_in_flight = 0

    @property
    def state(self) -> str:
        self._maybe_half_open()
        return self._state

    def _maybe_half_open(self) -> None:
        if (
            self._state == self.OPEN
            and self._opened_at is not None
            and self._clock() - self._opened_at >= self.reset_timeout_s
        ):
            self._state = self.HALF_OPEN
            self._probes_in_flight = 0

    def allow(self) -> bool:
        """May the caller attempt the protected operation now?"""
        self._maybe_half_open()
        if self._state == self.CLOSED:
            return True
        if self._state == self.HALF_OPEN:
            if self._probes_in_flight < self.half_open_probes:
                self._probes_in_flight += 1
                return True
            return False
        return False

    def record_success(self) -> None:
        self._failures = 0
        self._probes_in_flight = 0
        self._state = self.CLOSED
        self._opened_at = None

    def record_failure(self) -> None:
        self._maybe_half_open()
        if self._state == self.HALF_OPEN:
            # the trial call failed: straight back to OPEN, fresh timeout
            self._state = self.OPEN
            self._opened_at = self._clock()
            self._probes_in_flight = 0
            return
        self._failures += 1
        if self._failures >= self.failure_threshold:
            self._state = self.OPEN
            self._opened_at = self._clock()
