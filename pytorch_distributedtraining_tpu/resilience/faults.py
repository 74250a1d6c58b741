"""Deterministic fault injection: FaultPlan + fault_point hooks.

Every recovery path in this stack (elastic restarts, rendezvous retry,
preemption save, loader worker replacement, checkpoint-write retry) existed
before this module — but none were ever
*exercised* except by a real pool flap. A :class:`FaultPlan` injects the
failure repeatably so the chaos tests in ``tests/test_resilience.py`` can
assert recovery instead of hoping.

Named sites (each threaded into the layer that owns it):

=====================  =====================================================
``launch.worker``      launcher monitor SIGKILLs a chosen local rank
                       mid-generation (``runtime/launch.py``)
``dist.rendezvous``    coordinator handshake fails before
                       ``jax.distributed.initialize`` (``runtime/dist.py``)
``collective.barrier`` coordination barrier raises a pool-style
                       ``UNAVAILABLE`` error (``runtime/dist.py``)
``loader.fetch``       a data-loader worker crashes fetching a sample
                       (``data/loader.py``, thread and process paths)
``loader.stage``       H2D staging of a prefetched batch fails; the
                       prefetcher degrades to synchronous feeding
                       (``data/prefetch.py``)
``checkpoint.write``   transient I/O error on a checkpoint write
                       (``checkpoint_sharded.py``)
``ckpt.write``         kill/delay INSIDE the background checkpoint writer
                       — manufactures torn (uncommitted) step dirs for
                       crash-consistency drills (``checkpoint_sharded.py``)
``train.preempt``      mid-step SIGTERM preemption, delivered to self at a
                       chosen ``maybe_save`` call (``checkpoint_sharded.py``)
``launch.grow``        elastic launcher is about to initiate a grow-back
                       reshard — ``raise`` vetoes this grow attempt (the
                       gate re-arms), ``sleep`` delays the teardown
                       (``runtime/launch.py``)
``membership.heartbeat`` a host's membership heartbeat is dropped — the
                       host ages out of the live set and cannot be grown
                       onto (``runtime/membership.py``)
``serve.admit``        admission controller sheds a request at admission
                       — ``raise`` drops it, counted, engine keeps serving
                       (``serve/scheduler.py``)
``serve.client``       client misbehaves at delivery: ``sleep`` is a slow
                       reader stalling the tick loop, ``raise`` a
                       disconnect cancelling the request
                       (``serve/engine.py``, ``serve/tiles.py``)
``route.dispatch``     router is about to pick a replica for a dispatch
                       attempt — ``raise`` skips the attempt (burns retry
                       budget), ``sleep`` delays it (``serve/router.py``)
``replica.kill``       serve replica dies mid-decode — ``kill`` is the
                       chaos drill's SIGKILL-equivalent; the router must
                       fail over every resident request
                       (``serve/fleet.py``)
``replica.drain``      serve replica is about to migrate its resident
                       decode state out — ``raise`` forces the replay
                       path instead of the migrate path
                       (``serve/fleet.py``)
``comm.dcn``           the inter-slice (DCN) gradient sync is about to
                       dispatch — ``sleep`` models a degraded DCN link
                       stretching every two-level sync; the slow-slice
                       degradation drill rides this
                       (``parallel/hierarchy.py``)
=====================  =====================================================

A plan is JSON — inline in ``GRAFT_FAULT_PLAN`` or a file path — so it
crosses process boundaries for free (the launcher's children and
spawn-context loader workers inherit the env)::

    {"faults": [
        {"site": "loader.fetch", "at": 3, "times": 1,
         "action": "raise", "message": "injected decode crash"},
        {"site": "collective.barrier", "attempt": 0, "rank": 1,
         "action": "raise", "message": "UNAVAILABLE: TPU backend (injected)"},
        {"site": "launch.worker", "attempt": 0, "rank": 1, "after_s": 0.5}
    ]}

Rule fields: ``site`` (required); ``action`` — ``raise`` (default,
:class:`InjectedFault`), ``oserror``, ``exit``, ``kill`` (SIGKILL self),
``sigterm`` (SIGTERM self), ``sleep`` (simulate a hang); ``at`` — fire on
the Nth hit of the site, 1-based (default 1); ``times`` — consecutive hits
that fire (default 1; 0 = every hit from ``at`` on); ``rank`` — only in the
process whose ``RANK``/``LOCAL_RANK`` env matches; ``attempt`` — only when
``GRAFT_RESTART_ATTEMPT`` matches (hit counters reset per process, so
cross-generation schedules key on the launcher's attempt counter);
``match`` — equality constraints on the call-site context kwargs;
``message`` / ``arg`` — error text / action argument (exit code, sleep
seconds); ``after_s`` — delay for monitor-driven sites (``launch.worker``).

Stdlib-only; when no plan is installed, :func:`fault_point` is a dict
lookup and a ``None`` check — safe on hot paths.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time
from dataclasses import dataclass, field
from typing import Any

ENV_VAR = "GRAFT_FAULT_PLAN"

_VALID_ACTIONS = ("raise", "oserror", "exit", "kill", "sigterm", "sleep")

SITES = frozenset({
    "launch.worker",
    "launch.grow",
    "membership.heartbeat",
    "dist.rendezvous",
    "collective.barrier",
    "loader.fetch",
    "loader.stage",
    "checkpoint.write",
    "ckpt.write",
    "train.preempt",
    "serve.admit",
    "serve.client",
    "route.dispatch",
    "replica.kill",
    "replica.drain",
    "comm.dcn",
})


class InjectedFault(RuntimeError):
    """An error raised on purpose by a FaultPlan rule."""


def _telemetry_on_fire(site: str, action: str, msg: str) -> None:
    """Mark the injection in the telemetry stream, if telemetry is loaded.

    Looked up via ``sys.modules`` — never imported — so this module keeps
    its stdlib-only contract (the jax-free launcher imports it). When the
    tracer is live, the injection lands as an instant event and the
    flight recorder is flushed BEFORE the action executes:
    for ``kill``/``exit`` actions this flush is the only record the process
    leaves behind.
    """
    tr = sys.modules.get("pytorch_distributedtraining_tpu.observe.trace")
    if tr is None:
        return
    try:
        if tr.enabled():
            tr.instant(f"fault.{site}", "fault", action=action, message=msg)
            tr.flush_flight_record(f"fault:{site}")
    except Exception:
        pass  # injection semantics must never depend on telemetry health


@dataclass
class FaultRule:
    """One deterministic failure schedule at one site."""

    site: str
    action: str = "raise"
    at: int = 1
    times: int = 1
    rank: int | None = None
    attempt: int | None = None
    match: dict[str, Any] = field(default_factory=dict)
    message: str | None = None
    arg: float | None = None
    after_s: float = 0.0
    hits: int = 0  # per-process hit counter (mutable state)

    def __post_init__(self):
        if self.site not in SITES:
            raise ValueError(
                f"unknown fault site {self.site!r}; valid: {sorted(SITES)}"
            )
        if self.action not in _VALID_ACTIONS:
            raise ValueError(
                f"unknown fault action {self.action!r}; "
                f"valid: {_VALID_ACTIONS}"
            )
        if self.at < 1:
            raise ValueError(f"at must be >= 1 (1-based), got {self.at}")

    # -- matching ----------------------------------------------------------

    def _env_rank(self) -> int:
        for var in ("RANK", "LOCAL_RANK"):
            raw = os.environ.get(var)
            if raw:
                try:
                    return int(raw)
                except ValueError:
                    pass
        return 0

    def applies(self, **ctx) -> bool:
        """Static filters only (rank/attempt/match) — no counter movement."""
        if self.rank is not None and self.rank != self._env_rank():
            return False
        if self.attempt is not None:
            cur = int(os.environ.get("GRAFT_RESTART_ATTEMPT", "0") or 0)
            if self.attempt != cur:
                return False
        return all(ctx.get(k) == v for k, v in self.match.items())

    def should_fire(self, **ctx) -> bool:
        """Advance the hit counter; True when this hit is scheduled."""
        if not self.applies(**ctx):
            return False
        self.hits += 1
        if self.hits < self.at:
            return False
        return self.times <= 0 or self.hits < self.at + self.times

    # -- firing ------------------------------------------------------------

    def fire(self, site_msg: str) -> None:
        msg = self.message or f"injected fault at {site_msg}"
        _telemetry_on_fire(site_msg, self.action, msg)
        if self.action == "raise":
            raise InjectedFault(msg)
        if self.action == "oserror":
            import errno

            raise OSError(errno.EIO, msg)
        if self.action == "exit":
            os._exit(int(self.arg) if self.arg is not None else 1)
        if self.action == "kill":
            os.kill(os.getpid(), signal.SIGKILL)
        if self.action == "sigterm":
            os.kill(os.getpid(), signal.SIGTERM)
        if self.action == "sleep":
            time.sleep(float(self.arg) if self.arg is not None else 3600.0)


class FaultPlan:
    """A parsed set of :class:`FaultRule`\\ s with per-process counters."""

    def __init__(self, rules: list[FaultRule]):
        self.rules = list(rules)

    @classmethod
    def from_json(cls, obj: dict | list) -> "FaultPlan":
        if isinstance(obj, dict):
            obj = obj.get("faults", [])
        rules = []
        for raw in obj:
            unknown = set(raw) - {
                "site", "action", "at", "times", "rank", "attempt",
                "match", "message", "arg", "after_s",
            }
            if unknown:
                # a typoed key would silently never fire — fail loudly
                raise ValueError(
                    f"fault rule has unknown keys {sorted(unknown)}: {raw}"
                )
            rules.append(FaultRule(**raw))
        return cls(rules)

    @classmethod
    def from_env(cls, env_var: str = ENV_VAR) -> "FaultPlan | None":
        """Parse ``$GRAFT_FAULT_PLAN`` — inline JSON or a file path."""
        raw = os.environ.get(env_var, "").strip()
        if not raw:
            return None
        if raw.startswith("@"):
            raw = raw[1:]
        if not raw.lstrip().startswith(("{", "[")):
            with open(raw) as fh:
                raw = fh.read()
        return cls.from_json(json.loads(raw))

    def rules_for(self, site: str) -> list[FaultRule]:
        return [r for r in self.rules if r.site == site]

    def point(self, site: str, **ctx) -> None:
        """Hit ``site``; fire the first scheduled rule (if any)."""
        for rule in self.rules:
            if rule.site == site and rule.should_fire(**ctx):
                rule.fire(site)
                return


# -- module-level hook -------------------------------------------------------

# tri-state: "unset" = env not yet consulted; None = no plan (fast path)
_PLAN: FaultPlan | None | str = "unset"


def install_plan(plan: FaultPlan | None) -> None:
    """Install (or clear, with None) the process-wide plan — test hook."""
    global _PLAN
    _PLAN = plan


def active_plan() -> FaultPlan | None:
    """The process-wide plan, lazily parsed from the env once."""
    global _PLAN
    if _PLAN == "unset":
        _PLAN = FaultPlan.from_env()
    return _PLAN


def fault_point(site: str, **ctx) -> None:
    """Declare a named fault site; a no-op unless a plan schedules it here.

    Call it at the exact place the real failure would surface — the hook's
    cost without a plan is one global read and a ``None`` check.
    """
    plan = active_plan()
    if plan is not None:
        plan.point(site, **ctx)
