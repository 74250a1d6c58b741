"""Resilience: fault injection and outage classification.

The reference stack's robustness contract is implicit (elastic restarts,
rendezvous retry, preemption save — SURVEY §5) and was never adversarially
exercised; every layer classified and retried failures its own way. This
package makes the contract explicit and shared:

- :mod:`.faults` — a deterministic fault-injection harness
  (:class:`FaultPlan` + :func:`fault_point`): env/JSON-driven failures at
  named sites threaded through the launcher, rendezvous, data loader and
  checkpoint writer, so every recovery path has a repeatable chaos test
  instead of hoping.
- :mod:`.outage` — ONE outage classifier (:func:`classify`,
  :func:`classify_exception`) plus :class:`RetryPolicy` (exponential
  backoff + deterministic jitter) and :class:`CircuitBreaker` (half-open
  probes), reused by the launcher's restart monitor, the rendezvous,
  checkpoint writes and the W&B sink — no ad-hoc sentinel string matching
  per call site.

Everything here is stdlib-only at import time: the launcher (which must
stay jax-free) and spawn-context loader workers both import it.
"""

from .faults import (
    FaultPlan,
    FaultRule,
    InjectedFault,
    fault_point,
    install_plan,
)
from .outage import (
    CircuitBreaker,
    OutageClass,
    RetryPolicy,
    classify,
    classify_exception,
    external_termination,
)

__all__ = [
    "CircuitBreaker",
    "FaultPlan",
    "FaultRule",
    "InjectedFault",
    "OutageClass",
    "RetryPolicy",
    "classify",
    "classify_exception",
    "external_termination",
    "fault_point",
    "install_plan",
]
