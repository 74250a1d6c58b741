"""Host nanoseconds of ``observe.trace``'s spans, one JSON line an arm: what
the start-up ledger costs where it records (a span of category ``compile``,
a compile event through the listener) and what the warm dispatch path pays
(``dispatch_span`` of a warm owner, as ``TrainStep.__call__`` opens it).
Telemetry is off throughout. Host Python only: no device is touched, and
the numbers are the machine's they were taken on.

    python3 benchmarks/span_cost.py            # this tree
    (cd <another checkout> && python3 benchmarks/span_cost.py)

It asks nothing of the tree that PR 36 did not have, so it runs on PR 37's
parent for the "before" column (there a step class also called the
recompile probe after every dispatch: ``CHANGES.md``, PR 37 has that column
with the call added).
"""

import json
import statistics
import time

import _bootstrap  # noqa: F401
import jax  # noqa: F401  (spans are annotations only once jax is loaded)

from pytorch_distributedtraining_tpu.observe import trace

REPEATS, CALLS = 15, 20000


class _Owner:
    pass


def _ns_per_call(fn) -> float:
    """Median over ``REPEATS`` of the nanoseconds a call of ``fn``."""
    reads = []
    for _ in range(REPEATS):
        t0 = time.perf_counter_ns()
        for _ in range(CALLS):
            fn()
        reads.append((time.perf_counter_ns() - t0) / CALLS)
    return statistics.median(reads)


def main() -> None:
    trace.disable()
    owner = _Owner()
    with trace.dispatch_span(owner, "TrainStep"):
        pass  # the cold one

    def warm_dispatch():
        with trace.dispatch_span(owner, "TrainStep"):
            pass

    def cold_record():
        with trace.span("TrainStep.compile+dispatch", "compile", step=0):
            pass

    arms = {"warm_dispatch_span": warm_dispatch, "compile_span": cold_record}
    listener = getattr(trace, "_on_jax_duration", None)
    if listener is not None:
        arms["listener_small_trace"] = lambda: listener(
            "/jax/core/compile/jaxpr_trace_duration", 1e-4, fun_name="add"
        )
        arms["listener_kept_trace"] = lambda: listener(
            "/jax/core/compile/jaxpr_trace_duration", 0.02, fun_name="_step"
        )
    for name, fn in arms.items():
        print(json.dumps({
            "arm": name, "ns_per_call": round(_ns_per_call(fn), 1),
            "calls": CALLS, "repeats": REPEATS,
            "ledger": hasattr(trace, "startup_report"),
        }), flush=True)


if __name__ == "__main__":
    main()
