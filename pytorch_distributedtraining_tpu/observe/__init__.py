"""Observability: metrics sinks (W&B-compatible), profiling, span telemetry.

Twin of the reference's L7 layer (`/root/reference/Stoke-DDP.py`): W&B login
/init-with-retry/log/finish (`:43,316-325,47-58,339`), rank-aware prints,
plus the tracing the reference lacks (SURVEY §5) — `jax.profiler` hooks and
the program's own spans.
"""

# PEP 562 lazy exports: the serve fleet's control plane (serve/router.py,
# serve/fleet.py — replica processes under GRAFT_FLEET_FAKE=1) and the other
# jax-free tooling import the stdlib-only submodules here (slo, goodput,
# fleet, opcost, hlo); an eager `from .memory import ...` would drag jax into
# every one of them. Name -> (submodule, attr): submodule None = the submodule
# named `name` itself; attr "*" = the submodule object under an alias; attr
# None = the attribute named `name`.
_LAZY = {
    "wandb": ("wandb_compat", "*"),
    "hlo": (None, None),
    "WIRE_NARROW_DTYPES": ("hlo", None),
    "CollectiveOp": ("hlo", None),
    "HloInstruction": ("hlo", None),
    "OverlapAudit": ("hlo", None),
    "OverlapFinding": ("hlo", None),
    "PipelineAudit": ("hlo", None),
    "WireCollective": ("hlo", None),
    "collective_inventory": ("hlo", None),
    "collectives_schedulable": ("hlo", None),
    "counts": ("hlo", None),
    "has_logical_reduce_scatter": ("hlo", None),
    "max_all_reduce_elems": ("hlo", None),
    "overlap_audit": ("hlo", None),
    "pipeline_audit": ("hlo", None),
    "tokenize_hlo": ("hlo", None),
    "wire_inventory": ("hlo", None),
    "memory": (None, None),
    "MemoryStats": ("memory", None),
    "compiled_memory_stats": ("memory", None),
    "device_hbm_budget": ("memory", None),
    "host_memory_budget": ("memory", None),
    "record_hbm_stats": ("memory", None),
    "tune_batch_size": ("memory", None),
    "opcost": (None, None),
    "calibrate": ("opcost", None),
    "collective_bandwidth": ("opcost", None),
    "load_trace_events": ("opcost", None),
    "op_table": ("opcost", None),
    "capture": (None, None),
    "OnDemandProfiler": ("capture", None),
    "trace": (None, None),
    "goodput": (None, None),
    "GoodputLedger": ("goodput", None),
    "StepLog": ("goodput", None),
    "StragglerReport": ("goodput", None),
    "flag_stragglers": ("goodput", None),
    "mfu": ("goodput", None),
    "model_train_flops": ("goodput", None),
    "peak_flops": ("goodput", None),
    "read_step_logs": ("goodput", None),
    "straggler_check": ("goodput", None),
    "fleet": (None, None),
    "ClockOffset": ("fleet", None),
    "FleetMonitor": ("fleet", None),
    "MetricsExporter": ("fleet", None),
    "RankMetricsPublisher": ("fleet", None),
    "StreamHist": ("fleet", None),
    "estimate_offset": ("fleet", None),
    "estimate_store_offset": ("fleet", None),
    "lane_ledgers": ("fleet", None),
    "load_trajectory": ("fleet", None),
    "merge_ledgers": ("fleet", None),
    "merge_traces": ("fleet", None),
    "per_host_mfu": ("fleet", None),
    "regression_verdict": ("fleet", None),
    "slo": (None, None),
    "numerics": (None, None),
    "sink": (None, None),
    "JSONLSink": ("sink", None),
    "MetricsSink": ("sink", None),
    "NullSink": ("sink", None),
    "WandbSink": ("sink", None),
    "make_sink": ("sink", None),
    "profiling": (None, None),
    "profiler_trace": ("profiling", "trace"),
    "Tracer": ("trace", None),
    "export_chrome_trace": ("trace", None),
    "flush_flight_record": ("trace", None),
    "instant": ("trace", None),
    "span": ("trace", None),
}


def __getattr__(name):
    try:
        submodule, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    from importlib import import_module

    if submodule is None:
        return import_module(f".{name}", __name__)
    mod = import_module(f".{submodule}", __name__)
    if attr == "*":
        return mod
    return getattr(mod, attr or name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY))


__all__ = [
    "wandb",
    "MetricsSink",
    "JSONLSink",
    "NullSink",
    "WandbSink",
    "make_sink",
    "trace",
    "profiler_trace",
    "Tracer",
    "span",
    "instant",
    "export_chrome_trace",
    "flush_flight_record",
    "GoodputLedger",
    "StepLog",
    "StragglerReport",
    "flag_stragglers",
    "straggler_check",
    "read_step_logs",
    "mfu",
    "model_train_flops",
    "peak_flops",
    "CollectiveOp",
    "HloInstruction",
    "tokenize_hlo",
    "collective_inventory",
    "WireCollective",
    "wire_inventory",
    "WIRE_NARROW_DTYPES",
    "counts",
    "has_logical_reduce_scatter",
    "max_all_reduce_elems",
    "OverlapAudit",
    "OverlapFinding",
    "overlap_audit",
    "collectives_schedulable",
    "PipelineAudit",
    "pipeline_audit",
    "MemoryStats",
    "compiled_memory_stats",
    "device_hbm_budget",
    "host_memory_budget",
    "record_hbm_stats",
    "tune_batch_size",
    "opcost",
    "load_trace_events",
    "op_table",
    "collective_bandwidth",
    "calibrate",
    "OnDemandProfiler",
    "fleet",
    "StreamHist",
    "ClockOffset",
    "estimate_offset",
    "estimate_store_offset",
    "merge_traces",
    "lane_ledgers",
    "merge_ledgers",
    "per_host_mfu",
    "MetricsExporter",
    "RankMetricsPublisher",
    "FleetMonitor",
    "load_trajectory",
    "regression_verdict",
]
