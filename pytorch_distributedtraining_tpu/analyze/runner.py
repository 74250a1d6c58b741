"""Assemble an AnalysisContext from a live step and run the registry.

``analyze_step`` is the one entry point every integration uses — the
CLI, the stoke facade's ``GRAFT_ANALYZE`` hook, both drivers'
``--analyze`` flags and the ``__graft_entry__`` dryrun. It
AOT-lowers the step (CPU-safe: ``compiled_text`` goes through
``lower().compile()`` without executing) and abstract-evaluates the
jaxpr, then feeds both artifacts to every registered rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# importing the rule modules populates the registry
from . import hlo_rules as _hlo_rules  # noqa: F401
from . import runtime_rules as _runtime_rules  # noqa: F401
from . import source_rules as _source_rules  # noqa: F401
from . import trace_rules as _trace_rules  # noqa: F401
from .findings import Report
from .registry import PLANES, RULES, AnalysisContext, run_rules


def step_jaxpr(step, state, batch, lr_factor=1.0):
    """ClosedJaxpr of the step's uncompiled body, or None if tracing
    outside jit is impossible for this step (shard_map constraints)."""
    try:
        with step.mesh:
            return jax.make_jaxpr(step._step)(
                state, batch, jnp.float32(lr_factor)
            )
    except Exception:
        return None


def build_context(step, state, batch, lr_factor=1.0, *, static_args=(),
                  hlo=True, **extra) -> AnalysisContext:
    """Inspect a TrainStep/PipelineStep-shaped object into a context.

    ``hlo=False`` skips AOT compilation (trace-plane only — much
    cheaper, no XLA invocation).
    """
    hlo_text = (
        step.compiled_text(state, batch, lr_factor=lr_factor) if hlo else ""
    )
    devs = getattr(step.mesh, "devices", None)
    platform = (
        devs.flat[0].platform if devs is not None and devs.size else ""
    )
    policy = getattr(step, "policy", None)
    params = getattr(state, "params", None)
    ctx = AnalysisContext(
        jaxpr=step_jaxpr(step, state, batch, lr_factor),
        hlo_text=hlo_text,
        mesh=step.mesh,
        policy=policy,
        donate=getattr(step, "donate", False),
        detect_anomaly=getattr(step, "detect_anomaly", False),
        remat=getattr(policy, "remat", None),
        schedule=getattr(step, "schedule", None),
        platform=platform,
        params=params,
        static_args=tuple(static_args),
    )
    # CompressedGradStep (and the wire fixtures) carry their WireFormat
    # on .wire — auto-thread it so the bytes-on-wire rule sees it without
    # every caller plumbing an extra kwarg
    extra.setdefault("wire", getattr(step, "wire", None))
    # HierGradStep carries its slice axis on .dcn_axis (fixtures may set
    # .hier directly) — the dcn-flat-ring rule audits that claim
    extra.setdefault(
        "hier",
        getattr(step, "dcn_axis", None) or getattr(step, "hier", None),
    )
    for k, v in extra.items():
        setattr(ctx, k, v)
    return ctx


def analyze_step(step, state, batch, lr_factor=1.0, *, static_args=(),
                 planes=PLANES, ignore=None, **extra) -> Report:
    """Run the full rule registry over one step. Returns a Report."""
    ctx = build_context(
        step, state, batch, lr_factor,
        static_args=static_args, hlo="hlo" in planes, **extra,
    )
    return run_rules(ctx, planes=planes, ignore=ignore)


def rule_catalog() -> list:
    """(name, plane, doc) for every registered rule, for --list-rules."""
    return [(r.name, r.plane, r.doc) for r in RULES.values()]
