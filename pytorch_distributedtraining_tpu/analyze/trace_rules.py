"""Trace-plane rules: hazards visible in the abstract-evaluated jaxpr.

These run before any XLA work — on ``jax.make_jaxpr(step)(...)`` — so
they catch mistakes (host round-trips in the hot path, retrace-prone
captures, giant baked-in constants) at zero device cost. All thresholds
and primitive names here were probed against the pinned jax version;
see docs/STATIC_ANALYSIS.md for the catalog.
"""

from __future__ import annotations

from .findings import Finding, Severity
from .registry import rule

# closure-captured constants baked into the module: above WARN they bloat
# the executable and defeat donation; above ERROR they are almost
# certainly a missing function argument (weights captured by accident)
GIANT_CONST_WARN_BYTES = 1 << 20    # 1 MiB
GIANT_CONST_ERROR_BYTES = 128 << 20  # 128 MiB

# primitive name -> why it is a hazard in a hot train step
_CALLBACK_PRIMS = {
    "io_callback": (
        "io_callback forces an ordered host round-trip every step; the "
        "device pipeline drains while the host runs Python"
    ),
    "debug_callback": (
        "jax.debug.print/callback inserts a host transfer in the step; "
        "fine for debugging, a throughput hazard when left in"
    ),
    "pure_callback": (
        "pure_callback runs Python on the host mid-step; move the "
        "computation into jax or hoist it out of the jitted step"
    ),
}


def _walk_eqns(jaxpr):
    """Yield every eqn in a jaxpr, recursing into sub-jaxprs carried in
    eqn params (scan/while/cond bodies, remat, pjit, custom_vjp...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _subjaxprs(v):
    if hasattr(v, "eqns"):  # raw Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):  # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _subjaxprs(item)


@rule(
    "host-callback",
    "trace",
    "host round-trips (io/debug/pure callback) inside the jitted step",
)
def host_callback(ctx):
    if ctx.jaxpr is None:
        return
    seen: dict = {}
    for eqn in _walk_eqns(ctx.jaxpr.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            seen[eqn.primitive.name] = seen.get(eqn.primitive.name, 0) + 1
    for prim, n in sorted(seen.items()):
        if prim == "io_callback":
            sev = Severity.ERROR
        elif prim == "debug_callback" and ctx.detect_anomaly:
            # TrainStep(detect_anomaly=True) plants exactly this callback
            # on purpose — report it, but as informational
            sev = Severity.INFO
        else:
            sev = Severity.WARN
        yield Finding(
            "host-callback",
            sev,
            "jaxpr",
            f"{n}× {prim} in the step: {_CALLBACK_PRIMS[prim]}",
            evidence=f"primitive={prim} count={n}",
        )


@rule(
    "weak-type-capture",
    "trace",
    "Python scalars traced as weak-typed args retrace on dtype promotion",
)
def weak_type_capture(ctx):
    if ctx.jaxpr is None:
        return
    for i, var in enumerate(ctx.jaxpr.jaxpr.invars):
        aval = getattr(var, "aval", None)
        if getattr(aval, "weak_type", False):
            yield Finding(
                "weak-type-capture",
                Severity.WARN,
                f"jaxpr:invar[{i}]",
                "argument traced from a Python scalar (weak-typed "
                f"{aval.dtype}): passing a different Python type later "
                "(int vs float vs np scalar) retraces and recompiles; "
                "wrap it, e.g. jnp.float32(x), at the call site",
                evidence=f"aval={aval}",
            )


@rule(
    "static-arg-hashable",
    "trace",
    "static_argnums values must hash stably or every call recompiles",
)
def static_arg_hashable(ctx):
    for i, v in enumerate(ctx.static_args):
        try:
            hash(v)
        except TypeError:
            yield Finding(
                "static-arg-hashable",
                Severity.ERROR,
                f"static_args[{i}]",
                f"static argument of type {type(v).__name__} is "
                "unhashable: jit will raise at call time",
                evidence=repr(v)[:120],
            )
            continue
        cls = type(v)
        if (
            cls.__hash__ is object.__hash__
            and not isinstance(v, type)
        ):
            yield Finding(
                "static-arg-hashable",
                Severity.WARN,
                f"static_args[{i}]",
                f"static argument of type {cls.__name__} hashes by "
                "object identity: two equal configs built separately "
                "compile twice; use a frozen dataclass or tuple",
                evidence=repr(v)[:120],
            )


@rule(
    "giant-constant",
    "trace",
    "closure-captured arrays baked into the module as constants",
)
def giant_constant(ctx):
    if ctx.jaxpr is None:
        return
    for var, const in zip(ctx.jaxpr.jaxpr.constvars, ctx.jaxpr.consts):
        nbytes = getattr(const, "nbytes", 0)
        if nbytes < GIANT_CONST_WARN_BYTES:
            continue
        sev = (
            Severity.ERROR
            if nbytes >= GIANT_CONST_ERROR_BYTES
            else Severity.WARN
        )
        shape = getattr(const, "shape", ())
        dtype = getattr(const, "dtype", "?")
        yield Finding(
            "giant-constant",
            sev,
            "jaxpr:consts",
            f"step closes over a {nbytes / (1 << 20):.1f} MiB constant "
            f"({dtype}{list(shape)}): it is baked into the executable, "
            "re-uploaded per compile, and invisible to donation; pass it "
            "as an argument instead",
            evidence=f"constvar={var} nbytes={nbytes}",
        )


@rule(
    "remat-tag-coverage",
    "trace",
    "names-based remat policies need checkpoint_name tags in the model",
)
def remat_tag_coverage(ctx):
    if ctx.jaxpr is None or ctx.remat in (None, False):
        return
    from ..parallel.remat import kept_names, resolve_remat

    try:
        policy = resolve_remat(ctx.remat)
    except ValueError:
        return  # bad remat strings are the Policy validator's problem
    if policy not in ("names", "offload"):
        return
    tags = set()
    for eqn in _walk_eqns(ctx.jaxpr.jaxpr):
        if eqn.primitive.name == "name":
            tags.add(eqn.params.get("name"))
    # what the policy keeps beyond ``full`` (which keeps the attention
    # kernels' residuals too): the activations the models tag
    saved = set(kept_names(policy)) - set(kept_names("full"))
    if not (tags & saved):
        yield Finding(
            "remat-tag-coverage",
            Severity.WARN,
            "jaxpr",
            f"remat policy {policy!r} saves, beyond what 'full' keeps, "
            f"only tagged activations ({sorted(saved)}) but the traced "
            "step contains "
            + (
                f"no checkpoint_name tags"
                if not tags
                else f"only tags {sorted(tags)}"
            )
            + ": everything gets rematerialized, so the policy "
            "silently behaves like remat='full'",
            evidence=f"declared={sorted(saved)} traced={sorted(tags)}",
        )
