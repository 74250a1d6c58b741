"""Profiling — capability the reference lacks (SURVEY §5:
"Tracing/profiling: none").

- :func:`trace`: context manager around ``jax.profiler`` writing a
  TensorBoard-loadable trace (XLA op-level, HBM, ICI traffic on TPU). The
  program's own spans (``observe.trace``) are in every such profile as
  ``graft/<name>`` annotations, with no knob.
- :func:`remember_program` / :func:`program_texts`: the compiled HLO text
  of the programs the process ran, for whoever reads a device trace. A
  TPU trace names each executed op by its HLO instruction and carries no
  ``op_name``; the text maps the instruction to the scopes the program
  gave it (``jax.named_scope``, Flax module paths, ``jvp`` / ``transpose``
  / ``checkpoint``).
"""

from __future__ import annotations

import contextlib
import os
import tempfile
import warnings
import weakref

# the profiler is a process-global singleton in jax: a second
# start_trace raises. This module owns the arbitration so the manual
# --trace context manager and the on-demand anomaly capture
# (observe/capture.py) can coexist — whoever starts first wins, the
# second entrant becomes a no-op with a WARN instant.
_ACTIVE: dict = {"logdir": None}


def profiler_active() -> str | None:
    """The logdir of the trace this module started, or None."""
    return _ACTIVE["logdir"]


def _note_reentrant(logdir: str) -> None:
    warnings.warn(
        f"jax profiler trace already active (-> {_ACTIVE['logdir']!r}); "
        f"request for {logdir!r} is a no-op",
        RuntimeWarning,
        stacklevel=3,
    )
    from . import trace as _telemetry

    if _telemetry.enabled():
        _telemetry.instant(
            "profiler.reentrant", "profile",
            active=_ACTIVE["logdir"], requested=logdir,
        )


def start_profiler_trace(logdir: str) -> bool:
    """Guarded ``jax.profiler.start_trace``: True when this call started
    a trace, False when one was already active (no-op + WARN instant —
    never the RuntimeError jax raises on re-entry)."""
    if _ACTIVE["logdir"] is not None:
        _note_reentrant(logdir)
        return False
    import jax

    try:
        jax.profiler.start_trace(logdir)
    except RuntimeError:
        # someone started a trace through the raw jax API, bypassing
        # this guard — same verdict as the guarded case
        _note_reentrant(logdir)
        return False
    _ACTIVE["logdir"] = logdir
    return True


def stop_profiler_trace() -> None:
    """Stop the trace :func:`start_profiler_trace` started (no-op when
    this module owns none — never stops someone else's trace)."""
    if _ACTIVE["logdir"] is None:
        return
    import jax

    try:
        jax.profiler.stop_trace()
    finally:
        _ACTIVE["logdir"] = None


@contextlib.contextmanager
def trace(logdir: str | None = None):
    """Profile the block into ``logdir`` (default ``jax-trace`` under the
    system's temporary directory, which follows ``TMPDIR``)."""
    if logdir is None:
        logdir = os.path.join(tempfile.gettempdir(), "jax-trace")
    started = start_profiler_trace(logdir)
    try:
        yield logdir
    finally:
        if started:
            stop_profiler_trace()


# -- the compiled text of the programs that ran --------------------------
#
# A step class or the facade calls ``remember_program`` at the FIRST call
# of each jitted program it owns: one ``tree.map`` of the arguments to
# their abstract signature, once; nothing is lowered or compiled then.
# ``program_texts`` lowers and compiles from the signatures on demand —
# after a measured window, through the persistent compilation cache.

# jitted function -> (args, kwargs, mesh); an entry goes with its program
_PROGRAMS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def _signature(x):
    """An array as its ``ShapeDtypeStruct`` (with the sharding it was
    committed to, which a jit without ``in_shardings`` lowers by);
    anything else (a static flag, ``None``) as itself."""
    import jax

    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.aval.weak_type,
        sharding=x.sharding if x.committed else None,
    )


def _ambient_mesh():
    """The mesh of an enclosing ``with mesh:``, or None. It is part of a
    jit's tracing context, and the caller's to choose: lowered under
    another one, a program is traced anew (19 s for the SwinIR step on the
    chip, PR 24) instead of found. jax has no public reader for it; where
    the private one has moved, say so: the texts then come slowly."""
    try:
        from jax._src.mesh import thread_resources

        mesh = thread_resources.env.physical_mesh
    except (ImportError, AttributeError) as e:
        warnings.warn(
            f"remember_program: the ambient mesh cannot be read ({e}); "
            "program_texts may trace a program anew", RuntimeWarning,
        )
        return None
    return None if mesh.empty else mesh


def remember_program(jitted, args, kwargs=None) -> None:
    """Keep the abstract signature ``jitted`` is first called with, and
    the mesh context it is called in."""
    import jax

    _PROGRAMS[jitted] = (
        jax.tree.map(_signature, args),
        jax.tree.map(_signature, dict(kwargs or {})), _ambient_mesh(),
    )


def program_texts() -> list:
    """Compiled HLO text (``compiled.as_text()``, instruction metadata
    included) of every remembered program whose owner is still alive. A
    program that no longer lowers from its signature is left out."""
    texts = []
    for jitted, (args, kwargs, mesh) in list(_PROGRAMS.items()):
        try:
            with mesh or contextlib.nullcontext():
                texts.append(
                    jitted.lower(*args, **kwargs).compile().as_text()
                )
        except Exception as e:  # noqa: BLE001 — a reader's aid, never fatal
            warnings.warn(
                f"program_texts: {type(e).__name__}: {e}", RuntimeWarning
            )
    return texts
