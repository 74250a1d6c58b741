"""Named activation-rematerialization policies (TorchTitan-style SAC).

The reference surface (`torch.utils.checkpoint` + TorchTitan's selective
activation checkpointing, PAPERS.md) exposes activation checkpointing as a
*policy choice*, not a boolean: full recompute, recompute-everything-but-
matmuls, or save a named subset of activations. This module is the single
registry mapping those names onto ``jax.checkpoint`` policies so every
consumer (``TrainStep``, the stoke facade's eager backward, model-internal
per-block remat under scan) resolves the same spelling to the same policy.

Policies
--------
``none``
    No checkpointing: every forward intermediate stays live for backward.
    Fastest step, highest activation HBM.
``full``
    ``jax.checkpoint`` keeping ``KERNEL_RESIDUALS`` and nothing else:
    backward recomputes the forward (~1/3 extra FLOPs, near-minimum HBM)
    except a blockwise attention kernel's, whose output and row statistics
    (`ops/pallas_attn.py` names them in its forward rules) are kept, because
    only running the whole kernel again would regain them. That is one
    ``[B, T, H * dh]`` activation and one float32 ``[B, H, T, 8]`` a layer:
    117.4 MB + 14.7 MB a layer at ``[1, 16384, 28, 128]`` bf16, the second
    padded to 128 lanes in a TPU's memory, 234.9 MB, so together 1.4 GB over
    the four layers of the SmallThinker cell (PERF.md §6, PR 36): twice to
    three times the layer input ``full`` always held. Where the wrapped
    block is a scan's body (``remat_block(in_scan=True)``, the pipeline's
    chunk) what is kept is stacked over the layers, and the statistics are
    kept as dense 128-lane rows instead (``KERNEL_LSE_ROWS``: 13.6 + 3.4 MB
    a layer of GPT-2 XL on a chip's share, 0.82 GB over 48, where the padded
    statistics alone were 2.6), at the price of two passes over the padded
    buffer a layer. Where no such kernel runs (the einsum core on a CPU, a
    caller's ``attn_fn``) nothing carries the names and nothing is saved.
    This is what ``remat=True`` means here.
``dots``
    ``checkpoint_dots`` and ``KERNEL_RESIDUALS``: save matmul/einsum outputs
    (and a kernel's residuals: a ``pallas_call`` is no dot), recompute the
    cheap elementwise/norm tail. Most of the memory win at a fraction of the
    recompute cost — the usual sweet spot on matmul-heavy transformers.
``names``
    ``save_only_these_names(*CHECKPOINT_SAVED_NAMES)``: save exactly the
    activations the models tag via ``jax.ad_checkpoint.checkpoint_name``
    (attention outputs, the expensive-to-recompute softmax+AV product) and
    ``KERNEL_RESIDUALS``, recompute everything else.
``offload``
    ``save_and_offload_only_these_names``: same named subset, but saved to
    pinned host memory instead of HBM (streamed back for backward). Zero
    activation HBM for the tagged set; needs a backend with host offload
    support to pay off.

No policy asks for a kernel to be run twice to regain two tensors.

Booleans remain accepted everywhere for backward compatibility:
``False → none``, ``True → full``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax

# Activation names the model zoo tags with ``checkpoint_name`` — the saved
# set under the ``names``/``offload`` policies. Attention outputs are the
# canonical choice (TorchTitan's SAC default): recomputing them in backward
# costs the full QK^T/softmax/AV chain, while saving them is one [B, T, D]
# residual per block.
# ``KERNEL_RESIDUALS`` are the names `ops/pallas_attn.py`'s forward rules
# give ``out`` and ``lse`` (its ``RESIDUALS_NAME``) and ``lse`` again as
# dense rows (its ``DENSE_LSE_NAME``; a test holds the spellings equal):
# the first kept by every policy that rematerialises, ``full`` included,
# the second beside it where the kept residuals are stacked over a scan's
# layers (kept, it is what the backward reads, and the padded ``lse`` is
# pruned).
KERNEL_RESIDUALS = "attn_kernel_residuals"
KERNEL_LSE_ROWS = "attn_kernel_lse_rows"
CHECKPOINT_SAVED_NAMES = ("attn_out", KERNEL_RESIDUALS)

REMAT_POLICIES = ("none", "full", "dots", "names", "offload")


def resolve_remat(remat: bool | str | None) -> str:
    """Canonicalize a remat spec (bool | str | None) to a policy name."""
    if remat is None or remat is False:
        return "none"
    if remat is True:
        return "full"
    name = str(remat).strip().lower()
    if name in ("", "0", "false", "off"):
        return "none"
    if name in ("1", "true", "on"):
        return "full"
    if name not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {remat!r}; valid: "
            + ", ".join(REMAT_POLICIES)
            + " (or a bool)"
        )
    return name


def kept_names(name: str, stacked: bool = False) -> tuple[str, ...]:
    """The ``checkpoint_name`` tags a canonical policy keeps (``dots`` keeps
    every dot's output besides). ``stacked``: the wrapped function is, or
    holds, a scan over layers, so what is kept is kept once a layer in one
    buffer: the kernels' row statistics are then kept as dense rows."""
    if name == "none":
        return ()
    names = (
        (KERNEL_RESIDUALS,) if name in ("full", "dots")
        else CHECKPOINT_SAVED_NAMES
    )
    return names + (KERNEL_LSE_ROWS,) if stacked else names


def checkpoint_policy(name: str, stacked: bool = False):
    """The ``jax.checkpoint`` ``policy=`` value for a canonical name
    (``stacked``: see :func:`kept_names`).

    Returns ``None`` for ``none`` alone (don't wrap at all — see
    :func:`apply_remat`).
    """
    cp = jax.checkpoint_policies
    if name == "none":
        return None
    names = kept_names(name, stacked)
    if name in ("full", "names"):
        return cp.save_only_these_names(*names)
    if name == "dots":
        return cp.save_from_both_policies(
            cp.checkpoint_dots, cp.save_only_these_names(*names)
        )
    if name == "offload":
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src="device",
            offload_dst="pinned_host",
        )
    raise ValueError(f"no jax.checkpoint policy for {name!r}")


def note_remat(name: str, where: str, stacked: bool = False) -> None:
    """One ``remat.path`` instant in the telemetry ring: a block or a step
    was wrapped under policy ``name``, and these are the names it keeps."""
    from ..observe import trace

    trace.instant(
        "remat.path", policy=name, keeps=list(kept_names(name, stacked)),
        where=where,
    )


def apply_remat(
    fn: Callable, remat: bool | str | None, **checkpoint_kwargs
) -> Callable:
    """Wrap ``fn`` in ``jax.checkpoint`` under the named policy.

    ``none`` returns ``fn`` unwrapped. Extra kwargs (``static_argnums``,
    ``prevent_cse``) forward to ``jax.checkpoint``. Each trace of the
    wrapped function leaves a ``remat.path`` instant.
    """
    name = resolve_remat(remat)
    if name == "none":
        return fn

    @functools.wraps(fn)
    def noted(*args, **kwargs):
        note_remat(name, "apply_remat")
        return fn(*args, **kwargs)

    return jax.checkpoint(
        noted, policy=checkpoint_policy(name), **checkpoint_kwargs
    )
