"""Scan-over-layers utilities: param layout converters + remat wrapping.

``nn.scan`` over a repeated block compiles ONE block body instead of N —
the cold-compile lever (ISSUE 3) — but it changes the param layout: the
loop path stores per-layer subtrees (``h_0/…``, ``h_1/…``), the scan path
stores ONE subtree with every leaf stacked on a new leading axis
(``h/…`` with shape ``[n_layer, ...]``). These helpers convert between the
two layouts so checkpoints (including torch imports through
``interop.load_torch_into_template``, whose key maps target the loop
layout) keep working on scanned models, and so loop↔scan numerical
equivalence is testable leaf-for-leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import flax.linen as nn


def stack_trees(trees, xp=jnp):
    """Stack a list of identical-structure pytrees leaf-wise (new axis 0).

    ``xp`` selects the array namespace (``jnp`` default; pass ``numpy``
    for host-side use — the checkpoint reshard path converts layouts on
    host arrays before any device placement happens).
    """
    if not trees:
        raise ValueError("stack_trees needs at least one tree")
    return jax.tree.map(lambda *xs: xp.stack(xs), *trees)


def unstack_tree(tree, n: int):
    """Inverse of :func:`stack_trees`: split leading axis into n pytrees."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def stack_layer_params(
    params: dict, prefix: str, n: int, dest: str, xp=jnp
) -> dict:
    """Loop layout -> scan layout: fold ``{prefix}{i}`` subtrees into one
    stacked ``dest`` subtree (leading axis ``n``). Non-layer keys pass
    through untouched; returns a new dict. ``xp`` as in
    :func:`stack_trees`.
    """
    out = dict(params)
    layers = []
    for i in range(n):
        key = f"{prefix}{i}"
        if key not in out:
            raise KeyError(
                f"stack_layer_params: missing {key!r} (have "
                f"{sorted(k for k in out if k.startswith(prefix))})"
            )
        layers.append(out.pop(key))
    out[dest] = stack_trees(layers, xp=xp)
    return out


def unstack_layer_params(params: dict, dest: str, prefix: str, n: int) -> dict:
    """Scan layout -> loop layout: split the stacked ``dest`` subtree back
    into ``{prefix}{i}`` subtrees. Returns a new dict."""
    out = dict(params)
    if dest not in out:
        raise KeyError(f"unstack_layer_params: missing {dest!r}")
    stacked = out.pop(dest)
    for i, tree in enumerate(unstack_tree(stacked, n)):
        out[f"{prefix}{i}"] = tree
    return out


def remat_block(block_cls, remat, *, static_argnums=(2,), in_scan=False):
    """Wrap a block class in ``nn.remat`` under a named policy.

    ``remat`` is a bool or a policy name resolved through
    ``parallel.remat`` ("none" returns the class unwrapped). Inside a scan,
    ``prevent_cse=False`` is the standard form (the scan boundary already
    blocks the unsound CSE remat guards against). Called while the model
    is traced, and leaves a ``remat.path`` instant saying what is kept.
    """
    from ..parallel.remat import checkpoint_policy, note_remat, resolve_remat

    name = resolve_remat(remat)
    if name == "none":
        return block_cls
    note_remat(name, block_cls.__name__, stacked=in_scan)
    kwargs = {"static_argnums": static_argnums}
    if in_scan:
        kwargs["prevent_cse"] = False
    return nn.remat(
        block_cls, policy=checkpoint_policy(name, stacked=in_scan), **kwargs
    )
