"""What ``correct`` holds a training cell's first optimizer steps to, where
its workload file states ``follow_steps``: the compiled step that the window
will drive is driven through that many steps at set-up, by the window's own
call and feed, and once the window has closed, the peak has been read and the
program's state is freed, the plain reference (a family's float32 gradients,
``reference/adamw.py``'s update) takes the same steps from the same seed.
Compared, each under a limit of the workload file's ``tolerance``:

- ``loss_abs``: every step's loss, the widest gap;
- ``grad_norm_rel``: step 0's gradient norm before the clip;
- ``grad_leaf_rel``: the first gradient as the optimizer got it, leaf by
  leaf, worked out from the first moment after one step (``mu / (1 - b1)``);
- ``update_leaf_rel``: the parameters' change after the last step, leaf by
  leaf, against weights made anew from the seed.

The two by leaf read the worst leaf's gap between the program's norm and the
reference's (not the norm of their difference), over the reference's norm of
that leaf or of the median leaf, whichever is larger: some gradients are all
but zero. A leaf whose reference gradient is under a thousandth of the median
leaf's moves under Adam by round-off alone and is left out of the change. A
step that returns its state unchanged reads 1 in both."""

from __future__ import annotations

import itertools
import statistics

from chipbench.reference import adamw

NOUGHT = 1e-3  # of the median leaf's gradient: left out of the change


def first_moment(opt_state):
    """The first-moment tree inside an optax chain's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, tuple):
        for child in opt_state:
            found = first_moment(child)
            if found is not None:
                return found
    return None


def first_gradient_norms(opt_state, optimizer: dict) -> list:
    """Per leaf, the norm of the gradient the optimizer got at its first
    step, from its state after that step (device scalars)."""
    import jax

    b1 = optimizer.get("betas", adamw.BETAS)[0]
    norms = jax.jit(adamw.leaf_norms)(first_moment(opt_state))
    return [n / (1 - b1) for n in norms]


def change_norms(params, init_fn, seed: int) -> list:
    """Per leaf, the norm of ``params`` less the weights ``init_fn`` makes
    from ``seed`` (made anew: no copy lives through the steps)."""
    import jax

    start, _ = jax.jit(init_fn)(jax.random.PRNGKey(seed))
    return jax.jit(lambda a, b: adamw.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)
    ))(params, start)


def leaf_names(tree) -> list:
    import jax

    return [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def follow(task, optimizer: dict, seed: int, steps: int, lower=None,
           leave_out=None) -> dict:
    """The plain reference through the first ``steps`` optimizer steps of a
    cell, from ``seed`` alone: the family's weights and batches, its float32
    gradients (``task.reference_grads``), what else a step moves
    (``task.reference_state``, or None), ``reference/adamw.py``. ``lower``
    (keywords of the family's reference: narrower operands) and
    ``leave_out`` (batch -> the part of it a faulty step would see) make the
    controls that a cell's limits have to refuse, never the reference."""
    import jax
    import jax.numpy as jnp

    def one_step(params, m, v, model_state, step, rate_now, batch):
        loss, grads = task.reference_grads(
            params, model_state, batch, **(lower or {})
        )
        grads, norm = adamw.clipped(optimizer, grads)
        read = {"loss": loss, "grad_norm": norm,
                "grad_leaf": adamw.leaf_norms(grads)}
        return adamw.update(optimizer, step, rate_now, params, m, v, grads), read

    one_step = jax.jit(one_step, donate_argnums=(0, 1, 2))
    move_state = task.reference_state and jax.jit(task.reference_state)
    params, model_state = jax.jit(task.init_fn)(jax.random.PRNGKey(seed))
    names = leaf_names(params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    reads = []
    for k, batch in enumerate(itertools.islice(task.batches(seed), steps)):
        batch = jax.tree.map(jnp.asarray, batch)
        if leave_out is not None:
            batch = leave_out(batch)
        after = model_state
        if move_state and k < steps - 1:  # by this step's weights
            after = move_state(params, model_state, batch)
        (params, m, v), read = one_step(
            params, m, v, model_state, k, adamw.rate(optimizer, k), batch
        )
        model_state = after
        reads.append(read)
    del m, v
    moved = change_norms(params, task.init_fn, seed)
    reads, moved = jax.device_get((reads, moved))
    return {
        "loss": [float(r["loss"]) for r in reads],
        "grad_norm": float(reads[0]["grad_norm"]),
        "grad_leaf": [float(x) for x in reads[0]["grad_leaf"]],
        "update_leaf": [float(x) for x in moved],
        "leaves": names,
    }


def worst_leaf(program, reference, keep=None) -> tuple:
    """``(gap, index)`` of the leaf whose norm in ``program`` lies farthest
    from its norm in ``reference``, over the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    median = statistics.median(reference)
    gaps = [
        abs(a - b) / max(b, median) if keep is None or keep[i] else 0.0
        for i, (a, b) in enumerate(zip(program, reference))
    ]
    gap = max(gaps)
    return gap, gaps.index(gap)


def compare(program: dict, reference: dict) -> dict:
    """``{name: number}`` of what the module's docstring lists, ``program``
    and ``reference`` as ``follow`` returns them (of the program: the job's
    readings of its own first steps), and ``worst``: the leaves that set the
    two numbers by leaf."""
    floor = NOUGHT * statistics.median(reference["grad_leaf"])
    grad, g_at = worst_leaf(program["grad_leaf"], reference["grad_leaf"])
    moved, m_at = worst_leaf(
        program["update_leaf"], reference["update_leaf"],
        keep=[g >= floor for g in reference["grad_leaf"]],
    )
    names = reference["leaves"]
    return {
        "loss_abs": max(
            abs(a - b) for a, b in zip(program["loss"], reference["loss"])
        ),
        "grad_norm_rel": abs(
            program["grad_norm"] - reference["grad_norm"]
        ) / reference["grad_norm"],
        "grad_leaf_rel": grad,
        "update_leaf_rel": moved,
        "worst": {"grad_leaf_rel": names[g_at], "update_leaf_rel": names[m_at]},
    }
