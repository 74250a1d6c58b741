"""What ``correct`` holds the first optimizer steps of a ``follow_steps``
cell to (``chipbench/first_steps.py``, ``reference/adamw.py``,
``chipbench/control.py``), on the CPU at a toy's size: the plain AdamW
against the program's, the measure by the worst leaf, what ``run.py`` asks
of a window, every control refused, and a rehearsed run with the timed path
broken underneath coming out not correct."""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import control, first_steps, run
from chipbench.jobs import trainstep
from chipbench.reference import adamw

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {
    "tiny-glm.train": "rehearsal_glm",
    "tiny-smallthinker.train": "rehearsal_smallthinker",
}


@pytest.mark.parametrize("optimizer", [
    {"lr": 1e-3},
    {"lr": 1e-3, "clip_grad_norm": 1.0},
    {"lr": 3e-4, "warmup_steps": 4, "clip_grad_norm": 0.5},
    {"lr": 1e-2, "betas": [0.8, 0.95], "eps": 1e-6, "weight_decay": 0.1},
])
def test_the_plain_adamw_takes_the_programs_steps(optimizer):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    params = {"a": jax.random.normal(keys[0], (5, 3)),
              "b": {"c": jax.random.normal(keys[1], (4,))}}
    tx = trainstep.optimizer(dict(optimizer))
    theirs, state = params, tx.init(params)
    mine = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for step in range(3):
        grads = jax.tree.map(
            lambda p, k=keys[2 + step]: 3 * jax.random.normal(k, p.shape),
            params,
        )
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        got, _ = adamw.clipped(optimizer, grads)
        mine, m, v = adamw.update(
            optimizer, step, adamw.rate(optimizer, step), mine, m, v, got
        )
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-9)
    # the first gradient as the optimizer got it, from its first moment
    tx = trainstep.optimizer(dict(optimizer))
    _, state = tx.update(grads, tx.init(params), params)
    got, _ = adamw.clipped(optimizer, grads)
    np.testing.assert_allclose(
        first_steps.first_gradient_norms(state, optimizer),
        adamw.leaf_norms(got), rtol=1e-5,
    )


def test_the_rate_of_the_plain_adamw_is_the_programs_schedule():
    optimizer = {"lr": 3e-4, "warmup_steps": 2000}
    schedule = trainstep.rate(optimizer)
    for step in (0, 1, 2, 1000, 2000, 3000):
        assert adamw.rate(optimizer, step) == pytest.approx(
            float(schedule(step)), rel=1e-4  # optax counts in float32
        )
    assert adamw.rate({"lr": 1e-5}, 7) == 1e-5


def test_the_worst_leaf_is_measured_against_its_norm_or_the_median_leafs():
    reference = [4.0, 1.0, 1e-6, 2.0, 1.0]  # median 1.0
    assert first_steps.worst_leaf(reference, reference) == (0.0, 0)
    # a tiny leaf that doubles is measured against the median leaf
    gap, at = first_steps.worst_leaf([4.0, 1.0, 2e-6, 2.0, 1.0], reference)
    assert (gap, at) == (pytest.approx(1e-6), 2)
    gap, at = first_steps.worst_leaf([4.0, 1.0, 1e-6, 3.0, 1.0], reference)
    assert (gap, at) == (pytest.approx(0.5), 3)
    # a state left as it was reads 1; a leaf moved double reads 1 too
    assert first_steps.worst_leaf([0.0] * 5, reference)[0] == 1.0
    assert first_steps.worst_leaf([4.0, 2.0, 0, 2.0, 1.0], reference)[0] == 1.0
    gap, at = first_steps.worst_leaf(
        [0.0] * 5, reference, keep=[False, True, True, True, True]
    )
    assert (gap, at) == (1.0, 1)


def test_a_leaf_without_a_gradient_is_left_out_of_the_change_alone():
    reference = {
        "loss": [2.0, 1.9], "grad_norm": 1.0, "leaves": ["a", "b", "c"],
        "grad_leaf": [1.0, 1e-5, 1.0], "update_leaf": [1.0, 1.0, 1.0],
    }
    program = {**reference, "update_leaf": [1.0, 0.2, 0.9],
               "grad_leaf": [1.0, 3e-5, 1.0], "loss": [2.0, 1.85]}
    read = first_steps.compare(program, reference)
    assert read["update_leaf_rel"] == pytest.approx(0.1)
    assert read["grad_leaf_rel"] == pytest.approx(2e-5)
    assert read["loss_abs"] == pytest.approx(0.05)
    assert read["worst"] == {"grad_leaf_rel": "b", "update_leaf_rel": "c"}


FALLS = [10.3] * 5 + [10.2] * 40 + [10.1] * 5
FLAT_NOISY = [10.30, 10.31, 10.29, 10.30, 10.32] * 9 + [10.304] * 5


def window(losses, failed=0):
    return SimpleNamespace(
        losses=losses, failed=failed, attempted=len(losses), step_s=0.1,
        gaps=[0.1] * len(losses),
    )


RISES = [10.3] * 45 + [10.9] * 5


@pytest.mark.parametrize("losses,workload,problems", [
    (FALLS, {}, []),
    (RISES, {}, ["loss_last_tenth_less_first"]),
    (FLAT_NOISY, {}, ["the"]),  # where it was: not fallen
    (RISES, {"follow_steps": 3}, []),
    (FLAT_NOISY, {"follow_steps": 3}, []),
    (FALLS, {"follow_steps": 3}, []),
])
def test_the_loss_falls_where_no_reference_follows_the_steps(
    losses, workload, problems
):
    compared = {}
    found = run.window_problems(
        window(losses), {"compiles": 0}, workload, compared
    ) + run.over_limit(compared)
    assert [p.split()[0] for p in found] == problems
    assert ("loss_last_tenth_less_first" in compared) == (not workload)


def test_a_window_that_compiled_or_lost_a_step_is_over_its_limits():
    compared = {}
    run.window_problems(window(FALLS, failed=2), {"compiles": 1}, {}, compared)
    assert [p.split()[0] for p in run.over_limit(compared)] == [
        "compilations_in_window", "steps_failed"
    ]
    assert run.over_limit({"x": [float("nan"), 1.0]})  # no number, no pass
    assert run.window_problems(window([]), {"compiles": 0}, {}, {})


def test_the_tenths_of_a_window_of_fewer_than_ten_steps_are_single_steps():
    assert run.tenths([3.0, 1.0, 1.0]) == (3.0, 1.0)
    assert run.tenths(FALLS) == (10.3, 10.1)


@pytest.mark.parametrize("name", sorted(TINY))
def test_every_control_is_refused(name, capsys):
    """The reference one precision lower, half the batch left out, the state
    left unchanged: each put in the program's place and read through the
    harness's comparison, each has to fail a limit of the cell's."""
    assert control.main([
        "--workload", name, "--seeds", "2147484101,11",
        "--rehearse", os.path.join(HERE, TINY[name]),
    ]) == 0
    lines = [
        json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
    ]
    assert len(lines) == 2 * len(control.controls())
    for line in lines:
        assert line["correct"] is False, line
    for line in lines:
        if line["control"] == "state unchanged":
            read = line["compared"]
            assert read["update_leaf_rel"]["value"] == 1.0
            assert read["grad_leaf_rel"]["value"] == 1.0
            assert read["loss_abs"]["value"] == 0.0


def rehearse(name, capsys):
    code = run.main([
        "--workload", name, "--seed", "2147484101", "--seconds", "0.5",
        "--trace", "0", "--rehearse", os.path.join(HERE, TINY[name]),
    ])
    out, err = capsys.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


def over(line) -> set:
    return {
        name for name, entry in line["compared"].items()
        if not entry["value"] <= entry["limit"]
    }


def half_left_out(step):
    import jax.numpy as jnp

    def broken(state, batch):
        half = batch[0].shape[0] // 2
        return step(state, tuple(
            jnp.concatenate([x[:half], x[:half]]) for x in batch
        ))

    return broken


def state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        kept = jax.tree.map(jnp.copy, state)  # the step donates its own
        _, metrics = step(state, batch)
        return kept, metrics

    return broken


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("fault,seen_by", [
    (half_left_out, {"loss_abs", "grad_norm_rel", "grad_leaf_rel"}),
    (state_unchanged, {"update_leaf_rel", "grad_leaf_rel"}),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(
    name, fault, seen_by, monkeypatch, capsys
):
    """The rest of a run, driven past the harness's look for a chip, with
    the step that the window drives broken underneath: handed the first half
    of every batch twice, so that its mean is taken over half the rows; or
    returning its state as it got it. The reference, which takes the first
    steps from the seed, has to say so."""
    code, line, err = rehearse(name, capsys)
    assert code == 0 and line["correct"] is True, err[-2000:]
    assert not over(line)

    real = trainstep.make_step
    monkeypatch.setattr(
        trainstep, "make_step", lambda *a, **k: fault(real(*a, **k))
    )
    code, line, err = rehearse(name, capsys)
    assert code == 0 and line["correct"] is False
    assert over(line) & seen_by, line["compared"]
    assert over(line) <= seen_by | {"loss_abs"}, line["compared"]
    assert "is over its limit" in err
    if fault is state_unchanged:
        assert line["compared"]["update_leaf_rel"]["value"] == 1.0
        assert line["compared"]["grad_leaf_rel"]["value"] == 1.0
