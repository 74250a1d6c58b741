"""The window's arithmetic: the rate is taken at the window's median pace,
so neither a held or slow stretch of steps nor late stamps move it; what the
median leaves out shows as the window's stall share instead."""

import itertools
import math
import random
import time

import pytest

from chipbench import instruments, loop


def window(gaps, *, late=(), step_units=100.0):
    """A window whose steps took ``gaps`` seconds; ``late[i]`` seconds are
    added to the stamp of step i alone (the host read it late)."""
    marks = [0.0, *itertools.accumulate(gaps)]
    for i, dt in enumerate(late):
        marks[i + 1] += dt
    n = len(gaps)
    return loop.Window(
        seconds=marks[-1], units=n * step_units, steps=n, batches=n,
        attempted=n, failed=0, losses=[1.0] * n, tenths=[0.0] * 10,
        step_units=step_units, marks=marks,
    )


def test_steady_window():
    steady = window([0.1] * 150)
    assert steady.rate == pytest.approx(1000.0)
    assert steady.stall_s == pytest.approx(0.0, abs=1e-9)
    # every step slower: the rate moves
    assert window([0.11] * 150).rate == pytest.approx(1000.0 / 1.1)


def test_a_held_or_slow_stretch_does_not_move_the_rate():
    # five holds of 0.15 s within 60 of 150 steps: 5% of the window lost
    held = [0.1] * 150
    for i in (50, 63, 77, 90, 104):
        held[i] += 0.15
    assert window(held).rate == pytest.approx(1000.0, rel=1e-6)
    assert window(held).stall_s == pytest.approx(0.75, rel=1e-3)
    # 40% of the steps a tenth slower
    slow = [0.1] * 45 + [0.11] * 60 + [0.1] * 45
    assert window(slow).rate == pytest.approx(1000.0, rel=1e-6)
    assert sum(slow) * 1000.0 / 15000 == pytest.approx(1.04)  # the wall rate


def test_late_stamps_do_not_move_the_rate():
    """The host reads losses up to 70 ms late while the device keeps its
    pace: single intervals swing between 30 and 170 ms."""
    rng = random.Random(7)
    late = [rng.choice((0.0, 0.0, 0.03, 0.07)) for _ in range(150)]
    got = window([0.1] * 150, late=late)
    assert min(got.gaps) < 0.04 and max(got.gaps) > 0.16
    assert got.rate == pytest.approx(1000.0, rel=2e-3)


def test_many_short_steps():
    """20,000 steps of a millisecond: every n-th mark of a part is taken."""
    rng = random.Random(3)
    late = [rng.choice((0.0, 0.0, 0.0002, 0.0005)) for _ in range(20000)]
    t0 = time.perf_counter()
    assert window([0.001] * 20000, late=late).step_s == pytest.approx(
        0.001, rel=2e-3
    )
    assert time.perf_counter() - t0 < 5


def test_short_windows():
    assert math.isnan(window([0.1]).step_s)
    assert window([0.1, 0.1]).step_s == pytest.approx(0.1)
    # 14 steps (GPT-2 XL in 20 s) are read in three parts: one may be held
    assert window([1.7] * 6 + [2.9] + [1.7] * 7).step_s == pytest.approx(1.7)


def test_run_steps_stamps_every_step():
    """Every dispatched step is read and stamped, the ones in flight at the
    deadline too; one slow step leaves the pace where it was."""
    t_step = 0.02

    def dispatch(i):
        time.sleep(t_step * (6 if i == 3 else 1))
        return 1.0 / (i + 1)

    got = loop.run_steps(
        dispatch, lambda: None, 0.6, 8.0,
        instruments.Tracer(False, "", 0.0), instruments.Spans(False),
    )
    assert got.attempted == got.steps == len(got.losses) == len(got.gaps)
    assert got.steps > 10 and got.failed == 0
    assert got.step_s == pytest.approx(t_step, rel=0.3)
    assert got.stall_s > 4 * t_step
    assert got.rate == pytest.approx(8.0 / got.step_s)
