"""The measured window of a job that dispatches one compiled step per call.

The loop is the user's loop: no ``block_until_ready`` per step, one fence at
each end. The host stays a bounded ``LOOKAHEAD`` steps ahead: before it
dispatches step i it reads the loss of step i-2 (which is how a training job
collects its loss curve), so the device never drains and the queue cannot
run minutes ahead of a long step. No step is dispatched after the deadline.

The rate is taken at the window's median pace (``pace``), not as steps
over wall seconds. On the chip machine the host's cores are shared, and two
things happen to a run that are not the code's doing (PERF.md, PR 22). A
stretch of steps runs slow or the host is held for longer than the lookahead
covers, and 3-4% of a window is lost: the wall rate carries every such
stretch. Or the host reads losses late while the device keeps its pace, and
the intervals between single steps swing between 100 and 172 ms around a
steady 129: their median then reads 15% fast. So the window is read in
ninths; a ninth's pace is the median, over all pairs of its steps, of the
seconds per step between the two (late stamps shrink with the distance), and
the window's pace is the median of the ninths (a held or slow stretch spoils
the ninths it falls in, not the others). What the median leaves out is kept
beside it: ``Window.seconds`` is the wall time, and the per-layer metric
``window_stall_pct`` the share of it above the median pace, so steps that
are slow now and then (a periodic save, say) still show.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import statistics
import time

LOOKAHEAD = 2


@dataclasses.dataclass
class Window:
    seconds: float  # opening fence to closing fence
    units: float  # tokens or images of the completed steps
    steps: int  # optimizer steps completed
    batches: int  # batches handed to the program (per-batch metrics)
    attempted: int
    failed: int
    losses: list
    tenths: list  # units per second in each tenth of the window
    step_units: float  # tokens or images of one optimizer step
    marks: list  # host clock at each optimizer step's boundary, in order

    @property
    def gaps(self) -> list:
        """Seconds between successive optimizer steps."""
        return [b - a for a, b in zip(self.marks, self.marks[1:])]

    @functools.cached_property
    def step_s(self) -> float:
        """The window's median pace, seconds an optimizer step."""
        return pace(self.marks)

    @property
    def stall_s(self) -> float:
        """Wall seconds of the window above its median pace."""
        return self.seconds - self.units / self.step_units * self.step_s

    @property
    def rate(self) -> float:
        """Units per second at the median pace."""
        return self.step_units / self.step_s


BLOCKS = 9  # the window is read in ninths
BLOCK_STEPS = 4  # or in fewer parts, so that each holds four steps
PAIR_MARKS = 64  # a part's pairs are taken among at most twice as many marks


def pair_pace(marks) -> float:
    """Median over all pairs of marks of the seconds per step between them
    (the Theil-Sen slope of time over step number). Of a part with hundreds
    of steps every n-th mark is taken, so that the pairs stay in the
    thousands."""
    stride = max(1, (len(marks) - 1) // PAIR_MARKS)
    thin = marks[::stride]
    return statistics.median(
        (thin[j] - thin[i]) / (j - i)
        for i in range(len(thin)) for j in range(i + 1, len(thin))
    ) / stride


def pace(marks) -> float:
    """Seconds an optimizer step from one mark per step boundary: the median
    over the window's parts of each part's ``pair_pace``; nan where there are
    fewer than two steps."""
    steps = len(marks) - 1
    if steps < 2:
        return math.nan
    blocks = max(1, min(BLOCKS, steps // BLOCK_STEPS))
    edges = [round(b * steps / blocks) for b in range(blocks + 1)]
    return statistics.median(
        pair_pace(marks[lo:hi + 1]) for lo, hi in zip(edges, edges[1:])
    )


def tenth_rates(stamps, t_open: float, t_close: float, units_each: float):
    """Units per second completed in each tenth of [t_open, t_close]."""
    width = (t_close - t_open) / 10
    counts = [0] * 10
    for t in stamps:
        counts[min(9, max(0, int((t - t_open) / width)))] += 1
    return [c * units_each / width for c in counts]


def run_steps(dispatch, fence, seconds, units_per_step, tracer, spans) -> Window:
    """``dispatch(i)`` enqueues step i and returns its loss as a device
    scalar; ``fence()`` waits for the last step's state."""
    pending = collections.deque()
    losses, stamps = [], []
    t_open = time.perf_counter()
    deadline = t_open + seconds
    tracer.arm(t_open, seconds)
    attempted = 0
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        tracer.poll(now)
        if len(pending) >= LOOKAHEAD:
            with spans.span("loss_read"):
                losses.append(float(pending.popleft()))
            stamps.append(time.perf_counter())
        with spans.span("dispatch"):
            pending.append(dispatch(attempted))
        attempted += 1
    while pending:  # the steps in flight, each stamped as it completes
        with spans.span("loss_read"):
            losses.append(float(pending.popleft()))
        stamps.append(time.perf_counter())
    with spans.span("fence"):
        fence()
    t_close = time.perf_counter()
    tracer.stop()
    failed = sum(not math.isfinite(x) for x in losses)
    done = attempted - failed
    return Window(
        seconds=t_close - t_open, units=done * units_per_step, steps=done,
        batches=attempted, attempted=attempted, failed=failed, losses=losses,
        tenths=tenth_rates(stamps, t_open, t_close, units_per_step),
        # the device is idle at t_open, so step 0 takes one interval too
        step_units=units_per_step, marks=[t_open] + stamps,
    )
