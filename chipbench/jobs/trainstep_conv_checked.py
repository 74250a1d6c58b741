"""``trainstep_attention_checked`` (``jobs/trainstep_attention_checked.py``,
unchanged: the same step, warm-up, counters, window, expert-layer and
attention checks) for a model whose short convolutions the reference is held
against too: ``check`` also holds the worst conv mixer's step-0 distance from
the reference's convolution ON THE MIXER'S OWN ``bcx`` AND TAPS
(``short_conv_rel``, which the family's reference pass reports beside the
other layers' distances: ``family.short_conv_distances``) to the cell's
``tolerance``. Taps reversed or read a step off move the whole model's loss
and gradient norm by less than their limits."""

from __future__ import annotations

from chipbench.jobs import trainstep_attention_checked

STEP_MODULES = trainstep_attention_checked.STEP_MODULES
plan = trainstep_attention_checked.plan
CONV_LIMITS = ("short_conv_rel",)


class Job(trainstep_attention_checked.Job):
    def check(self, setup: dict, window) -> list:
        problems = super().check(setup, window)
        tol, ref = self.env.cell.workload["tolerance"], setup["reference"]
        self.env.counters["compared"].update(
            {k: [ref[k], tol[k]] for k in CONV_LIMITS}
        )
        return problems
