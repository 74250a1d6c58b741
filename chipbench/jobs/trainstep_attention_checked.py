"""``trainstep_counted`` (``jobs/trainstep_counted.py``, unchanged: the same
step, warm-up, counters, window and expert-layer check) for a model whose
attention cores the reference is held against too: ``check`` also holds the
worst core's step-0 distance from the reference's attention ON THE CORE'S OWN
INPUTS (``attention_rel``, which the family's reference pass reports beside
the expert layers' distances: ``family.attention_distances``) to the cell's
``tolerance``. The loss and the gradient norm of a whole model hardly feel
a band that is a key off."""

from __future__ import annotations

from chipbench.jobs import trainstep_counted

STEP_MODULES = trainstep_counted.STEP_MODULES
plan = trainstep_counted.plan
ATTENTION_LIMITS = ("attention_rel",)


class Job(trainstep_counted.Job):
    def check(self, setup: dict, window) -> list:
        problems = super().check(setup, window)
        tol, ref = self.env.cell.workload["tolerance"], setup["reference"]
        self.env.counters["compared"].update(
            {k: [ref[k], tol[k]] for k in ATTENTION_LIMITS}
        )
        return problems
