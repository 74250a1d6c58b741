"""A job added as one file: ``trainstep`` that leaves a mark for the
rehearsal's own per-layer metric to read."""

from chipbench.jobs import trainstep

STEP_MODULES = trainstep.STEP_MODULES


class Job(trainstep.Job):
    def setup(self) -> dict:
        out = super().setup()
        self.env.counters["rehearsal_mark"] = 1.0
        return out
