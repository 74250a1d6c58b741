"""``trainstep`` for a model whose step reports counters and whose expert
layers the reference is held against: the same job (``jobs/trainstep.py``
builds, warms, checks and times it, unchanged), with two additions.

- Once set-up is done, the step is called through a wrapper that keeps the
  step's own metrics as they come, as device scalars and with no read
  inside the window. ``run`` hands them to the per-layer readers once the
  window is closed: each counter's value at every step of the window
  (``counters["routing_per_step"]``, also written to ``counters.json``
  beside ``losses.json``: routing moves during a window), its mean, and
  the family's ``kernel_costs`` (what the step's kernels had to do for the
  rows that really arrived).
- ``check`` also holds the expert layers' step-0 distances from the
  reference (``family.expert_layer_distances``, which the family's
  reference pass reports beside loss and gradient norm) to the cell's
  ``tolerance``, and refuses a window that dropped an assignment.

Which counters: ``models/glm4_moe_lite.routing_counters``."""

from __future__ import annotations

import json
import os

from chipbench.jobs import trainstep

STEP_MODULES = trainstep.STEP_MODULES
plan = trainstep.plan
COUNTERS = (
    "expert_rows_max", "expert_rows_mean", "expert_load_max_over_mean",
    "assignments_landed", "experts_active", "dropped_assignments",
)
LAYER_LIMITS = ("router_score_rms", "expert_layer_rel")


class Job(trainstep.Job):
    def setup(self) -> dict:
        done = super().setup()
        inner, self.kept = self.step, []

        def counted(state, batch):
            state, metrics = inner(state, batch)
            self.kept.append([metrics[k] for k in COUNTERS])
            return state, metrics

        self.step = counted  # from here on: the window's calls
        return done

    def run(self, seconds: float):
        import jax

        window = super().run(seconds)  # closes with a fence: all are ready
        rows = jax.device_get(self.kept)  # one read, afterwards
        per_step = {
            k: [float(r[i]) for r in rows] for i, k in enumerate(COUNTERS)
        }
        env = self.env
        env.counters["routing_per_step"] = per_step
        env.counters["kernel_costs"] = self.task.kernel_costs
        for k, values in per_step.items():
            env.counters[k] = sum(values) / max(1, len(values))
        env.counters["dropped_assignments"] = sum(
            per_step["dropped_assignments"]
        )
        with open(os.path.join(env.out_dir, "counters.json"), "w") as f:
            json.dump({"cell": env.cell.name, "seed": env.seed,
                       "per_step": per_step}, f)
        return window

    def check(self, setup: dict, window) -> list:
        problems = super().check(setup, window)
        tol, ref = self.env.cell.workload["tolerance"], setup["reference"]
        self.env.counters["compared"].update(
            {k: [ref[k], tol[k]] for k in LAYER_LIMITS},
            dropped_assignments=[
                self.env.counters["dropped_assignments"], 0
            ],
        )
        return problems
