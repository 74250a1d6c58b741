"""``trainstep_counted`` (``jobs/trainstep_counted.py``, unchanged: the same
step, warm-up, routing counters, window and expert-layer check) for a model
with a learned sparse-attention indexer (``families/keye_vl2.py``):

- the step's indexer counters are kept like the routing counters, with no
  read inside the window: ``selected_keys`` (an exact int32 over the layers
  and queries) and ``indexer_kl``, each step's in ``counters.json`` beside
  the routing's, their window means in the counters;
- ``check`` also holds the attention layers' step-0 distances from the
  reference ON THEIR OWN INPUTS (``family.sparse_distances``, which the
  family's reference pass reports beside the expert layers'):
  ``sparse_attention_rel``, ``indexer_score_rel`` and ``indexer_kl_abs``
  under their limits, ``selection_agree`` over its floor (compared as
  ``selection_disagree``, ``1 - agree`` under ``1 - floor``: the harness's
  comparison is "at most"), and every step of the window to have selected
  exactly ``min(t + 1, topk)`` keys for every query ``t``
  (``selected_keys_missing``, 0): a truncated selection fails the run.

``run`` hands the readers of the two sparse rooflines the family's
``roofline`` (the counter ``sparse_roofline``)."""

from __future__ import annotations

import functools
import json
import os

from chipbench.jobs import trainstep_counted

STEP_MODULES = trainstep_counted.STEP_MODULES
plan = trainstep_counted.plan
INDEXER = ("selected_keys", "indexer_kl")
LIMITS = ("sparse_attention_rel", "indexer_score_rel", "indexer_kl_abs")


class Job(trainstep_counted.Job):
    def setup(self) -> dict:
        done = super().setup()
        inner, self.kept_indexer = self.step, []

        def counted(state, batch):
            state, metrics = inner(state, batch)
            self.kept_indexer.append([metrics[k] for k in INDEXER])
            return state, metrics

        self.step = counted  # the window's calls
        return done

    def run(self, seconds: float):
        import jax

        window = super().run(seconds)  # closes with a fence: all are ready
        rows = jax.device_get(self.kept_indexer)
        env = self.env
        per_step = {k: [float(r[i]) for r in rows] for i, k in enumerate(INDEXER)}
        env.counters["indexer_per_step"] = per_step
        for k, values in per_step.items():
            env.counters[k] = sum(values) / max(1, len(values))
        job, config = env.cell.workload["job_params"], env.cell.config
        env.counters["selected_keys_per_query"] = env.counters[
            "selected_keys"
        ] / (config["num_hidden_layers"] * job["batch"] * job["seq"])
        env.counters["sparse_roofline"] = functools.partial(
            env.family.roofline, config, job
        )
        path = os.path.join(env.out_dir, "counters.json")
        with open(path) as f:
            written = json.load(f)
        written["per_step"].update(per_step)
        with open(path, "w") as f:
            json.dump(written, f)
        return window

    def expected_keys(self) -> int:
        """Keys a step selects over all layers and queries: query t takes
        min(t + 1, topk)."""
        job, config = self.env.cell.workload["job_params"], self.env.cell.config
        t, topk = job["seq"], config["sa_config"]["topk"]
        head = min(t, topk)
        pairs = head * (head + 1) // 2 + (t - head) * topk
        return config["num_hidden_layers"] * job["batch"] * pairs

    def check(self, setup: dict, window) -> list:
        problems = super().check(setup, window)
        tol, ref = self.env.cell.workload["tolerance"], setup["reference"]
        want = self.expected_keys()
        steps = self.env.counters.get("indexer_per_step", {}).get(
            "selected_keys", []
        )
        self.env.counters["compared"].update(
            {k: [ref[k], tol[k]] for k in LIMITS},
            selection_disagree=[
                1.0 - ref["selection_agree"], 1.0 - tol["selection_agree"]
            ],
            selected_keys_missing=[
                max((abs(want - n) for n in steps), default=want), 0
            ],
        )
        return problems
