"""What the limits of a ``follow_steps`` cell have to refuse, read through the
harness's own comparison at the cell's own size; no benchmark run calls this.

    python3 -m chipbench.control --workload <cell> --seeds <n>[,<n>...]

For every seed the plain reference takes the cell's first steps
(``first_steps.follow``), and so does each control, PUT IN THE PROGRAM'S
PLACE: the reference one precision below the cell's (``fp8 operands``: every
matmul's operands rounded to float8 e4m3, the step below a bf16 policy), the
reference as a step with a fault planted (``half the batch``: the mean taken
over the first half of the rows, of a single row over the first half of its
positions), and ``state unchanged`` (no run: the optimizer's moments and the
weights stay as made, so every norm of a change is 0). One JSON line each:
what ``first_steps.compare`` reads, each number beside the workload file's
limit, and ``correct`` as ``run.py`` decides it. A control that comes out
``correct`` is a limit that is too wide.

``--rehearse DIR`` reads a tiny cell of ``DIR`` on whatever backend jax has
(the CPU tests')."""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import cells, first_steps
from chipbench.run import over_limit, start_backend


def half(batch):
    """The part of a batch that a step sees which leaves half of it out."""
    rows = batch[0].shape[0]
    if rows > 1:
        return tuple(x[: rows // 2] for x in batch)
    return tuple(x[:, : x.shape[1] // 2] for x in batch)


def controls() -> dict:
    import jax.numpy as jnp

    return {
        "fp8 operands": {"lower": {"operands": jnp.float8_e4m3fn}},
        "half the batch": {"leave_out": half},
        "state unchanged": None,
    }


def unchanged(reference: dict) -> dict:
    """What a step that returns its state as it got it leaves behind: the
    right losses at the unmoved weights are granted, no moment, no move."""
    nothing = [0.0] * len(reference["grad_leaf"])
    return {**reference, "grad_leaf": nothing, "update_leaf": nothing}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    opt = parser.parse_args(argv)

    cell = cells.load_cell(opt.workload, opt.rehearse)
    start_backend(cell, opt.rehearse is not None)
    family = cells.load_module("families", cell.config["family"], cell.roots)
    job = cell.workload["job_params"]
    task, tol = family.task(cell.config, job), cell.workload["tolerance"]
    steps = cell.workload["follow_steps"]
    for seed in (int(s) for s in opt.seeds.split(",")):
        reference = first_steps.follow(task, job["optimizer"], seed, steps)
        for name, how in controls().items():
            put = unchanged(reference) if how is None else first_steps.follow(
                task, job["optimizer"], seed, steps, **how
            )
            read = first_steps.compare(put, reference)
            worst = read.pop("worst")
            compared = {k: [v, tol[k]] for k, v in read.items()}
            print(json.dumps({
                "cell": cell.name, "seed": seed, "control": name,
                "correct": not over_limit(compared),
                "compared": {
                    k: {"value": v, "limit": limit}
                    for k, (v, limit) in compared.items()
                },
                "worst": worst, "loss": put["loss"],
                "reference_loss": reference["loss"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
