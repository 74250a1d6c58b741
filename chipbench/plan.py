"""Compile each cell's programs at the cell's sizes for a TPU that is
described and not attached (a ``v5e:2x2``), here, before any chip call.

A plan, never a time: bytes per device, collective counts and compile
seconds, to be recorded in the cell's file and in PERF.md. Run by hand::

    JAX_PLATFORMS=cpu python3 -m chipbench.plan [<cell> ...]

Each job's file says how its programs are compiled (``plan(cell, family,
devices)``), so a new job brings its own.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench.trace_reduce import COLLECTIVE_FAMILIES

TOPOLOGY = "v5e:2x2"


def abstract_state(init_fn, tx, mesh, policy):
    """What ``create_train_state`` would place on ``mesh``, as shapes with
    shardings: a described device cannot hold an array (copied from
    ``tests/test_chip_compile.py``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pytorch_distributedtraining_tpu.parallel.spec import tree_shardings
    from pytorch_distributedtraining_tpu.parallel.state import TrainState

    def build(rng):
        params, model_state = init_fn(rng)
        return TrainState(
            step=jnp.int32(0), params=params, opt_state=tx.init(params),
            model_state=model_state, rng=rng, scaler=None,
        )

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    specs = TrainState(
        step=P(), params=policy.params_specs(shapes.params, mesh),
        opt_state=policy.opt_specs(shapes.opt_state, mesh),
        model_state=jax.tree.map(lambda _: P(), shapes.model_state),
        rng=P(), scaler=None,
    )
    shardings = tree_shardings(specs, mesh)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )
    return state, shardings


def compile_plan(jitted, *args) -> dict:
    """Compile ``jitted`` for the shapes ``args`` and read the plan."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        "compile_s": round(seconds, 1),
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "collectives": {
            name: n for name in COLLECTIVE_FAMILIES
            if (n := len(re.findall(rf"= \S+ {name}(?:-start)?\(", text)))
        },
    }


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chipbench import cells

    with open(os.path.join(cells.ROOT, cells.MANIFEST)) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    names = (argv if argv is not None else sys.argv[1:]) or names
    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    # an executable for a chip that is not there can never be read back
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    for name in names:
        cell = cells.load_cell(name)
        family = cells.load_module(
            "families", cell.config["family"], cell.roots
        )
        job = cells.load_module("jobs", cell.workload["job"], cell.roots)
        plan = job.plan(cell, family, list(topo.devices)[: cell.chips])
        print(json.dumps({"cell": name, "topology": TOPOLOGY, "plan": plan}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
