"""Finds everything by name: a cell's files from ``BENCHMARK.json``, and the
code of a family, a job or a per-layer metric from its file name.

There is no registry to edit. A later PR adds ``workloads/<cell>.json``,
``configs/<config>.json``, ``families/<family>.py``, ``jobs/<job>.py`` or
``layer_metrics/<metric>.py`` and an entry in ``BENCHMARK.json``, and
changes no file that is here. A rehearsal directory (``--rehearse DIR``) is
searched first and has the same layout, with a manifest of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    workload: dict  # workloads/<cell>.json
    end_to_end: tuple  # metric entries of the manifest that this cell has
    per_layer: tuple
    roots: tuple  # where its files are looked for, in order


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find(kind: str, filename: str, roots) -> str:
    for root in roots:
        path = os.path.join(root, kind, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{filename} under {', '.join(roots)}"
    )


def load_json(kind: str, name: str, roots) -> dict:
    with open(find(kind, name + ".json", roots)) as f:
        return json.load(f)


def load_module(kind: str, name: str, roots):
    """``<root>/<kind>/<name>.py`` as a module of its own."""
    path = find(kind, name + ".py", roots)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}", path
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def reader_name(metric_name: str) -> str:
    """``device_idle_pct.images`` is read by ``layer_metrics/
    device_idle_pct.py``: one reader serves the variants that differ only
    in the end-to-end metric they move."""
    return metric_name.split(".", 1)[0]


def load_cell(name: str, rehearsal_dir: str | None = None) -> Cell:
    roots = (HERE,) if rehearsal_dir is None else (
        os.path.abspath(rehearsal_dir), HERE
    )
    manifest_dir = ROOT if rehearsal_dir is None else roots[0]
    with open(os.path.join(manifest_dir, MANIFEST)) as f:
        manifest = json.load(f)
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == name), None
    )
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"no cell {name!r} in {MANIFEST}; there are: {known}")
    workload = load_json("workloads", name, roots)
    config = load_json("configs", entry["config"], roots)
    for key, want in (("config", entry["config"]), ("chips", entry["chips"])):
        if workload.get(key) != want:
            raise SystemExit(
                f"workloads/{name}.json says {key}={workload.get(key)!r}, "
                f"{MANIFEST} says {want!r}"
            )
    return Cell(
        name=name, chips=entry["chips"], config=config, workload=workload,
        end_to_end=tuple(
            m for m in manifest["end_to_end"] if _applies(m, name)
        ),
        per_layer=tuple(
            m for m in manifest["per_layer"] if _applies(m, name)
        ),
        roots=roots,
    )
