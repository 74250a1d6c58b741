"""Diff two runs' op-cost tables: attribute a regression to op classes.

The regression sentry (observe/fleet.py, benchmarks/regress.py) says
*that* a headline metric regressed; this tool says *where the time
went* — which op class (compute / collective / copy / host-transfer)
and which collectives grew between a good run and a bad one:

    python benchmarks/trace_diff.py old_trace_dir new_trace_dir
    python benchmarks/trace_diff.py BENCH_LAST_GOOD.json fresh.json

Each argument is either a profiler trace directory (parsed with
``observe.opcost``) or a bench-record JSON file carrying an ``opcost``
block. regress.py calls :func:`attribute_records` at
verdict time, so a ``regression`` verdict in a bench record carries an
``attribution`` block naming the dominant class instead of just a
number that got worse.
"""

from __future__ import annotations

import argparse
import json
import os

import _bootstrap  # noqa: F401  (repo root on sys.path)

# NOTE: observe.opcost is imported lazily (inside _load) so that a
# jax-free parent can import this module for attribute_records —
# record-vs-record diffs are pure dict math.


def _norm(obj: dict) -> dict | None:
    """Normalize an op-cost carrier to ``{"per_class_s", "collectives"}``.

    Accepts an ``opcost.op_table`` result, a bench record (looks inside
    its ``opcost`` block), or an already-normalized block. None when the
    object carries no per-class table.
    """
    if not isinstance(obj, dict):
        return None
    if "opcost" in obj and isinstance(obj["opcost"], dict):
        return _norm(obj["opcost"])
    if "per_class_s" in obj:
        coll = obj.get("collectives") or {}
        if isinstance(coll, list):  # op_table row form
            coll = {r["op"]: r["s"] for r in coll}
        return {"per_class_s": dict(obj["per_class_s"]),
                "collectives": dict(coll)}
    if "classes" in obj:  # raw op_table
        return {
            "per_class_s": {
                cls: row["seconds"] for cls, row in obj["classes"].items()
            },
            "collectives": {
                r["op"]: r["s"] for r in obj.get("collectives", [])
            },
        }
    return None


def diff_tables(old: dict, new: dict) -> dict:
    """Per-class delta between two op-cost carriers.

    ``delta_s`` > 0 means the class got slower in ``new``;
    ``share_of_regression`` apportions the total slowdown across the
    classes that grew (None when the total didn't grow). The dominant
    class is the one owning the largest positive delta.
    """
    o, n = _norm(old), _norm(new)
    if o is None or n is None:
        raise ValueError("both sides need a per-class op-cost table")
    classes = sorted(set(o["per_class_s"]) | set(n["per_class_s"]))
    grew_total = sum(
        max(0.0, n["per_class_s"].get(c, 0.0) - o["per_class_s"].get(c, 0.0))
        for c in classes
    )
    by_class = {}
    for c in classes:
        ov = o["per_class_s"].get(c, 0.0)
        nv = n["per_class_s"].get(c, 0.0)
        delta = nv - ov
        by_class[c] = {
            "old_s": round(ov, 9),
            "new_s": round(nv, 9),
            "delta_s": round(delta, 9),
            "share_of_regression": (
                round(delta / grew_total, 4)
                if grew_total > 0 and delta > 0 else None
            ),
        }
    dominant = None
    if grew_total > 0:
        dominant = max(by_class, key=lambda c: by_class[c]["delta_s"])
    coll = {}
    for op in sorted(set(o["collectives"]) | set(n["collectives"])):
        ov = o["collectives"].get(op, 0.0)
        nv = n["collectives"].get(op, 0.0)
        if ov or nv:
            coll[op] = {
                "old_s": round(ov, 9),
                "new_s": round(nv, 9),
                "delta_s": round(nv - ov, 9),
            }
    out = {
        "total_old_s": round(sum(o["per_class_s"].values()), 9),
        "total_new_s": round(sum(n["per_class_s"].values()), 9),
        "dominant_class": dominant,
        "by_class": by_class,
        "collectives": coll,
    }
    if dominant is not None:
        row = by_class[dominant]
        out["detail"] = (
            f"op class '{dominant}' grew {row['delta_s'] * 1e3:.3f} ms "
            f"({row['old_s'] * 1e3:.3f} -> {row['new_s'] * 1e3:.3f} ms, "
            f"{row['share_of_regression']:.0%} of the slowdown)"
        )
    return out


def attribute_records(old_rec: dict, new_rec: dict) -> dict:
    """Attribution block for a regression verdict, from two bench
    records' ``opcost`` blocks. Never raises — a verdict must still
    publish when attribution has nothing to chew on; ``available``
    says which case this is."""
    try:
        d = diff_tables(old_rec, new_rec)
    except (ValueError, TypeError, KeyError) as e:
        return {
            "available": False,
            "reason": (
                "no per-class op tables on both sides "
                f"(need records with an opcost block): {e}"
            ),
        }
    d["available"] = True
    return d


def _load(spec: str) -> dict:
    if os.path.isdir(spec):
        from pytorch_distributedtraining_tpu.observe import opcost

        events, _ = opcost.load_trace_events(spec)
        return opcost.op_table(events)
    with open(spec, encoding="utf-8") as fh:
        return json.load(fh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline: trace dir or bench-record JSON")
    ap.add_argument("new", help="candidate: trace dir or bench-record JSON")
    opt = ap.parse_args(argv)
    try:
        diff = diff_tables(_load(opt.old), _load(opt.new))
    except (FileNotFoundError, ValueError) as e:
        raise SystemExit(str(e))
    print(json.dumps(diff))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
