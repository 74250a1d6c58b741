"""Summarize Chrome trace-event JSON: profiler ops AND telemetry spans.

One tool for both trace producers in this repo — they share the
trace-event format, so they share the summarizer:

- jax.profiler xplane dumps (a directory a profiler capture wrote):
  aggregates `X` duration events per lane, preferring device
  lanes (TPU pids) over host lanes, so the MFU question — *which ops own
  the step time?* — is answerable without TensorBoard.
- observe/trace.py telemetry exports (``telemetry-<pid>.trace.json``,
  written by ``--trace`` / ``Stoke.export_trace`` / bench telemetry;
  their process_name lane starts with ``graft-telemetry``): rolls spans
  up by category — the stdout twin of the goodput ledger's
  time_breakdown — plus instant-event counts (fault injections,
  recompiles).
- serving lifecycle exports (``serve-<pid>.trace.json`` from
  ``observe/slo.py``; process_name starts with ``graft-serve``): rolls
  the per-slot lanes back up into one row per request — id, latency,
  per-phase breakdown in ms, slot, prefill buckets touched — the
  tabular twin of the Perfetto view the flow arrows draw.

    python benchmarks/trace_summary.py /tmp/tpu_results/xplane --top 25
    python benchmarks/trace_summary.py /tmp/graft-runs/<pid> --top 25

One JSON line per row plus a total line; also prints the share of the
summed lane time each row owns. Framework-internal python frames
(``$file.py:line`` names) and the block_until_ready scaffolding are
excluded from op summaries.
"""

from __future__ import annotations

import argparse
import collections
import json

import _bootstrap  # noqa: F401  (repo root on sys.path)

from pytorch_distributedtraining_tpu.observe import opcost as _opcost

_SCAFFOLD = (
    "block_until_ready", "try_to_block", "ThunkExecutor", "trace",
    "stop_trace", "__exit__",
)


def load_events(trace_dir: str):
    """All events from every trace file (multi-host dirs have one per
    host); a bare .json whose .gz sibling exists is skipped, not doubled.

    The parser itself was hoisted into the package
    (``observe.opcost.load_trace_events``) so in-process consumers — the
    on-demand capture's post-fire ingest — share it; this wrapper keeps the CLI's exit behavior."""
    try:
        return _opcost.load_trace_events(trace_dir)
    except FileNotFoundError as e:
        raise SystemExit(str(e))


def summarize(events, top: int):
    lanes, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            lanes[e["pid"]] = e.get("args", {}).get("name", str(e["pid"]))
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e.get("args", {}).get(
                "name", ""
            )

    device_pids = {
        pid for pid, name in lanes.items()
        if "host" not in (name or "").lower()
    }
    use_pids = device_pids or set(lanes)
    # TensorBoard-style device traces put several thread lanes under one
    # pid ("XLA Modules" = whole-step envelopes, "Steps", "XLA Ops" = the
    # individual ops). Counting the envelope lanes would double the total
    # and halve every op's share — keep only op lanes when they exist.
    # exact-lane match against the known TensorBoard op-lane names: a
    # suffix heuristic (rstrip('s').endswith('op')) would also count lanes
    # like "Stop"/"Loops" as op lanes on unusual trace layouts
    op_tids = {
        key for key, name in threads.items()
        if key[0] in use_pids
        and (name or "").strip().lower() in ("xla ops", "tensorflow ops")
    }

    def _lane_ok(e):
        if e.get("pid") not in use_pids:
            return False
        if op_tids:
            return (e.get("pid"), e.get("tid")) in op_tids
        name = threads.get((e.get("pid"), e.get("tid")), "")
        return not any(s in name for s in ("Module", "Step"))

    dur = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or not _lane_ok(e):
            continue
        name = e.get("name", "?")
        if name.startswith("$") or any(s in name for s in _SCAFFOLD):
            continue
        # group fusion families: "copy_bitcast_fusion.142" -> one row
        head, _, tail = name.rpartition(".")
        if head and tail.isdigit():
            name = head + ".*"
        dur[name] += e.get("dur", 0.0)  # microseconds

    total = sum(dur.values())
    rows = [
        {
            "op": name,
            "ms": round(v / 1e3, 3),
            "share": round(v / total, 4) if total else 0.0,
        }
        for name, v in dur.most_common(top)
    ]
    return lanes, rows, total


def telemetry_rollup(events, top: int):
    """Category + span rollup for graft-telemetry lanes.

    The per-category row is the stdout twin of the goodput ledger's
    ``time_breakdown`` (same cats, pre-bucketing); instants (fault
    injections, recompile markers) are counted by name — zero-duration
    events would vanish from a duration summary.
    """
    by_cat = collections.Counter()
    by_span = collections.Counter()
    instants = collections.Counter()
    for e in events:
        if e.get("ph") == "i":
            instants[e.get("name", "?")] += 1
        elif e.get("ph") == "X":
            by_cat[e.get("cat", "other")] += e.get("dur", 0.0)
            by_span[e.get("name", "?")] += e.get("dur", 0.0)
    total = sum(by_cat.values())
    rows = [
        {
            "cat": cat,
            "ms": round(v / 1e3, 3),
            "share": round(v / total, 4) if total else 0.0,
        }
        for cat, v in by_cat.most_common()
    ]
    rows += [
        {
            "span": name,
            "ms": round(v / 1e3, 3),
            "share": round(v / total, 4) if total else 0.0,
        }
        for name, v in by_span.most_common(top)
    ]
    rows += [
        {"instant": name, "count": n} for name, n in instants.most_common()
    ]
    return rows, total


def numerics_rollup(events):
    """Summary row for ``numerics.*`` instants (observe/numerics.py).

    The generic instant counter above already tallies them by name; this
    keeps the plane's payloads — which leaf drew blame, what kind of
    divergence tripped, where a rollback landed — which a count-by-name
    row flattens away. Returns None when the trace carries no numerics
    events at all, so clean runs print nothing extra.
    """
    by_name = collections.Counter()
    blamed = collections.Counter()
    kinds = collections.Counter()
    rollbacks = []
    for e in events:
        if e.get("ph") != "i":
            continue
        name = e.get("name", "")
        if not name.startswith("numerics."):
            continue
        by_name[name] += 1
        args = e.get("args", {})
        if name == "numerics.nonfinite" and args.get("leaf"):
            blamed[args["leaf"]] += 1
        elif name == "numerics.divergence" and args.get("kind"):
            kinds[args["kind"]] += 1
        elif name == "numerics.rollback":
            rollbacks.append({
                "tripped_step": args.get("tripped_step"),
                "restored_step": args.get("restored_step"),
            })
    if not by_name:
        return None
    row = {
        "numerics_instants": dict(by_name.most_common()),
        "nonfinite_blame": dict(blamed.most_common()),
        "divergence_kinds": dict(kinds.most_common()),
    }
    if rollbacks:
        row["rollbacks"] = rollbacks
    return row


def serve_rollup(events):
    """Per-request rows from graft-serve lanes (observe/slo.py export).

    Each lane interleaves many requests' phase intervals (slot lanes are
    shared, the flow arrows tie one request's chain together); this
    inverts the layout — group the X events by request id and report
    the same per-phase breakdown the bench record carries. Flow events
    (ph s/t/f) carry no duration and are skipped.
    """
    threads = {
        (e["pid"], e.get("tid")): e.get("args", {}).get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    per_req: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args", {})
        uid = args.get("uid") or args.get("rid")
        if uid is None:
            continue
        row = per_req.setdefault(str(uid), {
            "rid": args.get("rid"),
            "t0": e["ts"], "t1": e["ts"] + e.get("dur", 0.0),
            "phase_ms": collections.Counter(),
            "slot": None, "buckets": set(),
        })
        row["t0"] = min(row["t0"], e["ts"])
        row["t1"] = max(row["t1"], e["ts"] + e.get("dur", 0.0))
        row["phase_ms"][e.get("name", "?")] += e.get("dur", 0.0)
        lane = threads.get((e.get("pid"), e.get("tid")), "")
        if lane.startswith("slot"):
            row["slot"] = lane
        if "bucket" in args:
            row["buckets"].add(args["bucket"])
    rows = []
    for uid, row in per_req.items():
        rows.append({
            "request": uid,
            "rid": row["rid"],
            "latency_ms": round((row["t1"] - row["t0"]) / 1e3, 3),
            "phase_ms": {
                k: round(v / 1e3, 3)
                for k, v in row["phase_ms"].most_common()
            },
            "slot": row["slot"],
            "buckets": sorted(row["buckets"]),
        })
    rows.sort(key=lambda r: -r["latency_ms"])
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    opt = ap.parse_args(argv)
    events, n_files = load_events(opt.trace_dir)
    lanes = {
        e["pid"]: e.get("args", {}).get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    tel_pids = {
        pid for pid, name in lanes.items()
        if (name or "").startswith("graft-telemetry")
    }
    serve_pids = {
        pid for pid, name in lanes.items()
        if (name or "").startswith("graft-serve")
    }
    tel_events = [e for e in events if e.get("pid") in tel_pids]
    serve_events = [e for e in events if e.get("pid") in serve_pids]
    op_events = [
        e for e in events
        if e.get("pid") not in tel_pids and e.get("pid") not in serve_pids
    ]
    if serve_events:
        rows = serve_rollup(serve_events)
        print(json.dumps({
            "serve_lanes": sorted(lanes[p] for p in serve_pids),
            "n_requests": len(rows),
            "n_events": len(serve_events),
        }))
        for r in rows[:opt.top]:
            print(json.dumps(r))
    if tel_events:
        rows, total = telemetry_rollup(tel_events, opt.top)
        print(json.dumps({
            "telemetry_lanes": sorted(
                lanes[p] for p in tel_pids
            ),
            "total_span_ms": round(total / 1e3, 3),
            "n_events": len(tel_events),
        }))
        # merged fleet trace (observe/fleet.py merge_traces): several
        # telemetry lanes in one file — a per-host/per-rank row each, so
        # "which lane owns the time" is answerable before the combined
        # rollup flattens them
        if len(tel_pids) > 1:
            for pid in sorted(tel_pids, key=lambda p: lanes[p]):
                lane_events = [e for e in tel_events if e.get("pid") == pid]
                by_cat = collections.Counter()
                for e in lane_events:
                    if e.get("ph") == "X":
                        by_cat[e.get("cat", "other")] += e.get("dur", 0.0)
                lane_total = sum(by_cat.values())
                print(json.dumps({
                    "lane": lanes[pid],
                    "total_span_ms": round(lane_total / 1e3, 3),
                    "n_events": sum(
                        1 for e in lane_events if e.get("ph") in ("X", "i")
                    ),
                    "by_cat_ms": {
                        c: round(v / 1e3, 3)
                        for c, v in by_cat.most_common()
                    },
                }))
        for r in rows:
            print(json.dumps(r))
        num_row = numerics_rollup(tel_events)
        if num_row is not None:
            print(json.dumps(num_row))
    if not (tel_events or serve_events) or any(
        e.get("ph") == "X" for e in op_events
    ):
        lanes_op, rows, total = summarize(op_events, opt.top)
        print(json.dumps({
            "lanes": sorted(set(lanes_op.values())),
            "total_op_ms": round(total / 1e3, 3),
            "n_events": len(op_events),
            "n_trace_files": n_files,
        }))
        for r in rows:
            print(json.dumps(r))
        # op-cost rollup: the same events bucketed by cost class
        # (observe/opcost.py) — the stdout twin of the bench record's
        # opcost block, so "did the collectives grow?" is answerable
        # from a bare trace dir without running trace_diff
        table = _opcost.op_table(op_events, top=opt.top)
        if table["total_s"] > 0:
            print(json.dumps({
                "opcost_classes_ms": {
                    cls: round(row["seconds"] * 1e3, 3)
                    for cls, row in table["classes"].items()
                    if row["events"]
                },
                "collectives_ms": {
                    r["op"]: round(r["s"] * 1e3, 3)
                    for r in table["collectives"]
                },
            }))


if __name__ == "__main__":
    main()
