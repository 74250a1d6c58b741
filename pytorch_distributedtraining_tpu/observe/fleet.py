"""Fleet observability plane: one timeline, one scrape, one sentry.

PR 7's telemetry and the elastic membership layer left every artifact
per-process: per-rank ``StepLog`` JSONLs, per-pid Chrome traces, per-pid
flight records. On a multi-host elastic pod there was no single fleet
timeline, no live view during a run, and no automated check that a fresh
bench record hasn't regressed against the ``BENCH_*.json`` trajectory.
This module is the controller-side aggregation plane over the existing
substrates (TorchTitan's position, PAPERS.md: production pre-training is
inseparable from fleet-wide monitoring):

- **cross-host trace merge** — :func:`estimate_offset` is an NTP-style
  midpoint estimator over a request/response ping (the membership
  store's ``clock_probe`` RPC rides the same line-JSON TCP protocol as
  every other membership call); :func:`merge_traces` re-bases each
  rank's exported trace onto one reference clock via those offsets and
  emits a single Chrome trace with per-host/per-rank process lanes.
  :func:`lane_ledgers` + :func:`merge_ledgers` build the fleet
  :class:`~.goodput.GoodputLedger` union from the merged trace, and
  :func:`per_host_mfu` is the per-host MFU table.
- **live metrics export** — :class:`StreamHist` is a mergeable
  fixed-bucket log-spaced streaming histogram (identical bounds on
  every rank, so merging is a count sum); ranks publish theirs through
  the membership store (``publish_metrics``), and :class:`FleetMonitor`
  on the controller folds them with the shared step logs into
  Prometheus text exposition served by :class:`MetricsExporter`
  (stdlib ``http.server``). The monitor continuously re-runs
  :func:`~.goodput.flag_stragglers`, emits ``fleet.straggler`` instants,
  and feeds the quarantine health signal (``record_probe(healthy=False)``
  resets the flagged host's healthy streak).
- **perf-regression sentry** — :func:`regression_verdict` compares a
  fresh bench record against the ``BENCH_r*.json`` /
  ``BENCH_LAST_GOOD.json`` trajectory with robust median/MAD thresholds
  per metric family: WARN on drift, ERROR on regression, and outage /
  fallback / zero-value records are *excluded* from the trajectory and
  never count as regressions themselves. ``benchmarks/regress.py`` is
  the CLI; graftcheck's ``bench-regression`` runtime rule reads
  :data:`runtime_stats`.

Stdlib-only by contract, like ``observe/trace.py`` and ``runtime/
membership.py``: the launcher's controller loop drives this module, and
nothing in it may touch jax.
"""

from __future__ import annotations

import bisect
import http.server
import json
import math
import os
import re
import sys
import threading
import time
from dataclasses import dataclass

from . import goodput as _goodput
from . import trace as _trace

__all__ = [
    "StreamHist",
    "ClockOffset",
    "estimate_offset",
    "estimate_store_offset",
    "merge_traces",
    "lane_ledgers",
    "merge_ledgers",
    "per_host_mfu",
    "prometheus_text",
    "MetricsExporter",
    "RankMetricsPublisher",
    "FleetMonitor",
    "genuine_measurement",
    "load_trajectory",
    "metric_direction",
    "regression_verdict",
    "fleet_summary_from_records",
    "runtime_stats",
]

# graftcheck's runtime plane (analyze/runtime_rules.py bench-regression
# rule) reads this via sys.modules — populated by regression_verdict()
# and the straggler monitor, never by imports.
runtime_stats: dict = {
    "verdicts": [],            # regression_verdict() results, newest last
    "stragglers_flagged": 0,   # cumulative fleet.straggler instants
    "scrapes": 0,              # /metrics GETs served
}


def reset_runtime_stats() -> None:
    runtime_stats.update(verdicts=[], stragglers_flagged=0, scrapes=0)


# -- mergeable streaming histograms -------------------------------------


class StreamHist:
    """Fixed-bucket log-spaced streaming histogram.

    The bucket bounds are a pure function of ``(lo_exp, hi_exp,
    per_decade)``, so every rank builds the *same* bounds independently
    and two histograms merge by summing counts — no rebinning, no
    coordination. Defaults cover 100µs..100s at 4 buckets/decade, the
    span of step times and serve latencies this stack measures; an
    under/overflow cell on each end keeps the count total exact.
    """

    __slots__ = ("bounds", "counts", "count", "sum", "min", "max")

    def __init__(
        self,
        lo_exp: float = -4.0,
        hi_exp: float = 2.0,
        per_decade: int = 4,
        bounds=None,
    ):
        if bounds is not None:
            self.bounds = tuple(float(b) for b in bounds)
        else:
            n = int(round((hi_exp - lo_exp) * per_decade))
            self.bounds = tuple(
                10.0 ** (lo_exp + i / per_decade) for i in range(n + 1)
            )
        if not self.bounds or any(
            b2 <= b1 for b1, b2 in zip(self.bounds, self.bounds[1:])
        ):
            raise ValueError("histogram bounds must be strictly increasing")
        # counts[i] holds bounds[i-1] < x <= bounds[i]; the last cell is
        # the overflow (x > bounds[-1]) so rendering with a +Inf bucket
        # (Prometheus cumulative form) loses nothing
        self.counts = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None

    def observe(self, x: float) -> None:
        x = float(x)
        self.counts[bisect.bisect_left(self.bounds, x)] += 1
        self.count += 1
        self.sum += x
        self.min = x if self.min is None else min(self.min, x)
        self.max = x if self.max is None else max(self.max, x)

    def merge(self, other: "StreamHist") -> "StreamHist":
        if tuple(other.bounds) != tuple(self.bounds):
            raise ValueError(
                "cannot merge histograms with different bucket bounds"
            )
        for i, c in enumerate(other.counts):
            self.counts[i] += int(c)
        self.count += other.count
        self.sum += other.sum
        for theirs in (other.min, other.max):
            if theirs is None:
                continue
            self.min = theirs if self.min is None else min(self.min, theirs)
            self.max = theirs if self.max is None else max(self.max, theirs)
        return self

    def quantile(self, q: float) -> float | None:
        """Upper bucket bound holding the q-quantile (conservative)."""
        if self.count <= 0:
            return None
        target = max(1, math.ceil(min(max(q, 0.0), 1.0) * self.count))
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                if i < len(self.bounds):
                    return self.bounds[i]
                return self.max  # overflow cell: best bound we have
        return self.max

    def to_dict(self) -> dict:
        return {
            "bounds": list(self.bounds),
            "counts": list(self.counts),
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
        }

    @classmethod
    def from_dict(cls, doc: dict) -> "StreamHist":
        h = cls(bounds=doc["bounds"])
        counts = [int(c) for c in doc.get("counts", [])]
        if len(counts) != len(h.counts):
            raise ValueError("histogram counts do not match bounds")
        h.counts = counts
        h.count = int(doc.get("count", sum(counts)))
        h.sum = float(doc.get("sum", 0.0))
        h.min = doc.get("min")
        h.max = doc.get("max")
        return h

    def prometheus_lines(self, name: str, labels: dict | None = None) -> list:
        """Prometheus text exposition: cumulative ``le`` buckets + sum/count."""
        base = ",".join(
            f'{k}="{v}"' for k, v in sorted((labels or {}).items())
        )
        sep = "," if base else ""
        lines = [f"# TYPE {name} histogram"]
        cum = 0
        for i, b in enumerate(self.bounds):
            cum += self.counts[i]
            lines.append(
                f'{name}_bucket{{{base}{sep}le="{format(b, ".6g")}"}} {cum}'
            )
        lines.append(f'{name}_bucket{{{base}{sep}le="+Inf"}} {self.count}')
        suffix = f"{{{base}}}" if base else ""
        lines.append(f"{name}_sum{suffix} {format(self.sum, '.9g')}")
        lines.append(f"{name}_count{suffix} {self.count}")
        return lines


# -- pairwise clock-offset estimation -----------------------------------


@dataclass(frozen=True)
class ClockOffset:
    """Remote-minus-local clock offset with its uncertainty bound.

    Midpoint method: one ping records local send ``t0``, the remote
    timestamp ``tr``, and local receive ``t1``; assuming the network
    delay splits evenly, ``offset = tr - (t0 + t1)/2`` and the true
    offset lies within ``±rtt/2`` of it *unconditionally* (the error is
    bounded by the delay asymmetry, which cannot exceed the RTT half).
    """

    offset_s: float
    uncertainty_s: float
    rtt_s: float
    pings: int

    def __float__(self) -> float:
        return self.offset_s


def estimate_offset(probe, pings: int = 8, clock=time.time) -> ClockOffset:
    """Estimate a remote clock's offset via repeated midpoint pings.

    ``probe()`` must return the remote clock's "now" (seconds); ``clock``
    is the local clock (injectable for tests). The minimum-RTT sample
    wins, NTP-style: queueing delay only ever *adds* to the RTT, so the
    fastest exchange carries the tightest ±rtt/2 bound.
    """
    best: tuple | None = None
    for _ in range(max(1, int(pings))):
        t0 = clock()
        tr = float(probe())
        t1 = clock()
        rtt = max(0.0, t1 - t0)
        off = tr - 0.5 * (t0 + t1)
        if best is None or rtt < best[0]:
            best = (rtt, off)
    rtt, off = best
    return ClockOffset(
        offset_s=off, uncertainty_s=0.5 * rtt, rtt_s=rtt,
        pings=max(1, int(pings)),
    )


def estimate_store_offset(store, pings: int = 8, clock=time.time) -> ClockOffset:
    """Offset of the membership store's clock (the controller's, when the
    store is a ``TCPMembershipStore`` proxy) vs this process's ``clock``.
    """
    return estimate_offset(
        lambda: store.clock_probe()["t"], pings=pings, clock=clock
    )


# -- cross-host trace merge ---------------------------------------------

_RANK_IN_NAME = re.compile(r"rank\s+(\d+)")


def _lane_meta(doc: dict) -> dict:
    """host/rank/wall anchor of one exported trace; ``graftMeta`` is the
    PR-12 export stamp, the process_name args are the fallback."""
    meta = doc.get("graftMeta") or {}
    host = str(meta.get("host") or "")
    rank = meta.get("rank")
    pid = meta.get("pid")
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            args = e.get("args") or {}
            host = host or str(args.get("host") or "")
            if rank is None:
                rank = args.get("rank")
            if rank is None:
                m = _RANK_IN_NAME.search(str(args.get("name", "")))
                if m:
                    rank = int(m.group(1))
            if pid is None:
                pid = e.get("pid")
            break
    return {
        "host": host or "host?",
        "rank": int(rank or 0),
        "pid": pid,
        "wall_t0": meta.get("wall_t0"),
    }


def merge_traces(inputs, offsets=None, out_path: str | None = None) -> dict:
    """Merge per-rank Chrome traces into one clock-aligned fleet trace.

    ``inputs`` — trace file paths and/or already-loaded trace dicts.
    ``offsets`` — ``{host: ClockOffset | float}``: that host's clock
    minus the reference (controller) clock; each lane's wall anchor is
    re-based by subtracting it. Lanes are assigned fresh pids in
    ``(host, rank)`` order with ``process_sort_index`` metadata, so
    merged lanes can never collide the way raw per-pid exports did.

    A lane exported before PR 12 has no ``graftMeta.wall_t0`` anchor; it
    still merges (own zero) and ``graftFleet.aligned`` reports False.
    """
    offsets = offsets or {}
    lanes = []
    for item in inputs:
        if isinstance(item, str):
            with open(item, encoding="utf-8") as fh:
                doc = json.load(fh)
        else:
            doc = item
        meta = _lane_meta(doc)
        off = offsets.get(meta["host"], 0.0)
        lanes.append({
            **meta,
            "offset_s": float(getattr(off, "offset_s", off)),
            "uncertainty_s": float(getattr(off, "uncertainty_s", 0.0)),
            "events": list(doc.get("traceEvents", [])),
        })
    lanes.sort(key=lambda l: (l["host"], l["rank"]))
    anchors = [
        l["wall_t0"] - l["offset_s"] for l in lanes
        if l["wall_t0"] is not None
    ]
    aligned = bool(anchors) and len(anchors) == len(lanes)
    t_zero = min(anchors) if anchors else 0.0
    merged: list = []
    lane_docs: list = []
    for i, lane in enumerate(lanes):
        pid = i + 1
        shift_us = 0.0
        if lane["wall_t0"] is not None:
            shift_us = ((lane["wall_t0"] - lane["offset_s"]) - t_zero) * 1e6
        merged.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {
                "name": (
                    f"graft-telemetry host={lane['host']} rank={lane['rank']}"
                ),
                "host": lane["host"], "rank": lane["rank"],
            },
        })
        merged.append({
            "ph": "M", "name": "process_sort_index", "pid": pid, "tid": 0,
            "args": {"sort_index": i},
        })
        n_events = 0
        for e in lane["events"]:
            if e.get("ph") == "M":
                if e.get("name") in ("process_name", "process_sort_index"):
                    continue  # replaced by the fleet lane metadata above
                e2 = dict(e)
                e2["pid"] = pid
                merged.append(e2)
                continue
            e2 = dict(e)
            e2["pid"] = pid
            if "ts" in e2:
                e2["ts"] = round(float(e2["ts"]) + shift_us, 3)
            merged.append(e2)
            n_events += 1
        lane_docs.append({
            "host": lane["host"], "rank": lane["rank"], "pid": pid,
            "source_pid": lane["pid"], "offset_s": lane["offset_s"],
            "uncertainty_s": lane["uncertainty_s"], "events": n_events,
        })
    doc = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "graftFleet": {"aligned": aligned, "lanes": lane_docs},
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = f"{out_path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        os.replace(tmp, out_path)
    return doc


def lane_ledgers(doc: dict) -> dict:
    """Per-lane :class:`~.goodput.GoodputLedger` from a (merged or single)
    Chrome trace dict — X events carry their span ``depth`` since PR 12,
    so the ledger's top-level-only billing survives the export."""
    names: dict = {}
    by_pid: dict = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e.get("pid")] = (e.get("args") or {}).get(
                "name", str(e.get("pid"))
            )
            continue
        if e.get("ph") not in ("X", "i"):
            continue
        rec = {
            "name": e.get("name", "?"),
            "cat": e.get("cat", "other"),
            "t0": float(e.get("ts", 0.0)) / 1e6,
            "dur": float(e.get("dur", 0.0)) / 1e6,
            "tid": e.get("tid", 0),
            "depth": int(e.get("depth", 0)),
            "attrs": {},
        }
        if e.get("ph") == "i":
            rec["instant"] = True
        by_pid.setdefault(e.get("pid"), []).append(rec)
    out = {}
    for pid, recs in sorted(by_pid.items(), key=lambda kv: str(kv[0])):
        label = names.get(pid, str(pid))
        t0 = min(r["t0"] for r in recs)
        t1 = max(r["t0"] + r["dur"] for r in recs)
        out[label] = _goodput.GoodputLedger.from_records(recs, t0, t1)
    return out


def merge_ledgers(ledgers: dict) -> dict:
    """Fleet union of per-lane ledgers: bucket seconds are summed across
    lanes (fleet-seconds), ``wall_s`` is the longest lane (the lanes ran
    concurrently), and the fleet goodput fraction is productive
    fleet-seconds over total fleet-seconds."""
    buckets = {b: 0.0 for b in _goodput.BUCKETS}
    fleet_seconds = 0.0
    wall = 0.0
    events = 0
    for led in ledgers.values():
        for b in _goodput.BUCKETS:
            buckets[b] += float(led.buckets.get(b, 0.0))
        fleet_seconds += float(led.wall_s)
        wall = max(wall, float(led.wall_s))
        events += int(led.events)
    return {
        "lanes": len(ledgers),
        "wall_s": round(wall, 6),
        "fleet_seconds": round(fleet_seconds, 6),
        "buckets": {b: round(v, 6) for b, v in buckets.items()},
        "goodput_fraction": (
            round(buckets["productive"] / fleet_seconds, 6)
            if fleet_seconds > 0 else None
        ),
        "events": events,
    }


def per_host_mfu(
    times_by_rank: dict,
    rank_hosts: dict | None = None,
    model_flops_per_step: float = 0.0,
    platform: str = "",
    device_kind: str = "",
) -> dict:
    """Per-host MFU table from per-rank step times.

    ``rank_hosts`` maps rank -> host id (e.g. from the membership
    store's ``live_ranks`` docs); unmapped ranks pool under ``host?``.
    MFU uses each host's median rank-median step time against one
    device's peak — the per-host number answers "is THIS host's silicon
    underperforming", which is what straggler triage needs.
    """
    rank_hosts = rank_hosts or {}
    per_host: dict = {}
    for r, ts in times_by_rank.items():
        if not ts:
            continue
        med = sorted(ts)[len(ts) // 2]
        host = str(
            rank_hosts.get(r) or rank_hosts.get(str(r)) or "host?"
        )
        per_host.setdefault(host, []).append((r, med))
    out = {}
    for host, pairs in sorted(per_host.items()):
        meds = sorted(m for _, m in pairs)
        med = meds[len(meds) // 2]
        row = {
            "ranks": sorted(int(r) for r, _ in pairs),
            "median_step_s": round(med, 6),
        }
        if model_flops_per_step > 0:
            row["mfu"] = _goodput.mfu(
                model_flops_per_step, med,
                n_devices=1, platform=platform, device_kind=device_kind,
            )
        out[host] = row
    return out


# -- Prometheus text exposition + HTTP endpoint -------------------------


def prometheus_text(hists: dict | None = None, gauges: dict | None = None) -> str:
    """Render histograms + gauges as Prometheus text exposition (0.0.4).

    Gauge keys may carry a label set inline (``name{rank="3"}``); the
    ``# TYPE`` header is emitted once per bare metric name.
    """
    lines: list = []
    for name in sorted(hists or {}):
        lines.extend(hists[name].prometheus_lines(name))
    typed: set = set()
    for name in sorted(gauges or {}):
        bare = name.split("{", 1)[0]
        if bare not in typed:
            typed.add(bare)
            lines.append(f"# TYPE {bare} gauge")
        lines.append(f"{name} {format(float(gauges[name]), '.9g')}")
    return "\n".join(lines) + "\n"


class MetricsExporter:
    """Stdlib HTTP endpoint serving ``collect()`` at ``/metrics``.

    ``collect`` is called per scrape and must return the Prometheus text
    body; a collect failure answers 500 instead of killing the serving
    thread. Daemon-threaded, so a dying launcher never hangs on it.
    """

    def __init__(self, collect, host: str = "127.0.0.1", port: int = 0):
        exporter = self
        self._collect = collect

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
                if self.path.split("?", 1)[0] not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                try:
                    body = exporter._collect().encode()
                except Exception as e:  # noqa: BLE001 — serve the error
                    self.send_error(500, explain=f"{type(e).__name__}: {e}")
                    return
                runtime_stats["scrapes"] += 1
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam stderr
                pass

        self._server = http.server.ThreadingHTTPServer((host, port), _Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="fleet-metrics",
            daemon=True,
        )
        self._thread.start()

    @property
    def address(self) -> tuple:
        return self._server.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}/metrics"

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


# -- rank-side publication ----------------------------------------------


def _serve_rolling_hists() -> dict:
    """The serving engine's rolling TTFT/latency/per-phase histograms via
    sys.modules — never imported (the engine pulls jax; this module must
    stay stdlib-importable)."""
    eng = sys.modules.get("pytorch_distributedtraining_tpu.serve.engine")
    rolling = getattr(eng, "rolling_hists", None) or {}
    return {
        name: h for name, h in rolling.items()
        if isinstance(h, StreamHist)
    }


def _serve_rolling_gauges() -> dict:
    """The serving engine's per-tick health gauges (queue depth, slot
    occupancy, free KV pages, SLO burn rate) plus the SLO tracker's
    budget counters — same sys.modules contract as the histograms."""
    out: dict = {}
    eng = sys.modules.get("pytorch_distributedtraining_tpu.serve.engine")
    for name, v in (getattr(eng, "rolling_gauges", None) or {}).items():
        if isinstance(v, (int, float)):
            out[str(name)] = float(v)
    return out


def _opcost_rolling_gauges() -> dict:
    """The op-cost plane's per-axis collective bandwidth + calibration
    ratios (observe/opcost.py) — sys.modules, never imported, so a rank
    that never ingested a profiler capture publishes nothing. Gauge
    names arrive pre-labelled per axis (``collective_bw_bytes_per_s_dp``
    etc.); the monitor adds the rank label like every other gauge."""
    out: dict = {}
    oc = sys.modules.get(
        "pytorch_distributedtraining_tpu.observe.opcost"
    )
    for name, v in (getattr(oc, "rolling_gauges", None) or {}).items():
        if isinstance(v, (int, float)):
            out[f"opcost_{name}"] = float(v)
    return out


def _numerics_rolling_gauges() -> dict:
    """The training-numerics plane's health gauges (grad_norm,
    nonfinite_steps_total, fp8_amax_saturation, update ratios, wire
    residual norms — observe/numerics.py) — sys.modules, never imported,
    so a run without the numerics plane publishes nothing."""
    out: dict = {}
    nm = sys.modules.get(
        "pytorch_distributedtraining_tpu.observe.numerics"
    )
    for name, v in (getattr(nm, "rolling_gauges", None) or {}).items():
        if isinstance(v, (int, float)):
            out[f"numerics_{name}"] = float(v)
    return out


def _router_rolling_gauges() -> dict:
    """The serve-fleet router's per-dispatch counters (in-flight depth,
    delivered/failover/replay/shed totals — serve/router.py) —
    sys.modules, never imported, so a rank that never hosted a router
    publishes nothing. The failover instants themselves land in the
    trace stream (``fleet.failover``); these gauges are the Prometheus
    view the monitor labels per rank."""
    out: dict = {}
    rt = sys.modules.get(
        "pytorch_distributedtraining_tpu.serve.router"
    )
    for name, v in (getattr(rt, "rolling_gauges", None) or {}).items():
        if isinstance(v, (int, float)):
            out[str(name)] = float(v)
    return out


class RankMetricsPublisher:
    """One rank's metric publication into the membership store.

    ``observe_step`` feeds the step-time histogram; ``publish`` writes
    every histogram (plus the serving engine's rolling counters, when
    that module is live) through ``store.publish_metrics`` — both store
    backends carry it, so TCP-only followers publish the same way the
    shared-filesystem ones do. Publication is rate-limited; the store
    write happens off the step's critical path at most once per
    ``publish_every_s``.
    """

    def __init__(
        self,
        store,
        host_id: str,
        rank: int,
        publish_every_s: float = 2.0,
        clock=time.monotonic,
    ):
        self.store = store
        self.host_id = str(host_id)
        self.rank = int(rank)
        self.publish_every_s = float(publish_every_s)
        self._clock = clock
        self._last_publish: float | None = None
        self.hists: dict = {"step_time_seconds": StreamHist()}
        self.offset: ClockOffset | None = None

    def sync_clock(self, pings: int = 8) -> ClockOffset | None:
        try:
            self.offset = estimate_store_offset(self.store, pings=pings)
        except Exception:  # noqa: BLE001 — telemetry never kills a rank
            self.offset = None
        return self.offset

    def observe_step(self, dt_s: float) -> None:
        self.hists["step_time_seconds"].observe(dt_s)
        self.publish()

    def observe(self, name: str, value: float) -> None:
        self.hists.setdefault(name, StreamHist()).observe(value)

    def publish(self, force: bool = False) -> bool:
        now = self._clock()
        if (
            not force
            and self._last_publish is not None
            and now - self._last_publish < self.publish_every_s
        ):
            return False
        self._last_publish = now
        hists = dict(self.hists)
        hists.update(_serve_rolling_hists())
        doc: dict = {"hists": {k: h.to_dict() for k, h in hists.items()}}
        gauges = _serve_rolling_gauges()
        gauges.update(_numerics_rolling_gauges())
        gauges.update(_opcost_rolling_gauges())
        gauges.update(_router_rolling_gauges())
        if gauges:
            doc["gauges"] = gauges
        if self.offset is not None:
            doc["clock_offset_s"] = self.offset.offset_s
            doc["clock_uncertainty_s"] = self.offset.uncertainty_s
        try:
            self.store.publish_metrics(
                host_id=self.host_id, rank=self.rank, doc=doc
            )
        except Exception:  # noqa: BLE001 — ditto
            return False
        return True


# -- controller-side monitor --------------------------------------------


class FleetMonitor:
    """Controller-side aggregation: step logs + published rank metrics →
    fleet histograms, straggler gauge, and (optionally) a live endpoint.

    ``poll`` is cheap and rate-limited — the launcher calls it from its
    monitor loop; ``refresh`` does the work: re-read the shared run
    dir's step logs (current generation epoch only), rebuild the fleet
    step-time histogram, re-run the straggler check, merge every rank's
    published histograms, and update the Prometheus snapshot the
    exporter serves. Newly flagged stragglers emit a ``fleet.straggler``
    instant and reset their host's consecutive-healthy-probes streak in
    the membership store — the same health signal quarantine admission
    reads, so a dragging host cannot earn a grow-back while it drags.
    """

    def __init__(
        self,
        run_dir: str | None = None,
        store=None,
        *,
        port: int | None = None,
        host: str = "127.0.0.1",
        interval_s: float = 2.0,
        z_threshold: float = 3.5,
        min_ranks: int = 3,
        epoch: int | None = None,
        clock=time.monotonic,
    ):
        self.run_dir = run_dir
        self.store = store
        self.interval_s = float(interval_s)
        self.z_threshold = float(z_threshold)
        self.min_ranks = int(min_ranks)
        self.epoch = epoch
        self._clock = clock
        self._last_refresh: float | None = None
        self._lock = threading.Lock()
        self._hists: dict = {}
        self._gauges: dict = {}
        self.flagged: set = set()
        self.report = None
        self.exporter = (
            MetricsExporter(self.prometheus, host=host, port=port)
            if port is not None else None
        )

    def note_epoch(self, epoch: int) -> None:
        """New generation: straggler stats restart from its fresh logs."""
        if self.epoch != epoch:
            self.epoch = epoch
            self.flagged = set()

    def poll(self, now: float | None = None) -> None:
        now = self._clock() if now is None else now
        if (
            self._last_refresh is not None
            and now - self._last_refresh < self.interval_s
        ):
            return
        self._last_refresh = now
        self.refresh()

    def refresh(self) -> None:
        try:
            times = _goodput.read_step_logs(self.run_dir, epoch=self.epoch)
        except OSError:
            times = {}
        hist = StreamHist()
        for ts in times.values():
            for t in ts:
                hist.observe(t)
        hists: dict = {"fleet_step_time_seconds": hist}
        report = _goodput.flag_stragglers(
            times, z_threshold=self.z_threshold, min_ranks=self.min_ranks
        )
        self.report = report
        self._note_stragglers(report)
        serve_gauges: dict = {}
        for doc in self._published():
            for name, payload in (doc.get("hists") or {}).items():
                try:
                    incoming = StreamHist.from_dict(payload)
                except (KeyError, TypeError, ValueError):
                    continue
                pname = re.sub(r"[^a-zA-Z0-9_:]", "_", str(name))
                if not pname.startswith("fleet_"):
                    pname = f"fleet_{pname}"
                if pname in hists:
                    try:
                        hists[pname].merge(incoming)
                    except ValueError:
                        continue  # foreign bounds cannot merge
                else:
                    hists[pname] = incoming
            # serving-health gauges ride the same snapshot, labelled per
            # rank so one dragging engine is visible next to the fleet's
            for name, v in (doc.get("gauges") or {}).items():
                if not isinstance(v, (int, float)):
                    continue
                pname = re.sub(r"[^a-zA-Z0-9_:]", "_", str(name))
                serve_gauges[
                    f'{pname}{{rank="{int(doc.get("rank", -1))}"}}'
                ] = float(v)
        gauges = {
            "fleet_ranks": float(len(times)),
            "fleet_stragglers": float(len(report.stragglers)),
        }
        gauges.update(serve_gauges)
        for r in report.stragglers:
            gauges[f'fleet_straggler_rank{{rank="{int(r)}"}}'] = 1.0
        with self._lock:
            self._hists = hists
            self._gauges = gauges

    def _published(self) -> list:
        if self.store is None:
            return []
        try:
            return self.store.read_metrics()
        except Exception:  # noqa: BLE001 — a torn store read never kills us
            return []

    def _note_stragglers(self, report) -> None:
        new = set(report.stragglers) - self.flagged
        self.flagged = set(report.stragglers)
        if not new:
            return
        runtime_stats["stragglers_flagged"] += len(new)
        rank_hosts: dict = {}
        if self.store is not None:
            try:
                rank_hosts = {
                    d["rank"]: d.get("host_id")
                    for d in self.store.live_ranks()
                }
            except Exception:  # noqa: BLE001
                rank_hosts = {}
        for r in sorted(new):
            if _trace.enabled():
                _trace.instant(
                    "fleet.straggler", "outage",
                    rank=int(r),
                    median_s=report.medians.get(r),
                    z=report.zscores.get(r),
                )
            host = rank_hosts.get(r)
            if host and self.store is not None:
                # the quarantine health signal: a dragging host's healthy
                # streak resets, so grow admission cannot pick it while
                # it drags (record_probe is the same signal the grow
                # probe loop feeds)
                try:
                    self.store.record_probe(host_id=host, healthy=False)
                    self.store.record_transition(
                        kind="straggler", rank=int(r), host=host,
                        median_s=report.medians.get(r),
                    )
                except Exception:  # noqa: BLE001
                    pass

    def prometheus(self) -> str:
        with self._lock:
            return prometheus_text(self._hists, self._gauges)

    def mfu_table(
        self,
        model_flops_per_step: float = 0.0,
        platform: str = "",
        device_kind: str = "",
    ) -> dict:
        try:
            times = _goodput.read_step_logs(self.run_dir, epoch=self.epoch)
        except OSError:
            return {}
        rank_hosts: dict = {}
        if self.store is not None:
            try:
                rank_hosts = {
                    d["rank"]: d.get("host_id")
                    for d in self.store.live_ranks()
                }
            except Exception:  # noqa: BLE001
                rank_hosts = {}
        return per_host_mfu(
            times, rank_hosts, model_flops_per_step,
            platform=platform, device_kind=device_kind,
        )

    def close(self) -> None:
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None


# -- perf-regression sentry ---------------------------------------------

_BENCH_FILE_RE = re.compile(r"^BENCH_r\d+\.json$")
_VERDICT_KEEP = 32


def genuine_measurement(rec) -> bool:
    """True for records the trajectory statistics may stand on.

    Outage error records (``value: 0.0`` + an ``"error"`` key), fallback
    records (``provenance: FALLBACK`` / ``measured: false``), and
    zero/absent values are all excluded — a pool outage is not a
    regression, and a fallback number was never measured.
    """
    if not isinstance(rec, dict):
        return False
    if "error" in rec:
        return False
    if rec.get("provenance") == "FALLBACK" or rec.get("measured") is False:
        return False
    try:
        return float(rec.get("value", 0.0)) > 0.0
    except (TypeError, ValueError):
        return False


def _unwrap(doc):
    """``BENCH_r*.json`` wrappers carry the record under ``parsed``."""
    if isinstance(doc, dict) and "parsed" in doc and "metric" not in doc:
        return doc.get("parsed")
    return doc


def load_trajectory(root: str | None = None) -> list:
    """Every bench record in the repo's trajectory files, oldest first:
    ``BENCH_r*.json`` (round wrappers) then ``BENCH_LAST_GOOD.json``.
    Non-genuine records are KEPT here (callers can count outages);
    :func:`regression_verdict` filters when it builds statistics."""
    root = root or os.getcwd()
    try:
        names = sorted(n for n in os.listdir(root) if _BENCH_FILE_RE.match(n))
    except OSError:
        names = []
    names.append("BENCH_LAST_GOOD.json")
    out: list = []
    seen: set = set()
    for name in names:
        try:
            with open(os.path.join(root, name), encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        rec = _unwrap(doc)
        if not isinstance(rec, dict):
            continue
        key = (
            rec.get("metric"), rec.get("value"), rec.get("measured_at")
        )
        if key in seen:
            continue  # BENCH_LAST_GOOD often duplicates the newest round
        seen.add(key)
        out.append(rec)
    return out


_SUMMARY_HEADLINES = {
    # summary records carry no top-level "value"; the regression sentry
    # trends their headline metric instead. serve_bench.py's serve_slo
    # record headlines the decode fast path's throughput claim — the
    # number speculative decoding exists to move.
    "serve_slo": ("decode_tokens_per_sec_spec", "tok/s"),
    # hier_bench.py's record headlines the per-device bytes the two-level
    # sync puts on the DCN hop — the number the hierarchy exists to shrink.
    "hier": ("dcn_bytes", "bytes"),
}


def headline_record(rec):
    """Map a summary record (no top-level ``value``) onto its headline
    metric so :func:`regression_verdict` can trend it; anything already
    carrying a ``value`` — or a summary without its headline field —
    passes through unchanged."""
    rec = _unwrap(rec)
    if not isinstance(rec, dict) or rec.get("value") is not None:
        return rec
    pick = _SUMMARY_HEADLINES.get(rec.get("metric"))
    if not pick or rec.get(pick[0]) is None:
        return rec
    name, unit = pick
    out = dict(rec)
    out.update(
        metric=name, value=float(rec[name]), unit=unit,
        headline_of=rec.get("metric"),
    )
    return out


def metric_direction(rec: dict) -> str:
    """Which way is worse: ``higher``-is-better (throughput, MFU) or
    ``lower``-is-better (latencies, recovery times)."""
    unit = str(rec.get("unit", "")).lower()
    metric = str(rec.get("metric", "")).lower()
    if "/s" in unit or "/sec" in unit or "per_s" in unit:
        return "higher"
    if (
        unit in ("s", "ms", "seconds")
        or metric.startswith("time")
        or metric.endswith("_s")
        or "latency" in metric
        or "ttft" in metric
    ):
        return "lower"
    if unit in ("bytes", "b") or metric.endswith("_bytes"):
        return "lower"  # wire/DCN payload gauges: growth is the regression
    return "higher"


def regression_verdict(
    fresh,
    history: list,
    *,
    warn_frac: float = 0.05,
    err_frac: float = 0.15,
    z_gate: float = 3.5,
) -> dict:
    """Compare a fresh bench record against the trajectory.

    Per metric family (records sharing ``metric``), the baseline is the
    median of the *genuine* historical values and the noise band is the
    robust z-gate over their MAD (``z_gate * 1.4826 * MAD / median``) —
    a shortfall inside the band is trajectory noise, not a verdict. A
    shortfall beyond the band is ``drift`` (WARN) from ``warn_frac`` and
    ``regression`` (ERROR) from ``err_frac``. Statuses:

    ``excluded``      fresh record is an outage/fallback — never a verdict
    ``no-trajectory`` no genuine history for this metric family
    ``improved`` / ``ok`` / ``drift`` / ``regression``
    """
    rec = _unwrap(fresh)
    verdict: dict = {
        "status": "excluded",
        "metric": rec.get("metric") if isinstance(rec, dict) else None,
        "value": rec.get("value") if isinstance(rec, dict) else None,
        "warn_frac": warn_frac,
        "err_frac": err_frac,
    }
    if not genuine_measurement(rec):
        verdict["detail"] = (
            "outage/fallback/zero-value record: excluded from regression "
            "accounting (a pool outage is not a perf regression)"
        )
    else:
        metric = rec.get("metric")
        vals = sorted(
            float(h["value"]) for h in history
            if genuine_measurement(h) and h.get("metric") == metric
        )
        value = float(rec["value"])
        direction = metric_direction(rec)
        verdict["direction"] = direction
        verdict["n_history"] = len(vals)
        if not vals:
            verdict["status"] = "no-trajectory"
            verdict["detail"] = (
                f"no genuine {metric!r} measurements in the trajectory"
            )
        else:
            med = vals[len(vals) // 2]
            mad = sorted(abs(v - med) for v in vals)[len(vals) // 2]
            worse = (
                (med - value) / med if direction == "higher"
                else (value - med) / med
            )
            noise = z_gate * 1.4826 * mad / med if med > 0 else 0.0
            if worse <= 0:
                status = "improved" if -worse > warn_frac else "ok"
            elif worse <= noise:
                status = "ok"  # inside the trajectory's own noise band
            elif worse >= err_frac:
                status = "regression"
            elif worse >= warn_frac:
                status = "drift"
            else:
                status = "ok"
            verdict.update(
                status=status,
                baseline_median=med,
                baseline_mad=mad,
                worse_frac=round(worse, 6),
                noise_frac=round(noise, 6),
            )
            arrow = "below" if direction == "higher" else "above"
            verdict["detail"] = (
                f"{metric}={value:g} vs trajectory median {med:g} "
                f"(n={len(vals)}, MAD={mad:g}): {worse:+.1%} {arrow} "
                f"baseline -> {status}"
            )
    runtime_stats["verdicts"].append(verdict)
    del runtime_stats["verdicts"][:-_VERDICT_KEEP]
    return verdict


# -- bench record summary -----------------------------------------------


def fleet_summary_from_records(records: list) -> dict | None:
    """The ``fleet`` field a bench record carries: the step-time
    histogram summary of one rank's tracer records (cat ``step``,
    top-level spans). Post-hoc over the already-recorded buffer — zero
    hot-path cost, so the 1% telemetry-overhead gate is untouched."""
    hist = StreamHist()
    for r in records:
        if (
            r.get("instant")
            or r.get("cat") != "step"
            or r.get("depth", 0) != 0
        ):
            continue
        hist.observe(r["dur"])
    if hist.count == 0:
        return None
    return {
        "host": _trace._host(),
        "rank": _trace._rank(),
        "steps": hist.count,
        "step_time_p50_s": hist.quantile(0.5),
        "step_time_p95_s": hist.quantile(0.95),
        "hist": hist.to_dict(),
    }
