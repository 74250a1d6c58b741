"""Fleet observability plane: trace merge, metrics export, perf sentry.

Covers the PR's acceptance criteria end to end on the CPU mesh: the
midpoint clock-offset estimator recovers injected skews within its
reported uncertainty (fake clocks and a real skewed TCP membership
store), a 2-process run merges into one Chrome trace with clock-aligned
per-host/per-rank lanes, the controller's endpoint serves scrapeable
Prometheus text with the fleet step-time histogram and straggler gauge,
and the regression sentry's truth table (improvement / drift /
regression / outage-excluded) holds on doctored records while the
repo's genuine BENCH trajectory passes. The satellite behaviors ride
along: torn-JSONL tolerance, epoch-namespaced step logs and their GC,
and host/rank stamping in exported traces.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from pytorch_distributedtraining_tpu.observe import fleet, goodput, trace
from pytorch_distributedtraining_tpu.observe.fleet import (
    ClockOffset,
    FleetMonitor,
    MetricsExporter,
    RankMetricsPublisher,
    StreamHist,
    estimate_offset,
    estimate_store_offset,
    genuine_measurement,
    lane_ledgers,
    load_trajectory,
    merge_ledgers,
    merge_traces,
    metric_direction,
    per_host_mfu,
    prometheus_text,
    regression_verdict,
)
from pytorch_distributedtraining_tpu.runtime.launch import _gc_stale_step_logs
from pytorch_distributedtraining_tpu.runtime.membership import (
    MembershipStore,
    TCPMembershipStore,
    serve_store,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fleet_stats():
    """runtime_stats is process-global (the analyze rule reads it via
    sys.modules) — no test may leak verdicts into another plane's run."""
    fleet.reset_runtime_stats()
    yield
    fleet.reset_runtime_stats()


def _scrape(url: str) -> str:
    return urllib.request.urlopen(url, timeout=5).read().decode()


# -- mergeable streaming histograms ------------------------------------


class TestStreamHist:
    def test_observe_merge_and_moments(self):
        a, b = StreamHist(), StreamHist()
        for v in (0.01, 0.02, 1.5):
            a.observe(v)
        b.observe(0.02)
        a.merge(b)
        assert a.count == 4
        assert a.sum == pytest.approx(1.55)
        assert a.min == pytest.approx(0.01)
        assert a.max == pytest.approx(1.5)
        assert sum(a.counts) == a.count

    def test_identical_bounds_everywhere(self):
        # the merge contract: every rank builds the same bounds with no
        # coordination, so count-sum merging is exact
        assert StreamHist().bounds == StreamHist().bounds

    def test_merge_rejects_foreign_bounds(self):
        with pytest.raises(ValueError, match="bounds"):
            StreamHist().merge(StreamHist(per_decade=8))

    def test_under_and_overflow_cells(self):
        h = StreamHist()
        h.observe(1e-7)   # below the lowest bound
        h.observe(1e7)    # above the highest
        assert h.counts[0] == 1 and h.counts[-1] == 1
        assert h.count == 2

    def test_quantile_is_conservative_upper_bound(self):
        h = StreamHist()
        for _ in range(99):
            h.observe(0.01)
        h.observe(5.0)
        assert h.quantile(0.5) >= 0.01
        assert h.quantile(1.0) >= 5.0
        assert StreamHist().quantile(0.5) is None

    def test_dict_round_trip(self):
        h = StreamHist()
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        h2 = StreamHist.from_dict(json.loads(json.dumps(h.to_dict())))
        assert h2.counts == h.counts
        assert h2.sum == pytest.approx(h.sum)
        h.merge(h2)  # round-tripped bounds still merge
        assert h.count == 6

    def test_prometheus_lines_cumulative(self):
        h = StreamHist()
        h.observe(0.01)
        h.observe(0.5)
        lines = h.prometheus_lines("fleet_step_time_seconds")
        assert lines[0] == "# TYPE fleet_step_time_seconds histogram"
        assert any('le="+Inf"} 2' in ln for ln in lines)
        assert any(ln.startswith("fleet_step_time_seconds_sum") for ln in lines)
        assert lines[-1] == "fleet_step_time_seconds_count 2"
        # cumulative counts never decrease
        cums = [
            int(ln.rsplit(" ", 1)[1]) for ln in lines if "_bucket{" in ln
        ]
        assert cums == sorted(cums)

    def test_prometheus_text_gauges_with_labels(self):
        text = prometheus_text(
            {"fleet_step_time_seconds": StreamHist()},
            {"fleet_stragglers": 1, 'fleet_straggler_rank{rank="3"}': 1.0},
        )
        assert "# TYPE fleet_stragglers gauge" in text
        assert 'fleet_straggler_rank{rank="3"} 1' in text
        # the TYPE header uses the bare name, not the labeled one
        assert "# TYPE fleet_straggler_rank gauge" in text


# -- clock-offset estimation -------------------------------------------


class TestClockOffset:
    @pytest.mark.parametrize("true_offset", [3.25, -2.0, 0.0, 120.5])
    def test_recovers_injected_offset_within_bounds(self, true_offset):
        local = [1000.0]

        def clock():
            local[0] += 0.004  # 4ms per clock read -> 8ms rtt
            return local[0]

        def probe():
            return local[0] + true_offset

        off = estimate_offset(probe, pings=6, clock=clock)
        assert isinstance(off, ClockOffset)
        # midpoint guarantee: the true offset lies within +-rtt/2
        assert abs(off.offset_s - true_offset) <= off.uncertainty_s + 1e-9
        assert off.uncertainty_s == pytest.approx(off.rtt_s / 2)
        assert float(off) == off.offset_s

    def test_min_rtt_sample_wins(self):
        # three pings with decreasing rtt; the tightest (0.1s) must be
        # the one the estimator keeps — scripted (t0, tr, t1) triples
        pings = [(0.0, 5.9, 2.0), (10.0, 15.2, 11.0), (20.0, 25.05, 20.1)]
        clocks = iter(t for t0, _, t1 in pings for t in (t0, t1))
        replies = iter(tr for _, tr, _ in pings)
        off = estimate_offset(
            lambda: next(replies), pings=3, clock=lambda: next(clocks)
        )
        assert off.rtt_s == pytest.approx(0.1)
        assert off.offset_s == pytest.approx(25.05 - 20.05)
        assert off.pings == 3

    def test_store_clock_probe_over_tcp(self, tmp_path):
        # a membership store whose clock runs 5s ahead: the TCP proxy's
        # clock_probe must surface it and the estimator must recover it
        backing = MembershipStore(
            str(tmp_path / "m"), clock=lambda: time.time() + 5.0
        )
        server, _ = serve_store(backing, port=0)
        try:
            store = TCPMembershipStore(
                f"127.0.0.1:{server.server_address[1]}"
            )
            off = estimate_store_offset(store, pings=4)
            assert abs(off.offset_s - 5.0) <= off.uncertainty_s + 0.05
            assert off.rtt_s < 2.0  # loopback line-JSON round trip
        finally:
            server.shutdown()
            server.server_close()


# -- cross-host trace merge --------------------------------------------

_EXPORT_SCRIPT = """
import os, sys, time
from pytorch_distributedtraining_tpu.observe import trace
trace.enable(crash_handler=False)
with trace.span("train.dispatch", "step", step=0):
    time.sleep(0.02)
with trace.span("train.dispatch", "step", step=1):
    time.sleep(0.02)
trace.instant("fleet.mark", "other")
trace.export_chrome_trace(sys.argv[1])
"""


class TestTraceMerge:
    def _export_two_process(self, tmp_path):
        """Two real processes on distinct fake hosts export traces."""
        paths = []
        for host, rank in (("node0", 0), ("node1", 1)):
            out = str(tmp_path / f"{host}.trace.json")
            env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                GRAFT_RUN_DIR=str(tmp_path),
                GRAFT_HOST_ID=host,
                GRAFT_RANK=str(rank),
            )
            env.pop("GRAFT_TELEMETRY", None)
            r = subprocess.run(
                [sys.executable, "-c", _EXPORT_SCRIPT, out],
                env=env, capture_output=True, text=True, cwd=REPO,
                timeout=240,
            )
            assert r.returncode == 0, r.stderr
            paths.append(out)
        return paths

    def test_export_stamps_host_rank_and_meta(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GRAFT_HOST_ID", "nodeX")
        monkeypatch.setenv("GRAFT_RANK", "7")
        tr = trace.Tracer()
        tr.enabled = True
        t0 = time.perf_counter()
        tr.add_span("s", "step", t0, 0.01, depth=0)
        tr.add_span("inner", "step", t0 + 0.001, 0.002, depth=1)
        path = tr.export_chrome_trace(str(tmp_path / "t.trace.json"))
        doc = json.load(open(path))
        meta = doc["graftMeta"]
        assert meta["host"] == "nodeX" and meta["rank"] == 7
        assert meta["pid"] == os.getpid()
        # wall anchor: trace-zero expressed on this host's wall clock
        assert abs(meta["wall_t0"] - time.time()) < 60.0
        pn = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        ][0]
        assert pn["args"]["host"] == "nodeX" and pn["args"]["rank"] == 7
        assert pn["args"]["name"].startswith("graft-telemetry")
        depths = sorted(
            e["depth"] for e in doc["traceEvents"] if e.get("ph") == "X"
        )
        assert depths == [0, 1]

    def test_host_fallback_uses_node_rank(self, monkeypatch):
        monkeypatch.delenv("GRAFT_HOST_ID", raising=False)
        monkeypatch.setenv("GRAFT_NODE_RANK", "3")
        assert trace._host() == "node3"

    def test_two_process_merge_lanes_and_alignment(self, tmp_path):
        paths = self._export_two_process(tmp_path)
        docs = [json.load(open(p)) for p in paths]
        # inject a synthetic +7.5s clock skew on node1 and estimate it
        # back with fake clocks, exactly as a controller would
        skew = 7.5
        docs[1]["graftMeta"]["wall_t0"] += skew
        local = [500.0]

        def clock():
            local[0] += 0.001
            return local[0]

        off = estimate_offset(
            lambda: local[0] + skew, pings=4, clock=clock
        )
        assert abs(off.offset_s - skew) <= off.uncertainty_s + 1e-9

        merged = merge_traces(
            [docs[0], docs[1]], offsets={"node1": off},
            out_path=str(tmp_path / "fleet.trace.json"),
        )
        lanes = merged["graftFleet"]["lanes"]
        assert merged["graftFleet"]["aligned"] is True
        assert [(l["host"], l["rank"]) for l in lanes] == [
            ("node0", 0), ("node1", 1),
        ]
        # fresh collision-free pids in (host, rank) order
        assert [l["pid"] for l in lanes] == [1, 2]
        assert lanes[1]["offset_s"] == pytest.approx(off.offset_s)
        def lane_gap(doc):
            by_pid = {}
            for e in doc["traceEvents"]:
                if e.get("ph") == "X":
                    by_pid.setdefault(e["pid"], []).append(e["ts"])
            return min(by_pid[2]) - min(by_pid[1])

        # against the uncorrected merge, applying the estimated offset
        # must pull node1's lane back by exactly the injected skew (to
        # within the estimator's reported uncertainty)
        uncorrected = merge_traces([docs[0], docs[1]])
        removed_us = lane_gap(uncorrected) - lane_gap(merged)
        assert removed_us == pytest.approx(
            skew * 1e6, abs=(off.uncertainty_s + 1e-6) * 1e6
        )
        # per-lane process metadata carries identity for the summarizer
        names = {
            e["pid"]: e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert names[1] == "graft-telemetry host=node0 rank=0"
        assert names[2] == "graft-telemetry host=node1 rank=1"

    def test_unaligned_without_wall_anchor(self):
        legacy = {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 42, "tid": 0,
             "args": {"name": "graft-telemetry (rank 2)"}},
            {"ph": "X", "name": "s", "cat": "step", "pid": 42, "tid": 0,
             "ts": 0.0, "dur": 100.0, "depth": 0},
        ]}
        merged = merge_traces([legacy])
        assert merged["graftFleet"]["aligned"] is False
        # rank recovered from the legacy process_name text
        assert merged["graftFleet"]["lanes"][0]["rank"] == 2

    def test_lane_ledgers_and_fleet_union(self, tmp_path):
        paths = self._export_two_process(tmp_path)
        merged = merge_traces(paths)
        ledgers = lane_ledgers(merged)
        assert len(ledgers) == 2
        for led in ledgers.values():
            # two top-level 20ms step spans -> productive time dominates
            assert led.buckets["productive"] == pytest.approx(
                0.04, rel=0.8
            )
        union = merge_ledgers(ledgers)
        assert union["lanes"] == 2
        assert union["fleet_seconds"] == pytest.approx(
            sum(l.wall_s for l in ledgers.values()), rel=1e-3
        )
        assert union["wall_s"] == pytest.approx(
            max(l.wall_s for l in ledgers.values()), rel=1e-3
        )
        assert 0.0 < union["goodput_fraction"] <= 1.0

    def test_trace_summary_rolls_up_fleet_lanes(self, tmp_path):
        paths = self._export_two_process(tmp_path)
        out_dir = tmp_path / "fleetdir"
        out_dir.mkdir()
        merge_traces(paths, out_path=str(out_dir / "fleet.trace.json"))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks",
                                          "trace_summary.py"),
             str(out_dir)],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        rows = [json.loads(ln) for ln in r.stdout.splitlines() if ln]
        lane_rows = [row for row in rows if "lane" in row]
        assert {row["lane"] for row in lane_rows} == {
            "graft-telemetry host=node0 rank=0",
            "graft-telemetry host=node1 rank=1",
        }
        assert all(row["total_span_ms"] > 0 for row in lane_rows)
        assert all("step" in row["by_cat_ms"] for row in lane_rows)

    def test_per_host_mfu_table(self, monkeypatch):
        monkeypatch.setenv("GRAFT_PEAK_FLOPS", "1e12")
        table = per_host_mfu(
            {0: [0.01] * 5, 1: [0.01] * 5, 2: [0.02] * 5},
            rank_hosts={0: "node0", 1: "node0", 2: "node1"},
            model_flops_per_step=1e9,
        )
        assert table["node0"]["ranks"] == [0, 1]
        assert table["node0"]["mfu"] == pytest.approx(0.1)
        assert table["node1"]["mfu"] == pytest.approx(0.05)


# -- torn step logs + epoch rotation (satellites) ----------------------


class TestStepLogHygiene:
    def test_torn_trailing_line_skipped_and_counted(self, tmp_path):
        with goodput.StepLog(rank=0, base=str(tmp_path)) as sl:
            for s in range(4):
                sl.record(s, 0.1)
        path = os.path.join(str(tmp_path), "steps", "rank_0.jsonl")
        with open(path, "ab") as fh:
            # killed mid-write: no newline, split inside a UTF-8 rune
            fh.write('{"rank": 0, "step": 9, "dt_s": 0.1, "x": "é'
                     .encode()[:-1])
        stats = {}
        times = goodput.read_step_logs(str(tmp_path), stats=stats)
        assert times[0] == [0.1] * 4
        assert stats["files"] == 1
        assert stats["skipped_lines"] == 1
        assert stats["torn_tail_lines"] == 1

    def test_interior_garbage_is_skipped_not_torn(self, tmp_path):
        d = os.path.join(str(tmp_path), "steps")
        os.makedirs(d)
        with open(os.path.join(d, "rank_1.jsonl"), "w") as fh:
            fh.write('{"dt_s": 0.1}\nnot json\n{"dt_s": 0.2}\n')
        stats = {}
        times = goodput.read_step_logs(str(tmp_path), stats=stats)
        assert times[1] == [0.1, 0.2]
        assert stats["skipped_lines"] == 1
        assert stats["torn_tail_lines"] == 0

    def test_epoch_namespaces_step_logs(self, tmp_path, monkeypatch):
        base = str(tmp_path)
        with goodput.StepLog(rank=0, base=base, epoch=2) as sl:
            sl.record(0, 0.3)
        assert os.path.exists(
            os.path.join(base, "steps", "epoch_2", "rank_0.jsonl")
        )
        # the env var is the cross-process channel (launcher -> ranks)
        monkeypatch.setenv("GRAFT_GEN_EPOCH", "2")
        assert goodput.read_step_logs(base) == {0: [0.3]}
        monkeypatch.setenv("GRAFT_GEN_EPOCH", "3")
        assert goodput.read_step_logs(base) == {}
        # explicit arg beats the env
        assert goodput.read_step_logs(base, epoch=2) == {0: [0.3]}

    def test_stale_epochs_do_not_pollute_straggler_check(self, tmp_path):
        base = str(tmp_path)
        # epoch 1: a 4-rank world where rank 3 dragged
        for r, dt in enumerate([0.1, 0.1, 0.1, 0.9]):
            with goodput.StepLog(rank=r, base=base, epoch=1) as sl:
                for s in range(5):
                    sl.record(s, dt)
        # epoch 2: shrunk to 3 healthy ranks
        for r in range(3):
            with goodput.StepLog(rank=r, base=base, epoch=2) as sl:
                for s in range(5):
                    sl.record(s, 0.1)
        assert goodput.straggler_check(base, epoch=1).stragglers == (3,)
        assert goodput.straggler_check(base, epoch=2).stragglers == ()

    def test_gc_drops_older_epochs_and_legacy_flat_logs(self, tmp_path):
        base = str(tmp_path)
        with goodput.StepLog(rank=0, base=base) as sl:  # legacy flat
            sl.record(0, 0.1)
        for e in (1, 2):
            with goodput.StepLog(rank=0, base=base, epoch=e) as sl:
                sl.record(0, 0.1)
        _gc_stale_step_logs(base, keep_epoch=2)
        steps = os.path.join(base, "steps")
        assert not os.path.exists(os.path.join(steps, "rank_0.jsonl"))
        assert not os.path.exists(os.path.join(steps, "epoch_1"))
        assert os.path.exists(
            os.path.join(steps, "epoch_2", "rank_0.jsonl")
        )

    def test_gc_keeps_flat_logs_at_epoch_zero(self, tmp_path):
        base = str(tmp_path)
        with goodput.StepLog(rank=0, base=base) as sl:
            sl.record(0, 0.1)
        _gc_stale_step_logs(base, keep_epoch=0)
        assert os.path.exists(
            os.path.join(base, "steps", "rank_0.jsonl")
        )


# -- live metrics export ------------------------------------------------


class TestMetricsPlane:
    def _seed_logs(self, base, medians=(0.1, 0.1, 0.1, 0.5)):
        for r, dt in enumerate(medians):
            with goodput.StepLog(rank=r, base=base) as sl:
                for s in range(5):
                    sl.record(s, dt)

    def test_monitor_flags_straggler_and_feeds_quarantine(self, tmp_path):
        base = str(tmp_path / "run")
        store = MembershipStore(str(tmp_path / "m"))
        store.note_rank(rank=3, host_id="node1")
        store.record_probe(host_id="node1", healthy=True)
        assert store.health("node1")["consecutive_healthy_probes"] == 1
        self._seed_logs(base)
        mon = FleetMonitor(base, store=store, interval_s=0.0)
        mon.refresh()
        try:
            assert mon.report.stragglers == (3,)
            # the quarantine admission signal: the dragging host's
            # healthy streak is reset, and the transition log says why
            assert store.health("node1")["consecutive_healthy_probes"] == 0
            kinds = [t["kind"] for t in store.transitions()]
            assert "straggler" in kinds
            assert fleet.runtime_stats["stragglers_flagged"] == 1
            # already-flagged ranks do not re-fire every refresh
            mon.refresh()
            assert fleet.runtime_stats["stragglers_flagged"] == 1
        finally:
            mon.close()

    def test_monitor_emits_fleet_straggler_instant(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("GRAFT_RUN_DIR", str(tmp_path))
        base = str(tmp_path / "run")
        self._seed_logs(base)
        trace.clear()
        trace.enable(crash_handler=False)
        try:
            mon = FleetMonitor(base, interval_s=0.0)
            mon.refresh()
            mon.close()
            instants = [
                r["name"] for r in trace.records() if r.get("instant")
            ]
            assert "fleet.straggler" in instants
        finally:
            trace.disable()
            trace.clear()

    def test_endpoint_serves_prometheus_text(self, tmp_path):
        base = str(tmp_path / "run")
        store = MembershipStore(str(tmp_path / "m"))
        self._seed_logs(base)
        pub = RankMetricsPublisher(store, "node0", 0, publish_every_s=0.0)
        pub.observe_step(0.1)
        pub.observe("serve_ttft_seconds", 0.05)
        assert pub.publish(force=True)
        mon = FleetMonitor(base, store=store, port=0, interval_s=0.0)
        try:
            mon.refresh()
            body = _scrape(mon.exporter.url)
            assert "# TYPE fleet_step_time_seconds histogram" in body
            # 20 step-log samples + 1 published -> merged count
            assert "fleet_step_time_seconds_count 21" in body
            assert "fleet_serve_ttft_seconds_count 1" in body
            assert "fleet_ranks 4" in body
            assert "fleet_stragglers 1" in body
            assert 'fleet_straggler_rank{rank="3"} 1' in body
            assert fleet.runtime_stats["scrapes"] == 1
            with pytest.raises(urllib.error.HTTPError):
                _scrape(mon.exporter.url.replace("/metrics", "/nope"))
        finally:
            mon.close()

    def test_publisher_rate_limit_and_clock_sync(self, tmp_path):
        store = MembershipStore(
            str(tmp_path / "m"), clock=lambda: time.time() + 2.0
        )
        t = [0.0]
        pub = RankMetricsPublisher(
            store, "node0", 0, publish_every_s=5.0, clock=lambda: t[0]
        )
        off = pub.sync_clock(pings=2)
        assert off is not None and abs(off.offset_s - 2.0) < 0.5
        assert pub.publish()           # first publish goes through
        assert not pub.publish()       # inside the rate-limit window
        t[0] += 6.0
        assert pub.publish()           # window expired
        doc = store.read_metrics()[0]
        assert doc["clock_offset_s"] == pytest.approx(
            off.offset_s, abs=0.5
        )

    def test_serve_rolling_hists_reach_publisher(self, tmp_path):
        eng_mod = pytest.importorskip(
            "pytorch_distributedtraining_tpu.serve.engine"
        )
        eng_mod.rolling_hists.clear()
        eng_mod.note_delivery(
            {"latency_s": 0.8, "ttft_s": 0.2, "queue_s": 0.1}
        )
        eng_mod.note_delivery({"latency_s": 0.9, "ttft_s": None})
        try:
            assert eng_mod.rolling_hists["serve_latency_seconds"].count == 2
            assert eng_mod.rolling_hists["serve_ttft_seconds"].count == 1
            store = MembershipStore(str(tmp_path / "m"))
            pub = RankMetricsPublisher(store, "node0", 0)
            assert pub.publish(force=True)
            hists = store.read_metrics()[0]["hists"]
            assert hists["serve_latency_seconds"]["count"] == 2
        finally:
            eng_mod.rolling_hists.clear()

    def test_monitor_survives_broken_collect(self, tmp_path):
        calls = {"n": 0}

        def collect():
            calls["n"] += 1
            raise RuntimeError("boom")

        exp = MetricsExporter(collect, port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _scrape(exp.url)
            assert ei.value.code == 500
            # the serving thread survived the failure
            with pytest.raises(urllib.error.HTTPError):
                _scrape(exp.url)
            assert calls["n"] == 2
        finally:
            exp.close()

    def test_note_epoch_resets_flagged_set(self, tmp_path):
        base = str(tmp_path / "run")
        self._seed_logs(base)
        mon = FleetMonitor(base, interval_s=0.0)
        mon.refresh()
        assert mon.flagged == {3}
        mon.note_epoch(2)
        assert mon.flagged == set()
        mon.close()


# -- perf-regression sentry --------------------------------------------


def _rec(value, metric="images_per_sec", unit="images/sec/chip", **kw):
    return {"metric": metric, "value": value, "unit": unit, **kw}


class TestRegressionSentry:
    def test_genuine_measurement_filter(self):
        assert genuine_measurement(_rec(100.0))
        assert not genuine_measurement(_rec(0.0))
        assert not genuine_measurement(_rec(100.0, error="pool outage"))
        assert not genuine_measurement(_rec(100.0, provenance="FALLBACK"))
        assert not genuine_measurement(_rec(100.0, measured=False))
        assert not genuine_measurement(None)
        assert not genuine_measurement({"metric": "x", "value": "nan?"})

    def test_metric_direction(self):
        assert metric_direction(_rec(1.0)) == "higher"
        assert metric_direction(
            {"metric": "time_to_recover_s", "value": 3.0, "unit": "s"}
        ) == "lower"
        assert metric_direction(
            {"metric": "serve_p99_latency", "value": 0.5, "unit": "ms"}
        ) == "lower"

    def test_truth_table(self):
        history = [_rec(v) for v in (98.0, 100.0, 102.0, 100.0, 99.0)]
        cases = [
            (130.0, "improved"),
            (100.5, "ok"),
            (93.0, "drift"),        # 7% down: beyond warn, short of err
            (80.0, "regression"),   # 20% down
        ]
        for value, expected in cases:
            v = regression_verdict(_rec(value), history)
            assert v["status"] == expected, (value, v)
        # an outage record is excluded, never a regression
        v = regression_verdict(
            _rec(0.0, error="no capacity"), history
        )
        assert v["status"] == "excluded"
        # outage records in HISTORY do not drag the baseline either
        poisoned = history + [_rec(0.0, error="outage")] * 10
        assert regression_verdict(_rec(100.0), poisoned)["status"] == "ok"
        # all verdicts landed in runtime_stats for the analyze rule
        assert len(fleet.runtime_stats["verdicts"]) == 6

    def test_lower_is_better_flips_the_sign(self):
        history = [
            {"metric": "time_to_recover_s", "value": v, "unit": "s"}
            for v in (10.0, 10.5, 9.8)
        ]
        worse = regression_verdict(
            {"metric": "time_to_recover_s", "value": 13.0, "unit": "s"},
            history,
        )
        assert worse["status"] == "regression"
        better = regression_verdict(
            {"metric": "time_to_recover_s", "value": 8.0, "unit": "s"},
            history,
        )
        assert better["status"] == "improved"

    def test_noise_band_from_mad_suppresses_jitter(self):
        # a genuinely noisy trajectory: 20% MAD-driven noise band means a
        # 10% dip is trajectory weather, not a drift
        history = [_rec(v) for v in (80.0, 90.0, 100.0, 110.0, 120.0)]
        v = regression_verdict(_rec(90.0), history)
        assert v["status"] == "ok"
        assert v["noise_frac"] > 0.10

    def test_no_trajectory_and_unwrap(self, tmp_path):
        v = regression_verdict(_rec(100.0), [])
        assert v["status"] == "no-trajectory"
        # BENCH_r* wrapper shapes unwrap through "parsed"
        wrapped = {"n": 7, "cmd": "x", "rc": 0, "parsed": _rec(50.0)}
        v = regression_verdict(wrapped, [_rec(100.0)])
        assert v["status"] == "regression"
        assert regression_verdict(
            {"n": 8, "cmd": "x", "rc": 1, "parsed": None}, [_rec(100.0)]
        )["status"] == "excluded"

    def test_load_trajectory_of_one_last_good_record(self, tmp_path):
        """A trajectory that is one last-good record and no round wrapper
        (what the repository carried last) still judges a fresh record."""
        with open(tmp_path / "BENCH_LAST_GOOD.json", "w") as fh:
            json.dump(_rec(4851.12, vs_baseline=0.809), fh)
        history = load_trajectory(str(tmp_path))
        genuine = [h for h in history if genuine_measurement(h)]
        assert genuine, "the trajectory lost its genuine record"
        # the genuine last-good record passes against its own trajectory
        v = regression_verdict(genuine[-1], history)
        assert v["status"] in ("ok", "improved")
        # a synthetic 20% throughput drop is flagged
        drop = dict(genuine[-1], value=genuine[-1]["value"] * 0.8)
        assert regression_verdict(drop, history)["status"] == "regression"

    def test_load_trajectory_doctored_dir(self, tmp_path):
        root = str(tmp_path)
        with open(os.path.join(root, "BENCH_r01.json"), "w") as fh:
            json.dump({"n": 1, "rc": 0, "parsed": _rec(100.0)}, fh)
        with open(os.path.join(root, "BENCH_r02.json"), "w") as fh:
            json.dump({"n": 2, "rc": 1, "parsed": None}, fh)
        with open(os.path.join(root, "BENCH_LAST_GOOD.json"), "w") as fh:
            json.dump(_rec(104.0), fh)
        history = load_trajectory(root)
        assert [h.get("value") for h in history] == [100.0, 104.0]

    def test_regress_cli_exit_codes(self, tmp_path):
        root = str(tmp_path)
        with open(os.path.join(root, "BENCH_r01.json"), "w") as fh:
            json.dump({"n": 1, "rc": 0, "parsed": _rec(100.0)}, fh)
        with open(os.path.join(root, "BENCH_LAST_GOOD.json"), "w") as fh:
            json.dump(_rec(100.0), fh)

        def run(rec):
            path = os.path.join(root, "fresh.json")
            with open(path, "w") as fh:
                json.dump(rec, fh)
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "benchmarks", "regress.py"),
                 path, "--root", root],
                capture_output=True, text=True, timeout=240,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            verdict = json.loads(r.stdout.strip().splitlines()[-1])
            return r.returncode, verdict["status"]

        assert run(_rec(101.0)) == (0, "ok")
        assert run(_rec(93.0)) == (1, "drift")
        assert run(_rec(80.0)) == (2, "regression")
        assert run(_rec(0.0, error="pool outage")) == (0, "excluded")

    def test_analyze_rule_fires_on_bad_verdicts(self):
        from pytorch_distributedtraining_tpu.analyze import (
            AnalysisContext,
            Severity,
            run_rules,
        )

        # 5-point history -> MAD 1 -> ~5.2% noise band, so 7% is a drift
        history = [_rec(v) for v in (98.0, 100.0, 102.0, 100.0, 99.0)]
        regression_verdict(_rec(80.0), history)   # regression
        regression_verdict(_rec(93.0), history)   # drift
        regression_verdict(_rec(101.0), history)  # ok -> no finding
        report = run_rules(
            AnalysisContext(), planes=("runtime",), ignore=frozenset()
        )
        hits = report.by_rule("bench-regression")
        assert {f.severity for f in hits} == {Severity.ERROR, Severity.WARN}
        assert all("images_per_sec" in f.message for f in hits)
        # quiet once the verdicts are cleared (the autouse fixture's
        # contract with the rest of the suite)
        fleet.reset_runtime_stats()
        report = run_rules(
            AnalysisContext(), planes=("runtime",), ignore=frozenset()
        )
        assert not report.by_rule("bench-regression")
