"""Unified telemetry: spans, goodput ledger, stragglers, flight recorder.

Covers the observability substrate end to end on the CPU mesh: span
nesting and ring truncation, the Chrome trace-event export round-trip
(including through ``benchmarks/trace_summary.py``), ledger bucket
accounting under injected faults, straggler flagging on a synthetic
skewed timing table, and the crash flight recorder naming the in-flight
span — the acceptance criteria of the telemetry PR.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from pytorch_distributedtraining_tpu.observe import goodput, trace
from pytorch_distributedtraining_tpu.observe.goodput import (
    GoodputLedger,
    StepLog,
    flag_stragglers,
    mfu,
    model_train_flops,
    peak_flops,
    read_step_logs,
    straggler_check,
)
from pytorch_distributedtraining_tpu.observe.trace import Tracer
from pytorch_distributedtraining_tpu.resilience.faults import (
    FaultPlan,
    InjectedFault,
    fault_point,
    install_plan,
)
from pytorch_distributedtraining_tpu.resilience.outage import OutageClass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def live_tracer(tmp_path, monkeypatch):
    """Enabled module tracer writing all run artifacts under tmp_path.

    The default tracer is process-global state — every test must leave it
    disabled and empty, and must not leave a fault plan installed.
    """
    monkeypatch.setenv("GRAFT_RUN_DIR", str(tmp_path))
    trace.clear()
    trace.enable(crash_handler=False)
    yield tmp_path
    trace.disable()
    trace.clear()
    install_plan(None)


# -- span recording ----------------------------------------------------


class TestSpans:
    def test_nesting_depth_and_order(self, live_tracer):
        with trace.span("outer", "step"):
            with trace.span("inner", "input"):
                time.sleep(0.002)
        recs = trace.records()
        by = {r["name"]: r for r in recs}
        assert by["outer"]["depth"] == 0
        assert by["inner"]["depth"] == 1
        # children close (and record) before their parent
        assert recs[0]["name"] == "inner"
        assert by["outer"]["dur"] >= by["inner"]["dur"]

    def test_ring_truncation_counts_drops(self):
        tr = Tracer(capacity=4)
        tr.enabled = True
        for i in range(10):
            tr.add_span(f"s{i}", "step", float(i), 0.5)
        recs = tr.records()
        assert len(recs) == 4
        assert tr.dropped == 6
        assert [r["name"] for r in recs] == ["s6", "s7", "s8", "s9"]

    def test_span_records_error_attr(self, live_tracer):
        with pytest.raises(ValueError):
            with trace.span("boom", "step"):
                raise ValueError("x")
        rec = trace.records()[-1]
        assert rec["attrs"]["error"] == "ValueError"

    def test_disabled_span_is_noop(self, live_tracer):
        trace.disable()
        with trace.span("ghost", "step"):
            pass
        trace.instant("ghost.event")
        assert trace.records() == []

    def test_dispatch_span_warm_transition(self, live_tracer):
        class Owner:
            pass

        o = Owner()
        with trace.dispatch_span(o, "train_step"):
            pass
        with trace.dispatch_span(o, "train_step"):
            pass
        recs = trace.records()
        assert recs[0]["name"] == "train_step.compile+dispatch"
        assert recs[0]["cat"] == "compile"
        assert recs[1]["name"] == "train_step.dispatch"
        assert recs[1]["cat"] == "step"

    def test_configure_from_env(self, live_tracer, monkeypatch):
        monkeypatch.setattr(trace, "install_crash_handler", lambda: None)
        assert trace.configure_from_env(
            {"GRAFT_TELEMETRY": "0", "GRAFT_TRACE": "/tmp/x"}
        ) is False
        assert not trace.enabled()
        # GRAFT_TRACE alone implies telemetry
        assert trace.configure_from_env({"GRAFT_TRACE": "/tmp/x"}) is True
        assert trace.enabled()


# -- Chrome trace-event export -----------------------------------------


class TestChromeExport:
    def test_schema_round_trip(self, live_tracer, tmp_path):
        with trace.span("a", "step", n=1):
            with trace.span("b", "input"):
                time.sleep(0.001)
        trace.instant("fault.test", "fault", action="raise")
        p = trace.export_chrome_trace(str(tmp_path / "t.trace.json"))
        with open(p, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert {e["ph"] for e in evs} >= {"M", "X", "i"}
        xs = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
        # timestamps re-zeroed to the earliest record
        assert min(e["ts"] for e in evs if e["ph"] in "Xi") == 0.0
        pn = [e for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"]
        assert pn[0]["args"]["name"].startswith("graft-telemetry")
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert inst["name"] == "fault.test" and inst["s"] == "t"
        assert inst["args"]["action"] == "raise"

    def test_default_path_under_graft_trace(self, live_tracer, monkeypatch,
                                            tmp_path):
        monkeypatch.setenv("GRAFT_TRACE", str(tmp_path / "tr"))
        trace.instant("x")
        p = trace.export_chrome_trace()
        assert p == str(
            tmp_path / "tr" / f"telemetry-{os.getpid()}.trace.json"
        )
        assert os.path.exists(p)

    def test_trace_summary_rolls_up_telemetry(self, live_tracer, tmp_path):
        with trace.span("train.dispatch", "step"):
            time.sleep(0.002)
        trace.instant("fault.loader.stage", "fault")
        trace.export_chrome_trace(str(tmp_path / "x.trace.json"))
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "benchmarks", "trace_summary.py"),
             str(tmp_path)],
            capture_output=True, text=True,
            cwd=os.path.join(REPO, "benchmarks"), timeout=60,
        )
        assert out.returncode == 0, out.stderr
        rows = [json.loads(l) for l in out.stdout.splitlines() if l]
        head = rows[0]
        assert head["telemetry_lanes"] and head["total_span_ms"] > 0
        assert any(r.get("cat") == "step" for r in rows)
        assert any(r.get("instant") == "fault.loader.stage" for r in rows)


# -- goodput ledger under injected faults ------------------------------


class TestGoodputLedger:
    def test_buckets_sum_to_wall_under_faults(self, live_tracer):
        install_plan(FaultPlan.from_json({"faults": [
            {"site": "loader.stage", "action": "raise"},
            {"site": "train.preempt", "action": "raise",
             "message": "injected preemption"},
        ]}))
        t0 = time.perf_counter()
        with trace.span("train.dispatch", "step"):
            time.sleep(0.02)
        with trace.span("loader.stage", "input"):
            time.sleep(0.01)
            with pytest.raises(InjectedFault):
                fault_point("loader.stage")
        with pytest.raises(InjectedFault, match="injected preemption"):
            fault_point("train.preempt")
        t1 = time.perf_counter()

        recs = trace.records()
        instants = [r["name"] for r in recs if r.get("instant")]
        assert "fault.loader.stage" in instants
        assert "fault.train.preempt" in instants

        led = GoodputLedger.from_records(recs, t0, t1)
        assert led.events >= 2
        # `other` absorbs the unattributed remainder, so the breakdown
        # accounts for the whole window (bench acceptance bound is 5%)
        assert abs(sum(led.buckets.values()) - led.wall_s) < 1e-6
        assert led.buckets["productive"] >= 0.015
        assert led.buckets["input_wait"] >= 0.005
        assert 0.0 < led.goodput_fraction() < 1.0
        bd = led.time_breakdown()
        assert set(bd) == set(goodput.BUCKETS)

    def test_only_top_level_spans_counted(self, live_tracer):
        with trace.span("outer", "step"):
            with trace.span("inner", "input"):
                time.sleep(0.005)
        recs = trace.records()
        outer = next(r for r in recs if r["name"] == "outer")
        led = GoodputLedger.from_records(
            recs, outer["t0"], outer["t0"] + outer["dur"]
        )
        # the nested input span is inside productive time, not billed twice
        assert led.buckets["input_wait"] == 0.0
        assert led.buckets["productive"] > 0.0

    def test_mfu_and_peak_table(self, monkeypatch):
        assert peak_flops("tpu", "TPU v4") == 275e12
        monkeypatch.setenv("GRAFT_PEAK_FLOPS", "1e12")
        assert peak_flops("cpu") == 1e12
        monkeypatch.delenv("GRAFT_PEAK_FLOPS")
        # 1e9 FLOPs / 0.01 s = 1e11 FLOP/s over 2 cpu-peaks (2 * 100e9)
        assert abs(mfu(1e9, 0.01, n_devices=2, platform="cpu") - 0.5) < 1e-9
        assert mfu(0.0, 1.0) is None

    def test_swinir_flops_in_roofline_band(self):
        class FakeSwin:
            embed_dim = 60
            depths = (6, 6, 6, 6)
            mlp_ratio = 2.0
            window_size = 8
            upscale = 2
            img_size = 64

        f = model_train_flops(FakeSwin(), 8, (64, 64))
        per_img_gflops = f / 8 / 1e9
        # ~21 GFLOPs/image trained for SwinIR-S x2@64 (3x forward)
        assert 15.0 < per_img_gflops < 30.0

    def test_gpt2_flops_scale_with_batch(self):
        class Cfg:
            n_layer = 12
            n_embd = 768
            n_positions = 1024
            vocab_size = 50257

        f1 = model_train_flops(Cfg(), 1)
        f8 = model_train_flops(Cfg(), 8)
        assert f1 > 0 and abs(f8 / f1 - 8.0) < 1e-9


# -- straggler detection -----------------------------------------------


class TestStragglers:
    def test_flags_slow_rank_on_skewed_table(self):
        rep = flag_stragglers({
            0: [0.100] * 20, 1: [0.101] * 20,
            2: [0.099] * 20, 3: [0.250] * 20,
        })
        assert rep.stragglers == (3,)
        assert rep.outage_class is OutageClass.OUTAGE
        assert "rank 3" in rep.render()

    def test_fast_outlier_is_not_a_straggler(self):
        rep = flag_stragglers({
            0: [0.1] * 5, 1: [0.1] * 5, 2: [0.1] * 5, 3: [0.01] * 5,
        })
        assert rep.stragglers == ()
        assert rep.outage_class is None

    def test_below_min_ranks_never_flags(self):
        assert flag_stragglers({0: [0.1], 1: [9.9]}).stragglers == ()

    def test_step_log_roundtrip_and_check(self, tmp_path):
        for rank, dt in ((0, 0.1), (1, 0.1), (2, 0.4)):
            with StepLog(rank=rank, base=str(tmp_path),
                         flush_every=4) as log:
                for s in range(8):
                    log.record(s, dt)
        table = read_step_logs(str(tmp_path))
        assert set(table) == {0, 1, 2}
        assert len(table[0]) == 8
        rep = straggler_check(str(tmp_path))
        assert rep.stragglers == (2,)


# -- crash flight recorder ---------------------------------------------


class TestFlightRecorder:
    def test_flush_on_exception_names_in_flight_span(self, live_tracer,
                                                     tmp_path):
        path = str(tmp_path / "flightrec-77.json")
        with pytest.raises(RuntimeError):
            with trace.span("train.dispatch", "step", step=7):
                try:
                    raise RuntimeError("boom")
                except RuntimeError as e:
                    trace.flush_flight_record(
                        "unhandled-exception", exc=e, path=path
                    )
                    raise
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["reason"] == "unhandled-exception"
        assert doc["in_flight"][-1]["name"] == "train.dispatch"
        assert doc["exception"]["type"] == "RuntimeError"
        assert doc["exception"]["message"] == "boom"
        line = trace.describe_flight_record(doc)
        assert "train.dispatch" in line and "RuntimeError" in line

    def test_fault_trip_leaves_flight_record(self, live_tracer):
        install_plan(FaultPlan.from_json(
            {"faults": [{"site": "checkpoint.write"}]}
        ))
        with pytest.raises(InjectedFault):
            with trace.span("ckpt.write", "checkpoint"):
                fault_point("checkpoint.write")
        docs = trace.read_flight_records(str(live_tracer))
        assert docs
        doc = docs[-1]
        assert doc["reason"] == "fault:checkpoint.write"
        assert doc["in_flight"][-1]["name"] == "ckpt.write"
        assert any(
            r["name"] == "fault.checkpoint.write" for r in doc["recent"]
        )

    def test_between_spans_description(self, live_tracer):
        p = trace.flush_flight_record("manual", path=str(
            live_tracer / "flightrec-1.json"
        ))
        with open(p, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert "between spans" in trace.describe_flight_record(doc)

    def test_launcher_reports_and_consumes_records(self, live_tracer,
                                                   capsys):
        from pytorch_distributedtraining_tpu.runtime import launch

        install_plan(FaultPlan.from_json(
            {"faults": [{"site": "train.preempt"}]}
        ))
        with pytest.raises(InjectedFault):
            with trace.span("train.dispatch", "step"):
                fault_point("train.preempt")
        launch._report_flight_records(str(live_tracer))
        err = capsys.readouterr().err
        assert "flight record" in err
        assert "train.dispatch" in err and "fault:train.preempt" in err
        # consumed: the next generation reports only fresh deaths
        assert trace.read_flight_records(str(live_tracer)) == []

    def test_crash_handler_chains_and_is_idempotent(self, live_tracer,
                                                    monkeypatch):
        calls = []
        monkeypatch.setattr(sys, "excepthook",
                            lambda *a: calls.append(a))
        monkeypatch.setattr(trace, "_prev_excepthook", None)
        trace.install_crash_handler()
        hook = sys.excepthook
        trace.install_crash_handler()
        assert sys.excepthook is hook  # no double-chaining
        exc = ValueError("dead")
        hook(ValueError, exc, None)
        assert calls, "previous excepthook must still run"
        docs = trace.read_flight_records(str(live_tracer))
        assert any(d["reason"] == "unhandled-exception"
                   and d["exception"]["message"] == "dead" for d in docs)
