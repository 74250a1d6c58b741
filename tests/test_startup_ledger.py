"""The start-up ledger of ``observe.trace``: spans of category ``compile`` and
``startup`` and jax's own compile events are kept with ``GRAFT_TELEMETRY``
unset, by program name and nested where they happened; the warm dispatch
path keeps nothing; ``startup_report`` partitions origin -> ``until``.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import losses, optim
from pytorch_distributedtraining_tpu.data import DataLoader, TensorDataset
from pytorch_distributedtraining_tpu.data.prefetch import DevicePrefetcher
from pytorch_distributedtraining_tpu.models import Net
from pytorch_distributedtraining_tpu.observe import goodput, trace
from pytorch_distributedtraining_tpu.parallel import (
    DDP, TrainStep, create_train_state,
)
from pytorch_distributedtraining_tpu.runtime.mesh import (
    MeshSpec, batch_spec, make_mesh,
)
from pytorch_distributedtraining_tpu.stoke import Stoke, StokeOptimizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND = ("compile.cache_read", "compile.xla")


@pytest.fixture
def ledger():
    """The process's ledger, emptied: the first ``LEDGER_CAPACITY`` records
    are kept, and earlier tests of this worker have compiled."""
    tracer = trace.get_tracer()
    was, tracer.enabled = tracer.enabled, False
    tracer.clear_startup()
    yield tracer
    tracer.clear_startup()
    tracer.enabled = was


def _batch(n=8):
    return (
        np.zeros((n, 8, 8, 3), np.float32), np.zeros((n, 16, 16, 3), np.float32)
    )


def _net_step():
    model = Net(upscale_factor=2)
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-3)
    state, shardings = create_train_state(
        init_fn=lambda rng: (
            model.init(rng, jnp.zeros((1, 8, 8, 3)))["params"], {}
        ),
        tx=tx, mesh=mesh, policy=DDP(),
    )

    def loss_fn(params, batch, rng, model_state):
        return losses.mse_loss(
            model.apply({"params": params}, batch[0]), batch[1]
        ), {}

    step = TrainStep(
        loss_fn, tx, mesh, DDP(), state_shardings=shardings, donate=False
    )
    return mesh, state, step


def _inside(rec, parent):
    return (
        rec["tid"] == parent["tid"] and rec["t0"] >= parent["t0"]
        and rec["t0"] + rec["dur"] <= parent["t0"] + parent["dur"] + 1e-3
    )


# -- what is kept, and where ---------------------------------------------------------


def test_cold_dispatch_and_its_compile_events_are_kept_with_telemetry_off(ledger):
    mesh, state, step = _net_step()
    with mesh:
        step(state, _batch())
    assert trace.records() == []  # the ring is off
    recs = ledger.startup_records()
    cold = next(r for r in recs if r["name"] == "TrainStep.compile+dispatch")
    assert cold["cat"] == "compile" and cold["attrs"]["step"] == 0
    inside = [
        r for r in recs if r["name"] in trace.COMPILE_EVENTS and _inside(r, cold)
    ]
    kinds = {r["name"] for r in inside}
    assert {"compile.trace", "compile.lower"} <= kinds and kinds & set(BACKEND)
    for r in inside:
        assert r["attrs"]["fun_name"], r
        assert r["attrs"]["program"] == "TrainStep.compile+dispatch"
        assert r["attrs"]["step"] == 0
        assert r["depth"] > cold["depth"]
    assert any("_step" in r["attrs"]["fun_name"] for r in inside)
    # the state's build is a phase of its own, with init's events inside it
    create = next(r for r in recs if r["name"] == "state.create")
    assert create["cat"] == "startup"
    assert any(
        r["name"] in BACKEND and _inside(r, create) for r in recs
    )


def test_a_thousand_warm_dispatches_keep_nothing(ledger, monkeypatch):
    class Owner:
        pass

    opened = []
    real = trace._LiveSpan.__init__
    monkeypatch.setattr(
        trace._LiveSpan, "__init__",
        lambda self, *a: (opened.append(a[1]), real(self, *a))[1],
    )
    owner = Owner()
    with trace.dispatch_span(owner, "TrainStep"):
        pass
    assert opened == ["TrainStep.compile+dispatch"]
    before = len(ledger.startup_records())
    for _ in range(1000):
        span = trace.dispatch_span(owner, "TrainStep")
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass
    assert len(opened) == 1
    assert len(ledger.startup_records()) == before
    assert trace.records() == []
    assert ledger.steady_at is not None  # stamped once, at the second


def test_a_jitted_function_inside_a_jitted_function_counts_once(ledger):
    @jax.jit
    def inner(x):
        time.sleep(0.05)  # runs while tracing
        return x * 2

    @jax.jit
    def outer(x):
        time.sleep(0.05)
        return inner(x) + 1

    t0 = time.perf_counter()
    outer(jnp.ones((3,)))
    t1 = time.perf_counter()
    traces = [
        r for r in ledger.startup_records() if r["name"] == "compile.trace"
    ]
    by_fun = {r["attrs"]["fun_name"]: r for r in traces}
    assert _inside(by_fun["inner"], by_fun["outer"])
    assert by_fun["inner"]["depth"] == by_fun["outer"]["depth"] + 1
    report = trace.startup_report(until=t1)
    summed = sum(r["dur"] for r in traces)
    assert report["trace_s"] == pytest.approx(by_fun["outer"]["dur"], abs=1e-3)
    assert report["trace_s"] < summed - 0.04
    assert report["trace_s"] <= t1 - t0
    cost = {
        (c["event"], c["fun_name"]): c for c in report["costliest"]
    }
    assert cost["compile.trace", "outer"]["self_seconds"] == pytest.approx(
        by_fun["outer"]["dur"] - by_fun["inner"]["dur"], abs=1e-3
    )


def test_a_cache_hit_reads_cache_read_and_a_miss_xla(tmp_path):
    code = (
        "import json, sys\n"
        "import jax, jax.numpy as jnp\n"
        "from pytorch_distributedtraining_tpu.observe import trace\n"
        "jax.config.update('jax_compilation_cache_dir', sys.argv[1])\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "f = jax.jit(lambda x: jnp.tanh(x) * 3 + 1)\n"
        "with trace.span('first', 'startup'):\n"
        "    f(jnp.ones((5,)))\n"
        "jax.clear_caches()\n"
        "with trace.span('second', 'startup'):\n"
        "    f(jnp.ones((5,)))\n"
        "print(json.dumps([[r['name'], r['attrs'].get('program')]\n"
        "    for r in trace.startup_records()\n"
        "    if '<lambda>' in r['attrs'].get('fun_name', '')]))\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    found = json.loads(done.stdout.strip().splitlines()[-1])
    assert ["compile.xla", "first"] in found
    assert ["compile.cache_read", "second"] in found
    assert ["compile.cache_read", "first"] not in found
    assert ["compile.xla", "second"] not in found


def test_a_shape_change_after_the_warm_point_carries_the_step(ledger):
    mesh, state, step = _net_step()
    with mesh:
        for _ in range(3):
            state, _ = step(state, _batch())
        warm = ledger.steady_at
        assert warm is not None
        state, _ = step(state, _batch(4))  # the fourth dispatch recompiles
    late = [
        r for r in ledger.startup_records()
        if r["name"] in trace.COMPILE_EVENTS and r["t0"] >= warm
    ]
    assert {r["name"] for r in late} >= {"compile.trace", "compile.lower"}
    assert all(
        r["attrs"]["program"] == "TrainStep" and r["attrs"]["step"] == 3
        for r in late
    ), late
    report = trace.startup_report()
    assert report["end"] == warm
    assert report["after_end_count"] == len(late)
    assert {(a["program"], a["step"]) for a in report["after_end"]} == {
        ("TrainStep", 3)
    }
    assert "compile events since" in trace.describe_startup(report)


def test_a_facade_program_that_recompiles_carries_the_optimizer_step(ledger):
    stoke = Stoke(
        model=Net(upscale_factor=2),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3},
        ),
        loss=losses.mse_loss, fuse_eager_step=False,
        mesh=make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1]),
    )

    def batch_of(n):
        loss = stoke.loss(stoke.model(_batch(n)[0]), _batch(n)[1])
        stoke.backward(loss=loss)
        stoke.step()

    batch_of(8)
    batch_of(8)
    warm = time.perf_counter()
    batch_of(4)  # the third optimizer step: every program sees a new shape
    jax.block_until_ready(stoke.state)
    late = [
        r for r in ledger.startup_records()
        if r["name"] in BACKEND and r["t0"] >= warm
    ]
    assert late
    assert {(r["attrs"].get("program"), r["attrs"].get("step")) for r in late} == {
        ("Stoke", 2)
    }, late


def test_trace_imports_without_jax_and_listens_only_once_a_span_opens():
    code = (
        "import sys\n"
        "from pytorch_distributedtraining_tpu.observe import trace\n"
        "assert 'jax' not in sys.modules\n"
        "assert not trace._LISTENING and trace.startup_records() == []\n"
        "with trace.span('cold', 'compile'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules and not trace._LISTENING\n"
        "assert trace.startup_records() == []\n"
        "import jax.monitoring\n"
        "from jax._src import monitoring\n"
        "assert not trace._LISTENING\n"
        "n = len(monitoring.get_event_duration_listeners())\n"
        "with trace.span('warm', 'step'):\n"
        "    pass\n"
        "assert trace._LISTENING\n"
        "assert len(monitoring.get_event_duration_listeners()) == n + 1\n"
        "with trace.span('cold', 'compile'):\n"
        "    pass\n"
        "assert len(monitoring.get_event_duration_listeners()) == n + 1\n"
        "assert [r['name'] for r in trace.startup_records()] == ['cold']\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_bounded_list_keeps_the_first_and_counts_the_rest():
    tracer = trace.Tracer()
    extra = 88
    for i in range(trace.LEDGER_CAPACITY + extra):
        tracer.add_span(f"s{i}", "startup", float(i), 0.5)
    tracer.add_span("warm", "step", 0.0, 0.5)  # not the ledger's
    kept = tracer.startup_records()
    assert len(kept) == trace.LEDGER_CAPACITY
    assert tracer.ledger_dropped == extra
    assert kept[0]["name"] == "s0"
    assert kept[-1]["name"] == f"s{trace.LEDGER_CAPACITY - 1}"
    assert tracer.records() == []  # the ring is off
    tracer.clear_startup()
    assert tracer.startup_records() == [] and tracer.ledger_dropped == 0


def test_a_span_timed_from_outside_goes_over_what_it_holds():
    tracer = trace.Tracer()
    tracer.add_span("before", "startup", 0.0, 1.0)
    tracer.add_span("child", "compile", 2.0, 1.0)
    tracer.add_span("grandchild", "compile", 2.2, 0.1, depth=1)
    tracer.add_span("parent", "startup", 1.5, 3.0)
    depth = {r["name"]: r["depth"] for r in tracer.startup_records()}
    assert depth == {"before": 0, "child": 1, "grandchild": 2, "parent": 0}


# -- the report ----------------------------------------------------------------------


def _rec(name, t0, dur, tid=1, cat="startup", **attrs):
    return {"name": name, "cat": cat, "t0": t0, "dur": dur, "tid": tid,
            "depth": 0, "attrs": attrs}


def _event(kind, fun, t0, dur, tid=1, **attrs):
    return _rec("compile." + kind, t0, dur, tid, "compile", fun_name=fun, **attrs)


@pytest.fixture
def made_up(monkeypatch):
    """A ledger written by hand: origin 100 s, two threads."""
    tracer = trace.Tracer()
    tracer._ledger = [
        _rec("runtime.initialize", 100.0, 0.5, perf_counter=100.0,
             time_ns=5, process_age_s=2.0),
        _rec("mesh.make", 101.0, 1.0),
        _rec("state.create", 103.0, 4.0),
        _event("trace", "build", 103.5, 2.0),
        _event("trace", "kernel", 104.0, 0.5),  # inside build's trace
        _event("lower", "jit(build)", 105.5, 0.5),
        _event("cache_read", "jit(build)", 106.0, 0.5),
        _event("trace", "reference", 107.5, 1.0),  # under no program span
        _rec("TrainStep.compile+dispatch", 109.0, 3.0, cat="compile", step=0),
        _event("trace", "_step", 109.0, 1.0),
        _event("trace", "kernel", 109.2, 0.5),  # the name again
        _event("xla", "jit(_step)", 110.0, 1.5),
        _rec("prefetch.start", 107.2, 1.0, tid=2),
        _rec("loader.start_workers", 107.3, 0.6, tid=2),
        _event("trace", "generator", 107.4, 0.2, tid=2),
        _event("trace", "_step", 120.0, 1.0, program="TrainStep", step=7),
    ]
    monkeypatch.setattr(trace, "_TRACER", tracer)
    return tracer


def test_report_phases_gaps_and_split_add_up_exactly(made_up):
    report = trace.startup_report(until=113.0)
    assert report["origin"] == 98.0  # the process's start
    assert report["seconds"] == 15.0
    assert [p["name"] for p in report["phases"]] == [
        "runtime.initialize", "mesh.make", "state.create",
        "TrainStep.compile+dispatch",
    ]
    total = sum(p["seconds"] for p in report["phases"]) + sum(
        g["seconds"] for g in report["gaps"]
    )
    assert total == pytest.approx(15.0, abs=1e-9)
    assert sum(report["split"].values()) == pytest.approx(15.0, abs=1e-9)
    names = [(g["after"], g["before"]) for g in report["gaps"]]
    assert names == [
        ("process start", "runtime.initialize"),
        ("runtime.initialize", "mesh.make"), ("mesh.make", "state.create"),
        ("state.create", "TrainStep.compile+dispatch"),
        ("TrainStep.compile+dispatch", "end"),
    ]
    gap = report["gaps"][3]  # 107 .. 109: the reference's trace, the feeder
    assert gap["seconds"] == pytest.approx(2.0)
    assert gap["compile_seconds"] == pytest.approx(1.0)
    assert gap["background"] == ["prefetch.start"]
    assert gap["background_seconds"] == pytest.approx(1.0)
    assert report["outside_program_s"] == pytest.approx(2 + 0.5 + 1 + 2 - 1 + 1)


def test_report_unions_self_seconds_and_counts(made_up):
    report = trace.startup_report(until=113.0)
    # build 2.0 (kernel inside it) + reference 1.0 + _step 1.0 (kernel
    # inside it) + the feeder thread's 0.2: a sum would read 5.2
    assert report["trace_s"] == pytest.approx(4.2)
    assert report["lower_s"] == pytest.approx(0.5)
    assert report["cache_read_s"] == pytest.approx(0.5)
    assert report["xla_s"] == pytest.approx(1.5)
    cost = {(c["event"], c["fun_name"]): c for c in report["costliest"]}
    assert cost["compile.trace", "build"]["self_seconds"] == pytest.approx(1.5)
    assert cost["compile.trace", "kernel"]["count"] == 2
    assert cost["compile.trace", "kernel"]["self_seconds"] == pytest.approx(1.0)
    state = report["by_name"]["state.create"]
    assert state["seconds"] == pytest.approx(4.0)
    assert state["compile_seconds"] == pytest.approx(3.0)
    assert state["self_seconds"] == pytest.approx(1.0)
    cold = report["phases"][-1]
    assert cold["self_seconds"] == pytest.approx(0.5)
    assert cold["compile_seconds"] == pytest.approx(2.5)
    assert report["split"]["compile_s"] == pytest.approx(3.0 + 1.0 + 2.5)
    # the other thread's spans: the prefetcher's start holds the loader's
    assert [b["name"] for b in report["background"]] == ["prefetch.start"]
    assert report["by_name"]["loader.start_workers"]["seconds"] == (
        pytest.approx(0.6)
    )


def test_report_lists_what_compiled_after_the_end_by_step(made_up):
    report = trace.startup_report(until=113.0)
    assert report["after_end_count"] == 1
    late = report["after_end"][0]
    assert (late["event"], late["fun_name"]) == ("compile.trace", "_step")
    assert (late["program"], late["step"]) == ("TrainStep", 7)
    assert late["at"] == pytest.approx(22.0)
    # an earlier end cuts the cold dispatch short and moves it all along
    early = trace.startup_report(until=110.0)
    assert early["seconds"] == 12.0
    assert early["phases"][-1]["seconds"] == pytest.approx(1.0)
    assert early["xla_s"] == 0.0 and early["after_end_count"] == 2


def test_report_without_an_origin_span_starts_at_the_first_record(monkeypatch):
    tracer = trace.Tracer()
    tracer._ledger = [_rec("mesh.make", 50.0, 1.0), _rec("state.create", 52.0, 1.0)]
    tracer.steady_at = 54.0
    monkeypatch.setattr(trace, "_TRACER", tracer)
    report = trace.startup_report()
    assert (report["origin"], report["end"]) == (50.0, 54.0)
    assert [(g["after"], g["before"]) for g in report["gaps"]] == [
        ("mesh.make", "state.create"), ("state.create", "end"),
    ]
    assert {"perf_counter", "time_ns"} <= set(report["clock"])
    line = trace.describe_startup(report)
    assert line.startswith("start-up 4.00 s") and "\n" not in line


# -- the phases, where the work happens ----------------------------------------------


@pytest.fixture(scope="module")
def program_ledger():
    """The ledger after a tiny program's start: mesh, state, a facade with
    its first optimizer step, a two-worker loader behind the device
    prefetcher."""
    tracer = trace.get_tracer()
    was, tracer.enabled = tracer.enabled, False
    tracer.clear_startup()
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    stoke = Stoke(
        model=Net(upscale_factor=2),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3},
        ),
        loss=losses.mse_loss, mesh=mesh,
    )
    xs, ys = _batch(16)
    loader = DataLoader(TensorDataset(xs, ys), batch_size=4, num_workers=2)
    source = DevicePrefetcher(
        loader._make_iter(loader._begin_epoch(), to_device=False), mesh,
        batch_spec(mesh),
    )
    for x, y in source:
        loss = stoke.loss(stoke.model(x), y)
        stoke.backward(loss=loss)
        stoke.step()
    jax.block_until_ready(stoke.state)
    records = tracer.startup_records()
    report = trace.startup_report(until=time.perf_counter())
    tracer.clear_startup()
    tracer.enabled = was
    return records, report


@pytest.mark.parametrize("name,category,attr", [
    ("mesh.make", "startup", "dp"),
    ("facade.construct", "startup", "policy"),
    ("facade.init_state", "startup", None),
    ("state.create", "startup", "policy"),
    ("facade.program.compile+dispatch", "compile", "program"),
    ("loader.start_workers", "startup", "workers"),
    ("prefetch.start", "startup", "depth"),
])
def test_each_phase_is_a_record_of_the_ledger(program_ledger, name, category, attr):
    records, report = program_ledger
    found = [r for r in records if r["name"] == name]
    assert found, sorted({r["name"] for r in records})
    assert all(r["cat"] == category for r in found)
    if attr is not None:
        assert all(attr in r["attrs"] for r in found)
    assert report["by_name"][name]["count"] == len(found)


def test_the_phases_nest_and_the_feeder_is_background(program_ledger):
    records, report = program_ledger
    by = {r["name"]: r for r in records}
    assert _inside(by["state.create"], by["facade.init_state"])
    assert _inside(by["loader.start_workers"], by["prefetch.start"])
    assert by["loader.start_workers"]["attrs"]["context"] == "thread"
    assert by["prefetch.start"]["tid"] != threading.get_ident()
    assert "prefetch.start" in {b["name"] for b in report["background"]}
    assert "prefetch.start" not in {p["name"] for p in report["phases"]}
    programs = {
        r["attrs"]["program"] for r in records
        if r["name"] == "facade.program.compile+dispatch"
    }
    assert programs  # each facade program's first call, and only its first
    assert report["by_name"]["facade.program.compile+dispatch"]["count"] == (
        len(programs)
    )


def test_clock_anchor_aligns_the_ledger_with_a_wall_clock():
    anchor = trace.clock_anchor()
    assert abs(anchor["perf_counter"] - time.perf_counter()) < 1.0
    assert abs(anchor["time_ns"] - time.time_ns()) < 1e9
    if os.path.exists("/proc/self/stat"):
        assert 0.0 < anchor["process_age_s"] < 24 * 3600


def test_goodput_bills_startup_with_compile():
    assert set(goodput.CATEGORY_BUCKET) | {"membership", "other"} >= set(
        trace.CATEGORIES
    )
    recs = [
        _rec("state.create", 0.0, 2.0), _rec("x.dispatch", 2.0, 1.0, cat="step"),
    ]
    ledger = goodput.GoodputLedger.from_records(recs, 0.0, 3.0)
    assert ledger.buckets["compile"] == pytest.approx(2.0)
    assert ledger.buckets["productive"] == pytest.approx(1.0)
    assert ledger.buckets["other"] == pytest.approx(0.0)
