"""The program names its own work: every span of ``observe.trace`` is a
``graft/<name>`` annotation in any profile, with ``GRAFT_TELEMETRY`` unset;
the ring holds the same names with it set; the compiled programs carry the
scopes a trace reader joins on, under the module names ``chipbench/``
matches; the facade's count of dispatched programs agrees with the
benchmark's outside-in one.
"""

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import losses, optim
from pytorch_distributedtraining_tpu.data import DataLoader, TensorDataset
from pytorch_distributedtraining_tpu.models import (
    GPT2, GPT2Config, Net, SwinIR, cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.observe import profiling, trace
from pytorch_distributedtraining_tpu.parallel import (
    DDP, TrainStep, create_train_state,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributedtraining_tpu.stoke import Stoke, StokeOptimizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = trace.ANNOTATION_PREFIX

# the facade's spans of the reference-shaped loop, split path: parent -> the
# spans that must be nested in it on the same thread
FACADE_NESTING = {
    "facade.model": (),
    "facade.loss": (),
    "facade.backward": ("facade.backward.grad", "facade.note_loss"),
    "facade.step": (
        "facade.step.flush_micros", "facade.step.materialize_lazies",
        "facade.step.lr", "facade.step.apply",
    ),
    "facade.detach_and_sync_loss": (),
}
FUSED_NESTING = {
    "facade.step": ("facade.step.fused",),
    "facade.fused_step": ("TrainStep.dispatch", "facade.note_loss"),
}


# -- tiny programs ---------------------------------------------------------------


def _pairs(n=8, seed=0):
    rng = np.random.default_rng(seed)
    hr = rng.random((n, 16, 16, 3)).astype(np.float32)
    return hr.reshape(n, 8, 2, 8, 2, 3).mean(axis=(2, 4)), hr


def _train_step(model, sample, loss_fn, accum=1):
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=1e-3, clip_grad_norm=1.0)
    state, shardings = create_train_state(
        init_fn=lambda rng: (model.init(rng, sample)["params"], {}),
        tx=tx, mesh=mesh, policy=DDP(),
    )
    step = TrainStep(
        loss_fn, tx, mesh, DDP(), grad_accum_steps=accum,
        state_shardings=shardings, donate=False,
    )
    return mesh, state, step


def _net_step():
    model = Net(upscale_factor=2)

    def loss_fn(params, batch, rng, model_state):
        return losses.mse_loss(
            model.apply({"params": params}, batch[0]), batch[1]
        ), {}

    return _train_step(model, jnp.zeros((1, 8, 8, 3)), loss_fn)


def _stoke(fuse):
    # the fused eager window is taken only where no loss is read inside an
    # accumulation window (the reference loop reads one per batch)
    accum = 1 if fuse else 2
    return Stoke(
        model=Net(upscale_factor=2),
        optimizer=StokeOptimizer(
            optimizer="AdamW",
            optimizer_kwargs={"lr": 1e-3, "weight_decay": 1e-4},
        ),
        loss=losses.mse_loss, grad_accum_steps=accum, fuse_eager_step=fuse,
    )


def _facade_loop(stoke, batches=4):
    x, y = _pairs()
    for _ in range(batches):
        loss = stoke.loss(stoke.model(x), y)
        stoke.backward(loss=loss)
        stoke.step()
        stoke.detach_and_sync_loss(loss=loss)


def _loader_epoch(workers=2):
    xs, ys = _pairs(n=16)
    loader = DataLoader(
        TensorDataset(xs, ys), batch_size=4, num_workers=workers
    )
    assert len(list(loader)) == 4


def _everything():
    """Two steps of a tiny TrainStep, a tiny facade loop on each eager
    path, two fused steps, and a two-worker loader's epoch."""
    mesh, state, step = _net_step()
    with mesh:
        for _ in range(3):
            state, _ = step(state, _pairs())
    _facade_loop(_stoke(fuse=False))
    fused = _stoke(fuse=True)
    _facade_loop(fused)
    fused.fused_step(*_pairs())
    fused.fused_step(*_pairs())
    jax.block_until_ready(fused.state)
    _loader_epoch()


# -- spans in a profile, telemetry off ---------------------------------------------


@pytest.fixture(scope="module")
def profile_spans(tmp_path_factory):
    """``{thread line: [(name, start_ns, end_ns)]}`` of the ``graft/`` events
    of a profile taken around ``_everything()`` with telemetry off."""
    from jax.profiler import ProfileData

    assert not os.environ.get("GRAFT_TELEMETRY")
    was, trace.get_tracer().enabled = trace.enabled(), False
    logdir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        _everything()
    finally:
        jax.profiler.stop_trace()
        trace.get_tracer().enabled = was
    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    by_thread = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for number, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    # a line is a thread; Python's threads share a name
                    by_thread.setdefault((number, line.name), []).append((
                        ev.name[len(PREFIX):], ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats),
                    ))
    return by_thread


def _names(by_thread):
    return {name for events in by_thread.values() for name, *_ in events}


def _thread_of(by_thread, name):
    threads = {
        thread for thread, events in by_thread.items()
        if any(n == name for n, *_ in events)
    }
    assert len(threads) == 1, (name, threads)
    return threads.pop()


def test_profile_holds_the_dispatch_span(profile_spans):
    main = _thread_of(profile_spans, "TrainStep.dispatch")
    dispatches = [e for e in profile_spans[main] if e[0] == "TrainStep.dispatch"]
    # three calls of the TrainStep (the first is compile+dispatch), two of
    # the one behind fused_step
    assert len(dispatches) >= 3
    assert "TrainStep.compile+dispatch" in _names(profile_spans)
    # the step number is an argument, counted on the host
    steps = [e[3]["step"] for e in dispatches]
    assert steps[:2] == [1, 2]


@pytest.mark.parametrize("parent", sorted({**FACADE_NESTING, **FUSED_NESTING}))
def test_profile_holds_the_facade_spans_nested(profile_spans, parent):
    main = _thread_of(profile_spans, "facade.backward")
    events = profile_spans[main]
    parents = [e for e in events if e[0] == parent]
    assert parents, parent
    for child in (*FACADE_NESTING.get(parent, ()), *FUSED_NESTING.get(parent, ())):
        inside = [
            e for e in events if e[0] == child and any(
                p[1] <= e[1] and e[2] <= p[2] for p in parents
            )
        ]
        assert inside, f"no {child} nested in a {parent}"
    assert all("step" in e[3] for e in parents)


def test_profile_holds_the_loader_spans_on_the_feeder(profile_spans):
    feeder = _thread_of(profile_spans, "loader.collect")
    assert _thread_of(profile_spans, "loader.collate") == feeder
    assert feeder != _thread_of(profile_spans, "facade.backward")
    waits = [
        e for e in profile_spans[_thread_of(profile_spans, "input.wait")]
        if e[0] == "input.wait"
    ]
    assert len(waits) >= 4 and "queued" in waits[0][3]
    collates = [e for e in profile_spans[feeder] if e[0] == "loader.collate"]
    assert len(collates) == 4
    assert all(float(e[3]["worker_s"]) > 0 for e in collates)


# -- the ring, telemetry on -------------------------------------------------------


@pytest.fixture(scope="module")
def ring_names():
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        _everything()
        return {r["name"] for r in trace.records()}
    finally:
        tracer.enabled = was
        trace.clear()


@pytest.mark.parametrize("family", ["step", "facade", "loader"])
def test_ring_holds_the_same_names(profile_spans, ring_names, family):
    prefixes = {
        "step": ("TrainStep.",), "facade": ("facade.",),
        "loader": ("loader.", "input."),
    }[family]
    in_profile = {n for n in _names(profile_spans) if n.startswith(prefixes)}
    assert in_profile and in_profile <= ring_names


@pytest.mark.parametrize("workers,name", [
    (0, "input.fetch"), (2, "loader.collect"), (2, "loader.collate"),
])
def test_ring_records_number_the_batches(workers, name):
    """A loader's spans carry the batch's number, and the feeder's the
    workers' own fetch seconds."""
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    try:
        _loader_epoch(workers)
        attrs = [r["attrs"] for r in trace.records() if r["name"] == name]
    finally:
        tracer.enabled = was
        trace.clear()
    assert [a["n"] for a in attrs] == [0, 1, 2, 3]
    if workers:
        assert all(a["worker_s"] > 0 for a in attrs)


# -- names inside the compiled programs -------------------------------------------


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _gpt2_text():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)

    def loss_fn(params, batch, rng, model_state):
        logits = model.apply({"params": params}, batch[0])
        return cross_entropy_loss(logits, batch[1]), {}

    mesh, state, step = _train_step(
        model, jnp.zeros((1, 8), jnp.int32), loss_fn
    )
    tokens = jnp.zeros((2, 16), jnp.int32)
    return step.compiled_text(state, (tokens, tokens))


def _swinir_text():
    model = SwinIR(
        upscale=2, embed_dim=12, depths=(2,), num_heads=(2,), window_size=4
    )

    def loss_fn(params, batch, rng, model_state):
        out = model.apply({"params": params}, batch[0])
        return losses.feat_loss(out, batch[1]), {}

    mesh, state, step = _train_step(
        model, jnp.zeros((1, 8, 8, 3)), loss_fn, accum=2
    )
    batch = (jnp.zeros((4, 8, 8, 3)), jnp.zeros((4, 16, 16, 3)))
    return step.compiled_text(state, batch)


@pytest.fixture(scope="module")
def compiled_texts():
    return {"gpt2": _gpt2_text(), "swinir": _swinir_text()}


@pytest.mark.parametrize("model,scopes", [
    ("gpt2", ("optimizer", "clip", "adamw", "loss", "attention", "embed",
              "head")),
    ("swinir", ("optimizer", "clip", "adamw", "feat_loss", "attention",
                "window_layout", "upsample", "grad_accum")),
])
def test_compiled_text_names_the_work(compiled_texts, model, scopes):
    text = compiled_texts[model]
    assert re.search(r"^HloModule jit__step\b", text, re.M)
    paths = {tuple(re.split(r"[/()]+", n)) for n in _op_names(text)}
    for scope in scopes:
        assert any(scope in path for path in paths), scope
    # backward and forward are autodiff's own names
    assert any("transpose" in p and "attention" in p for p in paths)


@pytest.mark.parametrize("model", ["gpt2", "swinir"])
def test_the_update_is_all_under_optimizer(compiled_texts, model):
    """Not one instruction of the clip or of AdamW is outside the
    ``optimizer`` scope, and nothing but bookkeeping (the step counter, the
    rng fold, the loss's mean) is left under the bare ``jit(_step)``."""
    names = _op_names(compiled_texts[model])
    for name in names:
        if re.search(r"(^|/)(clip|adamw)(/|$)", name):
            assert "/optimizer/" in name, name
    bare = {
        n.split("/", 1)[1] for n in names
        if re.fullmatch(r"jit\(_step\)/[\w\-]+", n)
    }
    assert not bare & {"sqrt", "rsqrt", "integer_pow", "pow"}, bare


@pytest.mark.parametrize("program,module", [
    ("_jit_loss_grad", "jit_loss_grad"), ("_jit_apply", "jit_apply_updates"),
    ("_jit_eager_step", "jit_eager_step"),
])
def test_facade_programs_keep_their_names(program, module):
    """What ``chipbench/stoke_common.py`` wraps and what the trace names."""
    stoke = _stoke(fuse=program == "_jit_eager_step")
    for attr in ("_build_jits", "_build_fused", "_fused"):
        assert hasattr(stoke, attr)
    _facade_loop(stoke, batches=2)
    assert callable(getattr(stoke, program))
    assert stoke.programs[program] >= 1
    modules = {
        re.search(r"^HloModule (\S+?),", t, re.M).group(1)
        for t in profiling.program_texts()
    }
    assert module in modules


@pytest.mark.parametrize("inside_mesh", [True, False])
def test_program_texts_find_the_train_step_without_tracing_it_anew(
    inside_mesh,
):
    """The text comes from the signature and the mesh context of the
    first call, so the lowering finds the traced program (on the chip a
    second trace of the SwinIR step is 19 s)."""
    import contextlib

    model = Net(upscale_factor=2)
    traces = []

    def loss_fn(params, batch, rng, model_state):
        traces.append(1)
        return losses.mse_loss(
            model.apply({"params": params}, batch[0]), batch[1]
        ), {}

    mesh, state, step = _train_step(model, jnp.zeros((1, 8, 8, 3)), loss_fn)
    with mesh if inside_mesh else contextlib.nullcontext():
        step(state, _pairs())
    traced = len(traces)
    texts = [
        t for t in profiling.program_texts()
        if re.search(r"^HloModule jit__step\b", t, re.M)
    ]
    assert texts and any("/optimizer/" in t for t in texts)
    assert len(traces) == traced


# -- counters ------------------------------------------------------------------------


@pytest.mark.parametrize("fuse", [False, True], ids=["split", "fused"])
def test_facade_counts_its_programs_as_the_benchmark_does(fuse):
    """The facade's own counts of dispatched programs (``_run`` keeps a
    program's signature at the first) equal the counts ``chipbench`` takes
    from outside on the same loop."""
    import types

    from chipbench.instruments import Spans
    from chipbench.stoke_common import FACADE_PROGRAMS, bench_stoke_class

    env = types.SimpleNamespace(calls={}, spans=Spans(False))
    cls = bench_stoke_class(Stoke, env, [])
    stoke = cls(
        model=Net(upscale_factor=2),
        optimizer=StokeOptimizer(
            optimizer="AdamW", optimizer_kwargs={"lr": 1e-3},
        ),
        loss=losses.mse_loss, grad_accum_steps=1 if fuse else 2,
        fuse_eager_step=fuse,
    )
    _facade_loop(stoke, batches=4)
    stoke.fused_step(*_pairs())
    for name in FACADE_PROGRAMS:
        assert stoke.programs.get(name, 0) == env.calls.get(name, 0), name
    assert stoke.programs["_jit_eager_step" if fuse else "_jit_apply"] > 0
    # the windows of the loop (four of one batch, or two of two) and one fused
    assert stoke._opt_steps == (5 if fuse else 3)


# -- the module itself ---------------------------------------------------------------


def test_trace_imports_without_jax_and_spans_are_null():
    code = (
        "import sys\n"
        "from pytorch_distributedtraining_tpu.observe import trace\n"
        "assert 'jax' not in sys.modules\n"
        "class Owner: pass\n"
        "o = Owner()\n"
        "for s in (trace.span('x', n=1), trace.dispatch_span(o, 'K'),\n"
        "          trace.bucket_dispatch_span(o, 'K', 8)):\n"
        "    assert s is trace._NULL_SPAN, s\n"
        "    with s as inner:\n"
        "        inner.set(a=1)\n"
        "assert o._telemetry_dispatches == 1\n"
        "assert 'jax' not in sys.modules\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]


def test_span_is_an_annotation_with_telemetry_off():
    tracer = trace.get_tracer()
    was, tracer.enabled = tracer.enabled, False
    try:
        span = trace.span("x", "step", step=3)
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span as inner:
            assert inner.set(a=1) is inner
        assert trace.records() == [] or was
    finally:
        tracer.enabled = was


def test_run_dir_follows_tmpdir(tmp_path, monkeypatch):
    import tempfile

    from pytorch_distributedtraining_tpu.observe.capture import (
        OnDemandProfiler,
    )

    monkeypatch.delenv("GRAFT_RUN_DIR", raising=False)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", None)
    run = trace.run_dir()
    assert run == os.path.join(str(tmp_path), "graft-runs", str(os.getpid()))
    assert os.path.isdir(run)
    assert OnDemandProfiler().trace_dir == os.path.join(run, "captures")
    monkeypatch.setenv("GRAFT_RUN_DIR", str(tmp_path / "named"))
    assert trace.run_dir() == str(tmp_path / "named")
