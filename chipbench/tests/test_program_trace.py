"""``program_trace`` on a hand-made trace and a small HLO text: the join of
an executed op to its scope, phase / component / matmul / purpose, the
partition of exposed collective time, the window of whole steps, the
completion lag, span self times; then every new reader in a traced rehearsal
on the CPU, and the manifest."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import program_trace as pt
from chipbench.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_program")

# the format of compiled.as_text() on the v5e (PR 24), cut to a few lines
HLO = """HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}

%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %convolution.1 = bf16[16,8]{1,0} convolution(%p0.1, %p0.1), dim_labels=bf_io->bf, metadata={op_name="jit(_step)/jvp(GPT2)/h_0/c_attn/dot_general" stack_frame_id=3}
}

%fused_computation.2 (p0.2: bf16[16,8]) -> bf16[16,8] {
  %p0.2 = bf16[16,8]{1,0} parameter(0)
  ROOT %exp.1 = bf16[16,8]{1,0} exponential(%p0.2), metadata={op_name="jit(_step)/jvp(GPT2)/h_0/attention/exp"}
}

%fused_computation.5 (p0.5: bf16[16,8], p1.5: bf16[16,8]) -> bf16[16,8] {
  %p0.5 = bf16[16,8]{1,0} parameter(0)
  %p1.5 = bf16[16,8]{1,0} parameter(1)
  %convolution.5 = bf16[16,8]{1,0} convolution(%p0.5, %p0.5), dim_labels=bf_io->bf
  ROOT %add.5 = bf16[16,8]{1,0} add(%convolution.5, %p1.5), metadata={op_name="jit(_step)/transpose(jvp(GPT2))/h_0/mlp_proj/dot_general"}
}

ENTRY %main.9 (arg0: bf16[8,8], arg1: bf16[16,8]) -> bf16[16,8] {
  %arg0 = bf16[8,8]{1,0} parameter(0), metadata={op_name="state.params['w']"}
  %arg1 = bf16[16,8]{1,0} parameter(1), metadata={op_name="batch[0]"}
  %copy-start.1 = (bf16[8,8]{1,0:S(1)}, bf16[8,8]{1,0}, u32[]{:S(2)}) copy-start(%arg0)
  %copy-done.1 = bf16[8,8]{1,0:S(1)} copy-done(%copy-start.1)
  %fusion.1 = bf16[16,8]{1,0} fusion(%arg1, %copy-done.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(GPT2)/h_0/c_attn/dot_general"}
  %fusion.2 = bf16[16,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/jvp(GPT2)/h_0/attention/exp"}
  %fusion.3 = bf16[16,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/transpose(jvp(GPT2))/h_0/attention/mul"}
  %fusion.4 = bf16[16,8]{1,0} fusion(%fusion.3), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_step)/transpose(jvp(GPT2))/checkpoint/rematted_computation/h_0/mlp_fc/dot_general"}
  %fusion.5 = bf16[16,8]{1,0} fusion(%fusion.4, %fusion.3), kind=kOutput, calls=%fused_computation.5
  %all-gather.1 = bf16[16,8]{1,0} all-gather(%fusion.1), dimensions={0}, metadata={op_name="jit(_step)/jvp(GPT2)/h_0/attention/reshape"}
  %collective-permute-start.1 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}, u32[], u32[]) collective-permute-start(%copy-done.1), source_target_pairs={{0,1}}, metadata={op_name="jit(_step)/jvp(GPT2)/h_0/c_attn/dot_general"}
  %collective-permute-done.1 = bf16[8,8]{1,0} collective-permute-done(%collective-permute-start.1), metadata={op_name="jit(_step)/jvp(GPT2)/h_0/c_attn/dot_general"}
  %collective-permute-start.2 = (bf16[16,8]{1,0}, bf16[16,8]{1,0}, u32[], u32[]) collective-permute-start(%fusion.5), source_target_pairs={{0,1}}, metadata={op_name="jit(_step)/transpose(jvp(GPT2))/h_0/mlp_proj/dot_general"}
  %collective-permute-done.2 = bf16[16,8]{1,0} collective-permute-done(%collective-permute-start.2), metadata={op_name="jit(_step)/transpose(jvp(GPT2))/h_0/mlp_proj/dot_general"}
  %all-reduce.1 = f32[]{:T(128)} all-reduce(%fusion.5), to_apply=%region, metadata={op_name="jit(_step)/optimizer/clip/reduce_sum"}
  %fusion.6 = bf16[16,8]{1,0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/optimizer/adamw/mul"}
  %fusion.7 = bf16[16,8]{1,0} fusion(%fusion.6), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/optimizer/add"}
  %compare.8 = pred[]{:T(512)} compare(%fusion.7, %fusion.7), direction=LT
  ROOT %select.9 = bf16[16,8]{1,0} select(%compare.8, %fusion.7, %fusion.7)
}
"""


@pytest.fixture(scope="module")
def module():
    return pt.parse_hlo(HLO)


def test_parse(module):
    assert module["module"] == "jit__step"
    fusion = module["instructions"]["fusion.1"]
    assert fusion.opcode == "fusion" and fusion.calls == "fused_computation.1"
    assert fusion.operands == ("arg1", "copy-done.1") and fusion.dims == (16, 8)
    start = module["instructions"]["collective-permute-start.1"]
    assert start.opcode == "collective-permute-start" and start.dims == (8, 8)
    assert module["weight_dims"] == {(8, 8), (8,)}
    assert "convolution.1" in module["computations"]["fused_computation.1"]


@pytest.mark.parametrize("name,phase,component,matmul", [
    ("fusion.1", "forward", "projection", True),
    ("fusion.2", "forward", "attention", False),
    ("fusion.3", "backward", "attention", False),
    ("fusion.4", "recompute", "mlp", True),
    # no metadata of its own: its fused computation's root names it
    ("fusion.5", "backward", "mlp", True),
    ("fusion.6", "optimizer", "adamw", False),
    ("fusion.7", "optimizer", "update", False),
    # what the compiler added belongs to the op that reads it
    ("copy-done.1", "forward", "projection", False),
    ("copy-start.1", "forward", "projection", False),
    ("all-reduce.1", "optimizer", "clip", False),
    # an argument's name is no scope; the nearest named neighbour is
    ("compare.8", "optimizer", "update", False),
    ("nothing.0", "unnamed", "unnamed", False),
])
def test_classification(module, name, phase, component, matmul):
    scope = pt.scope_of(module, name)
    assert (pt.phase(scope), pt.component(scope)) == (phase, component)
    assert pt.is_matmul(module, name) is matmul


@pytest.mark.parametrize("name,purpose,weight", [
    ("all-gather.1", pt.ASSEMBLE, False),
    ("all-reduce.1", pt.REDUCE, False),
    ("collective-permute-start.1", pt.ASSEMBLE, True),  # passes a shard along
    ("collective-permute-done.1", pt.ASSEMBLE, True),
    ("collective-permute-start.2", pt.REDUCE, False),  # partial sums
    ("fusion.1", pt.UNKNOWN, False),
])
def test_collective_purpose(module, name, purpose, weight):
    assert pt.collective_purpose(module, name) == purpose
    assert pt.weight_shaped(module, name) is weight


def op(name, start, end):
    text = {"fusion": "fusion(%x), kind=kOutput", "while": "while(%x)"}.get(
        name.split(".")[0], name.rsplit(".", 1)[0] + "(%x)"
    )
    return Event(f"%{name} = f32[8]{{0}} {text}", start, end)


def test_reduce_device(module):
    trace = Trace(
        ops={0: [
            op("fusion.1", 0.0, 2.0),  # forward projection, matmul
            op("all-gather.1", 2.0, 3.0),  # exposed assemble, forward
            op("fusion.3", 3.0, 4.0),  # backward attention
            op("collective-permute-done.2", 4.0, 4.5),  # exposed, reduce
            op("fusion.6", 5.0, 6.0),  # optimizer
            op("mystery.1", 6.0, 6.5),  # not in the text
            op("while.1", 0.0, 7.0),  # a container
            op("fusion.1", 10.0, 11.0),  # the next step: outside
        ]},
        async_ops={0: [
            # in flight under the matmul and after it: 0.5 s exposed at
            # 4.5-5, where no op of the ops line runs
            op("collective-permute-start.1", 1.0, 5.0),
        ]},
        modules={0: [Event("jit__step(7)", 0.0, 7.0),
                     Event("jit__step(7)", 10.0, 12.0)]},
        host_spans=[],
    )
    out = pt.reduce_device(trace, {"jit__step": module}, 0.0, 10.0)
    table = out["table"]
    assert table[("forward", "projection")] == pytest.approx(2.0)
    assert table[("forward", "attention")] == pytest.approx(1.0)  # all-gather
    assert table[("backward", "attention")] == pytest.approx(1.0)
    assert table[("backward", "mlp")] == pytest.approx(0.5)
    assert table[("optimizer", "adamw")] == pytest.approx(1.0)
    assert table[("unnamed", "unnamed")] == pytest.approx(0.5)
    assert out["unjoined_s"] == pytest.approx(0.5)
    assert out["ops_s"] == pytest.approx(out["busy_s"]) == pytest.approx(6.0)
    assert out["matmul_s"] == pytest.approx(2.0)
    # exposed: a partition of the collective time with no compute running
    assert out["exposed_s"] == pytest.approx(2.0)
    assert out["exposed"] == {
        pt.ASSEMBLE: pytest.approx(1.5), pt.REDUCE: pytest.approx(0.5),
        pt.UNKNOWN: pytest.approx(0.0),
    }
    assert sum(out["exposed"].values()) == pytest.approx(out["exposed_s"])
    assert out["exposed_phase"]["assemble/forward"] == pytest.approx(1.0)
    assert out["exposed_phase"]["assemble/in_flight"] == pytest.approx(0.5)


def steps_trace(first_end):
    """Four executions of a 1 s step, 1.0 s apart, each 0.8 s busy; the
    profile opens at 0.75 s into the first when ``first_end`` is 0.25."""
    starts = [0.0, first_end, first_end + 1.0, first_end + 2.0]
    ends = [first_end, *(t + 1.0 for t in starts[1:])]
    return Trace(
        ops={0: [op("fusion.1", t, min(t + 0.8, e))
                 for t, e in zip(starts, ends)]},
        async_ops={}, host_spans=[],
        modules={0: [Event("jit__step(7)", t, e)
                     for t, e in zip(starts, ends)]},
    )


@pytest.mark.parametrize("first_end,share,short_pct", [
    (1.0, 1.0, 0.0),  # the profile opened between two steps
    # a quarter of the first step is in the profile: the harness counts
    # 0.25 + 0.8 + 0.8 s as three steps
    (0.25, 0.25, 100.0 * (1.0 - (1.85 / 3.0) / 0.8)),
])
def test_window_leaves_the_first_execution_out(module, first_end, share,
                                               short_pct):
    trace = steps_trace(first_end)
    window = pt.traced_steps(trace)
    assert window["steps"] == 2 and window["executions"] == 4
    assert (window["lo"], window["hi"]) == (first_end, first_end + 2.0)
    assert window["first_execution_traced_share"] == pytest.approx(share)
    profile = pt.Profile(trace=trace, run_ids=[1, 2, 3, 4], spans=[],
                         completed={})
    found = pt.analyse(profile, {"jit__step": module})
    assert found["device"]["steps"] == 2
    assert found["steps"]["device_ms_per_step"] == pytest.approx(800.0)
    assert found["steps"]["harness_short_pct"] == pytest.approx(short_pct)
    assert found["gaps"]["idle_s"] == pytest.approx(0.4)


def test_window_needs_three_executions():
    trace = steps_trace(1.0)
    trace.modules[0] = trace.modules[0][:2]
    assert pt.traced_steps(trace) is None


def test_completion_lag_pairs_by_run_id():
    modules = [Event("jit__step(1)", t, t + 0.1) for t in (0.0, 0.2, 0.4)]
    profile = pt.Profile(
        trace=Trace(ops={}, async_ops={}, modules={0: modules}, host_spans=[]),
        run_ids=[10, 11, 12], spans=[],
        # out of order on the host's axis does not matter; 12 has no event
        completed={11: 0.3004, 10: 0.1002, 99: 5.0},
    )
    lag = pt.completion_lag(profile)
    assert lag["pairs"] == 2
    assert lag["lag_ms_min"] == pytest.approx(0.2)
    assert lag["lag_ms_max"] == pytest.approx(0.4)
    assert pt.completion_lag(pt.Profile(profile.trace, [None] * 3, [], {})) is None


def test_self_times_and_gaps():
    spans = sorted([
        pt.Span("facade.step", 0.0, 1.0, "main", {}),
        pt.Span("facade.step.apply", 0.2, 0.9, "main", {}),
        pt.Span("TrainStep.dispatch", 0.3, 0.5, "main", {}),
        pt.Span("loader.collate", 0.1, 0.6, "feeder", {}),  # another thread
        pt.Span("facade.model", 1.0, 1.5, "main", {}),
    ], key=lambda s: (s.start, -s.end))
    selfs = pt.self_times(spans)
    assert selfs["facade.step"] == pytest.approx(0.3)
    assert selfs["facade.step.apply"] == pytest.approx(0.5)
    assert selfs["TrainStep.dispatch"] == pytest.approx(0.2)
    assert selfs["loader.collate"] == pytest.approx(0.5)
    assert pt.main_thread(spans) == "main"
    mine = [s for s in spans if s.thread == "main"]
    # a device gap goes to the innermost span of the dispatching thread
    found = pt.gaps_by_span([(0.55, 0.75), (1.2, 1.3)], mine)
    assert found[0] == ["facade.step.apply", pytest.approx(0.2)]
    assert found[1] == ["facade.model", pytest.approx(0.1)]


# -- every new reader, in a traced run on the CPU ---------------------------------


def rehearse(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("GRAFT_TELEMETRY", None)
    env.pop("GRAFT_RUN_DIR", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "3", "--seconds", "2", "--trace", "1", "--rehearse", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("cell,expected,spans", [
    # no device plane on the CPU: the device-trace readers give None
    ("tiny-gpt2.train", set(), {"TrainStep.dispatch", "input.wait"}),
    ("tiny-swinir.stoke-loop",
     {"facade_self_ms_per_batch", "loader_produce_ms_per_batch"},
     {"facade.step.apply", "facade.backward.grad", "loader.collect",
      "loader.collate", "input.wait"}),
    ("tiny-swinir.fused-step", {"facade_self_ms_per_batch"},
     {"facade.fused_step", "TrainStep.dispatch", "facade.note_loss"}),
])
def test_readers_in_a_traced_rehearsal(cell, expected, spans):
    done = rehearse(cell)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["rehearsed_metrics"]) == expected
    (own,) = [json.loads(l) for l in done.stderr.splitlines()
              if l.startswith('{"program_trace"')]
    found = own["program_trace"]
    assert found["device"] is None and found["clock"] is None
    # the program's spans are in the profile with GRAFT_TELEMETRY unset
    assert spans <= set(found["spans"]["names"])


def test_manifest_reads_every_new_metric_somewhere():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        rehearsed = {m["name"] for m in json.load(f)["per_layer"]}
    new = {m["name"] for m in manifest["per_layer"]
           if "program_trace" in open(os.path.join(
               ROOT, "chipbench", "layer_metrics",
               m["name"].split(".")[0] + ".py")).read()}
    assert len(new) == 16 and new == rehearsed
