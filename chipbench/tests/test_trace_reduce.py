"""The reduction from trace to numbers: a hand-made case with known answers,
and a small trace recorded on a TPU v5e."""

import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event, Trace

RECORDED = os.path.join(
    os.path.dirname(__file__), "data", "gpt2_1layer_4steps.xplane.pb"
)


def hlo(name, kind=None):
    text = f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %x)"
    return text + (f", kind={kind}" if kind else "")


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9),
    ]
    assert tr.subtract([(0, 2), (5, 6)], [(1, 5.5)]) == [(0, 1), (5.5, 6)]


def test_names():
    text = "%fusion.93 = bf16[2,12]{1,0} fusion(bf16[2]{0} %p), kind=kOutput, calls=%f"
    assert tr.op_family(text) == "fusion/kOutput"
    assert tr.op_family(hlo("all-gather-start.3")) == "all-gather-start"
    assert tr.is_collective("all-gather-start")
    assert tr.is_collective("all-reduce")
    assert not tr.is_collective("fusion/kLoop")
    assert tr.module_name("jit__step(12573960010487406730)") == "jit__step"


def hand_made() -> Trace:
    """Two steps of 10 s on two devices. Device 0, per step: compute 0-4,
    an all-reduce 4-5 (synchronous: nothing else runs), compute 5-7 under
    an asynchronous all-gather 4.5-8 (exposed 4.5-5 with the all-reduce and
    7-8 alone), idle 8-10. The host waits for input in 8-9.5 of step one,
    inside a longer loop span, and is in no span in the gap of step two."""
    ops, async_ops = [], []
    for base in (0.0, 10.0):
        ops += [
            Event(hlo("fusion.1", "kOutput"), base + 0, base + 4),
            Event(hlo("all-reduce.2"), base + 4, base + 5),
            Event(hlo("fusion.3", "kLoop"), base + 5, base + 7),
            # a loop around all of it: no work of its own, busy through its body
            Event(hlo("while.5"), base + 0, base + 9),
        ]
        async_ops.append(Event(hlo("all-gather-start.4"), base + 4.5, base + 8))
    modules = [Event(f"jit__step({i})", t, t + 8) for i, t in enumerate((0, 10, 20))]
    other = [Event("jit_convert_element_type(7)", 9.9, 9.91)]
    return Trace(
        ops={0: ops, 1: [Event(hlo("fusion.1", "kOutput"), 0, 20)]},
        async_ops={0: async_ops}, modules={0: modules + other},
        host_spans=[
            Event("cb/loop", 0.0, 12.0), Event("cb/input_wait", 8.0, 9.5),
        ],
    )


def test_hand_made_case():
    out = tr.reduce(hand_made(), ("jit__step",))
    assert out["steps"] == 2 and out["devices"] == 2
    assert out["window_s"] == pytest.approx(20.0)
    assert out["busy_s"] == pytest.approx(14.0)  # 2 x (4 + 1 + 2)
    assert out["busy_mean_s"] == pytest.approx(17.0)  # device 1 never idles
    assert out["idle_share"] == pytest.approx(0.3)
    assert out["idle_share_worst"] == pytest.approx(0.3)
    assert out["step_period_s"] == pytest.approx(10.0)
    assert out["collective_s"] == pytest.approx(8.0)  # 2 x [4, 8]
    assert out["exposed_collective_s"] == pytest.approx(4.0)  # 2 x (1 + 1)
    assert out["longest_gap_s"] == pytest.approx(3.0)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # step one's gap 7-10: the input wait takes 8-9.5, the loop span around
    # it the rest; step two's gap 17-20 lies in no span
    assert gaps == pytest.approx(
        {"cb/input_wait": 1.5, "cb/loop": 1.5, "(no span)": 3.0}
    )
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx(
        {"fusion/kOutput": 8.0, "fusion/kLoop": 4.0, "all-reduce": 2.0}
    )


def test_too_few_steps_reduce_to_nothing():
    trace = hand_made()
    trace.modules[0] = trace.modules[0][:1]
    assert tr.reduce(trace, ("jit__step",)) is None
    assert tr.reduce(hand_made(), ("jit_other",)) is None


def test_recorded_v5e_trace():
    trace = tr.load(RECORDED)
    assert sorted(trace.ops) == [0] and len(trace.ops[0]) == 940
    assert [s.name for s in trace.host_spans] == [
        "cb/dispatch", "cb/dispatch", "cb/sleep", "cb/dispatch",
        "cb/dispatch", "cb/fence",
    ]
    out = tr.reduce(trace, ("jit__step",))
    assert out["steps"] == 3
    assert out["window_s"] == pytest.approx(0.057238564, rel=1e-6)
    assert out["busy_s"] == pytest.approx(0.009519069, rel=1e-6)
    assert out["idle_share"] == pytest.approx(0.833695, rel=1e-5)
    assert out["collective_s"] == 0 and out["exposed_collective_s"] == 0
    # the 50 ms the host slept is the gap, and is named
    name, seconds = out["breakdown"]["idle_gaps"][0]
    assert name == "cb/sleep" and seconds == pytest.approx(0.047441, rel=1e-4)
    assert out["breakdown"]["device_ops"][0][0] == "multiply_add_fusion/kLoop"
    assert len(out["breakdown"]["device_ops"]) <= 10
