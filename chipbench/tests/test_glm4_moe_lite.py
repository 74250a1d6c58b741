"""What PR 27 added to the benchmark, on the CPU: the family's arithmetic
against the issue's, the configuration file against its source's widths,
``scope_trace`` on a hand-made trace and a small HLO text, the readers where
there is nothing to read, and the new cell rehearsed through the harness's
own command line from a rehearsal directory of its own."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import cells, program_trace as pt, run, scope_trace
from chipbench.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_glm")
CELL = "glm-4.7-flash.train-4k"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return cells.load_module("families", cell.config["family"], cell.roots)


def test_the_configuration_keeps_every_published_width(cell):
    config = cell.config
    published = {
        "hidden_size": 2048, "num_attention_heads": 20, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "n_routed_experts_published": 64,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    }
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers",
    ])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        5, 8, 19360, 0)
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert "8 chips share each layer" in config["deployment"]
    assert {"rotary_layout", "bias_update_rate", "initializer_range"} <= set(
        config["assumed"]
    )


def test_the_familys_arithmetic_is_the_issues(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    p = family.layer_params(config)
    assert round(p["mla"] / 1e6, 2) == 21.76
    assert round(p["expert"] / 1e6, 2) == 9.44
    assert round(p["router"] / 1e6, 2) == 0.13
    per_step = family.train_flops_per_token(config, job["seq"]) * (
        job["batch"] * job["seq"]
    )
    assert 23.4e12 < per_step < 23.6e12  # "about 23.5 TFLOP a step"
    attn = family.attention_cost(config, job["batch"], job["seq"])
    # the causal half of two matmuls of 2 * T^2 * 256 a head
    assert attn["forward"][0] == pytest.approx(
        2 * 2 * 20 * 4096 * 4097 / 2 * 512, rel=1e-9
    )
    assert attn["backward"][0] == 2 * attn["forward"][0]
    flops, nbytes = family.grouped_matmul_cost(config, 4096, 8)
    assert flops == 3 * 2 * 4096 * 2048 * 1536
    assert nbytes > 8 * 3 * 2048 * 1536 * 2  # at least every active weight
    assert family.grouped_matmul_cost(config, 0, 0) == (0, 0)  # no rows: idle
    costs = family.kernel_costs(config, job, 4 * 4096, 32)
    # remat: the forward runs twice, the backward is twice a forward
    assert costs["grouped_matmul"][0] == 4 * 3 * 2 * 4 * 4096 * 2048 * 1536
    assert costs["attention"][0] == 5 * 4 * attn["forward"][0]


HLO = """HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}

%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0} parameter(0)
  ROOT %mul.1 = bf16[16,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/experts/mul"}
}

ENTRY %main.9 (arg0: bf16[16,8]) -> bf16[16,8] {
  %arg0 = bf16[16,8]{1,0} parameter(0), metadata={op_name="batch[0]"}
  %fusion.1 = bf16[16,8]{1,0} fusion(%arg0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/mla/q_a_proj/dot_general"}
  %attention.1 = bf16[16,8]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/mla/attention/pallas_call"}
  %fusion.2 = bf16[16,8]{1,0} fusion(%attention.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/router/dot_general"}
  %fusion.3 = bf16[16,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/dispatch/gather"}
  %gmm.1 = bf16[16,8]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/experts/jit(gmm)/pallas_call"}
  %fusion.4 = bf16[16,8]{1,0} fusion(%gmm.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/experts/mul"}
  %tgmm.1 = bf16[16,8]{1,0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/transpose(jvp(Glm4MoeLite))/jvp(Glm4MoeLite)/checkpoint/layers_1/moe/experts/jit(tgmm)/pallas_call"}
  %fusion.5 = bf16[16,8]{1,0} fusion(%tgmm.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/combine/mul"}
  ROOT %fusion.6 = bf16[16,8]{1,0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Glm4MoeLite)/layers_1/moe/shared_expert/mlp_shared/up_proj/dot_general"}
}
"""


def op(name, start, end):
    kind = name.split(".")[0]
    text = "fusion(%x), kind=kLoop" if kind == "fusion" else "custom-call(%x)"
    return Event(f"%{name} = bf16[16,8]{{1,0}} {text}", start, end)


def test_scope_trace_on_a_hand_made_trace():
    module = pt.parse_hlo(HLO)
    order = [
        ("fusion.1", 2.0), ("attention.1", 8.0), ("fusion.2", 1.0),
        ("fusion.3", 3.0), ("gmm.1", 4.0), ("fusion.4", 0.5), ("tgmm.1", 2.0),
        ("fusion.5", 5.0), ("fusion.6", 6.0),
    ]
    ops, t = [], 0.0
    for name, seconds in order:
        ops.append(op(name, t, t + seconds))
        t += seconds
    trace = Trace(
        ops={0: ops}, async_ops={},
        modules={0: [Event("jit__step(1)", 0.0, t)]}, host_spans=[],
    )
    got = scope_trace.reduce_scopes(trace, {"jit__step": module}, 0.0, t)
    assert got["scope_s"] == {
        "mla": 10.0, "attention": 8.0, "router": 1.0, "dispatch": 3.0,
        "experts": 6.5, "combine": 5.0, "shared_expert": 6.0,
    }
    assert got["kernel_s"] == {"experts": 6.0, "attention": 8.0}
    assert got["kernel_events"] == {"experts": 2, "attention": 1}
    assert scope_trace.is_kernel(module, "gmm.1")
    assert not scope_trace.is_kernel(module, "fusion.4")
    # clipped to the window
    half = scope_trace.reduce_scopes(trace, {"jit__step": module}, 0.0, 6.0)
    assert half["scope_s"]["attention"] == 4.0


@pytest.mark.parametrize("reader", [
    "expert_ms_per_step", "route_ms_per_step", "mla_projection_ms_per_step",
    "expert_load_max_over_mean", "dropped_assignments",
    "grouped_matmul_roofline_pct", "attention_roofline_pct",
])
def test_a_reader_with_nothing_to_read_returns_none(reader, monkeypatch):
    """No profile, no counters (the parent's program, another job): None,
    and nothing raises."""
    monkeypatch.setenv("GRAFT_RUN_DIR", "/nonexistent/run")
    monkeypatch.setattr(scope_trace, "_CACHE", {})
    ctx = types.SimpleNamespace(counters={}, device_kind=None, chips=1)
    module = cells.load_module("layer_metrics", reader, (cells.HERE,))
    assert module.read(ctx) is None


def test_the_counted_job_keeps_the_windows_counters_and_holds_the_layers():
    """After set-up the step is called through the wrapper, which keeps the
    step's counters in call order (set-up's own calls are not kept); the
    check refuses an expert layer too far from the reference's, and a
    dropped assignment."""
    job_module = cells.load_module("jobs", "trainstep_counted", (cells.HERE,))
    calls = []

    def inner(state, batch):
        calls.append(state)
        return state + 1, {k: float(len(calls)) for k in job_module.COUNTERS}

    def base_setup(self):  # what trainstep.Job.setup leaves behind
        self.step = inner
        self.step(0, None)
        return {"step0": {}, "reference": {}}

    tolerance = {"loss_abs": 1.0, "grad_norm_rel": 1.0,
                 "router_score_rms": 1e-3, "expert_layer_rel": 1e-2}
    env = types.SimpleNamespace(
        cell=types.SimpleNamespace(workload={"tolerance": tolerance}),
        counters={"dropped_assignments": 0},
    )
    job = job_module.Job(env)
    original = job_module.trainstep.Job.setup
    job_module.trainstep.Job.setup = base_setup
    try:
        job.setup()
    finally:
        job_module.trainstep.Job.setup = original
    state = 1
    for _ in range(3):
        state, metrics = job.step(state, None)
    assert state == 4 and metrics["dropped_assignments"] == 4.0
    assert [row[0] for row in job.kept] == [2.0, 3.0, 4.0]

    sound = {
        "step0": {"loss": 1.0, "grad_norm": 1.0},
        "reference": {"loss": 1.0, "grad_norm": 1.0,
                      "router_score_rms": 4e-4, "expert_layer_rel": 4e-3},
    }

    def over():
        assert job.check(sound, None) == []
        return [p.split()[0] for p in run.over_limit(env.counters["compared"])]

    assert over() == []
    sound["reference"]["router_score_rms"] = 1.1e-3  # a bf16 router's
    assert over() == ["router_score_rms"]
    sound["reference"].update(router_score_rms=4e-4, expert_layer_rel=0.07)
    assert over() == ["expert_layer_rel"]
    sound["reference"]["expert_layer_rel"] = 4e-3
    env.counters["dropped_assignments"] = 2
    assert over() == ["dropped_assignments"]


@pytest.mark.parametrize("lower, number, sound_under, control_over", [
    ({"router": "bfloat16"}, "router_score_rms", 1e-6, 1e-5),
    ({"operands": "float8_e4m3fn"}, "expert_layer_rel", 0.01, 0.05),
])
def test_the_layer_distances_tell_a_lower_precision(
    family, lower, number, sound_under, control_over
):
    """``expert_layer_distances`` on the rehearsal's model: the program's
    own layers read under a limit that the reference's layers, computed one
    precision lower on the same inputs, read over (a bf16 router by its
    scores, fp8 operands by the layer's output)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import glm4_moe_lite as reference
    from pytorch_distributedtraining_tpu.models import glm4_moe_lite as glm

    tiny = cells.load_cell("tiny-glm.train", REHEARSAL)
    job = tiny.workload["job_params"]
    arch = reference.arch_of(tiny.config)
    model = glm.Glm4MoeLite(family.model_config(tiny.config, job), interpret=True)
    tokens = jnp.asarray(next(family.task(tiny.config, job).batches(3))[0])
    variables = model.init(jax.random.PRNGKey(3), tokens)
    params, bias = variables["params"], variables[glm.ROUTER_STATE]
    cast = lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)  # noqa: E731
    probe = model.apply(
        {"params": cast(params), glm.ROUTER_STATE: bias}, tokens,
        mutable=[glm.MOE_PROBE],
    )[1][glm.MOE_PROBE]
    lower = {k: jnp.dtype(v) for k, v in lower.items()}
    lowered = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            x = layer["moe"]["input"].astype(jnp.float32)
            b = bias[name]["moe"]["bias"]
            p = dict(params[name]["moe"])
            p["router"] = p["router"].astype(jnp.bfloat16).astype(jnp.float32)
            router = lower.get("router")
            lowered[name] = {"moe": {
                "input": x,
                "scores": reference.router_scores(x, p["router"], router),
                "picks": reference.route(x, p["router"], b, arch, router)[0],
                "output": reference.expert_layer(x, p, b, arch, **lower),
            }}
    read = lambda found: float(family.expert_layer_distances(  # noqa: E731
        reference, arch, jnp.bfloat16, params, bias, found
    )[number])
    assert read(probe) < sound_under < control_over < read(lowered)


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "glm-4.7-flash"
    # the metrics PR 27 brought; a later sparse cell may join their lists
    mine = {
        m["name"] for m in manifest["per_layer"]
        if CELL in m.get("workloads", ())
    }
    assert mine >= {
        "expert_ms_per_step", "route_ms_per_step",
        "mla_projection_ms_per_step", "expert_load_max_over_mean",
        "dropped_assignments", "grouped_matmul_roofline_pct",
        "attention_roofline_pct",
    }
    shared = {
        m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
        if CELL in m.get("workloads", []) and m.get("workloads") != [CELL]
    }
    assert {"tokens_per_s", "mfu_pct.tokens", "attention_ms_per_step.tokens",
            "recompute_ms_per_step", "peak_hbm_gb.tokens"} <= shared


def test_the_new_cell_rehearsed_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-glm.train",
         "--seed", "2900000033", "--seconds", "2", "--trace", "1",
         "--rehearse", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 4 and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}
    # no device plane on the CPU: the counters' readers and the host's read
    assert set(line["rehearsed_metrics"]) == {
        "cache_misses", "compile_s", "dropped_assignments",
        "expert_load_max_over_mean", "input_wait_ms_per_batch.tokens",
        "window_stall_pct.tokens",
    }
