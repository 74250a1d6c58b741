"""What PR 35 added to the benchmark, on the CPU: the family's arithmetic
against the issue's, the configuration file against its source's widths,
``named_scopes`` on a hand-made trace and a small HLO text, the two readers
over it where there is something and where there is nothing to read, the
family's distances telling a lower precision and a planted fault, and the new
cell rehearsed through the harness's own command line from a rehearsal
directory of its own. ``tests/test_chipbench_afmoe.py`` imports these, so
tier-1 counts them."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import cells, named_scopes, program_trace as pt
from chipbench.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_trinity")
CELL = "trinity-mini.train-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = [
    "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
    "vocab_size",
]


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return cells.load_module("families", cell.config["family"], cell.roots)


def test_the_configuration_keeps_every_published_width(cell):
    config = cell.config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_experts_published": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "sliding_window": 2048, "rope_theta": 10000, "route_scale": 2.826,
        "route_norm": True, "score_func": "sigmoid", "rms_norm_eps": 1e-5,
        "mup_enabled": True, "load_balance_coeff": 0.001,
        "tie_word_embeddings": False, "rope_scaling": None,
        "model_type": "afmoe", "max_position_embeddings": 131072,
    }
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 25024)
    # the dense layer (sliding) and one whole period of the published pattern
    assert config["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert config["vocab_size"] * 8 == config["vocab_size_published"] == 200192
    assert (config["num_hidden_layers_published"],
            config["num_dense_layers_published"]) == (32, 2)
    assert "16 chips share each layer" in config["deployment"]
    assert "504.1 M parameters" in config["deployment"]
    assumed = config["assumed"]
    assert {
        "attention_gate", "qk_norm", "layer_norms", "positions",
        "rotary_layout", "embedding_multiplier", "shared_expert",
        "bias_update", "router_dtype", "auxiliary_loss", "initializer_range",
    } <= set(assumed)
    assert "modeling_afmoe.py" in assumed["modeling_file"]
    # the configuration's own initializer block: no branch output is scaled
    # (each ends in a norm), the embedding at unit variance
    assert config["initializer"] == {
        "range": 0.02, "residual_outputs": [], "residual_layers": 32,
        "embedding": ["embed_tokens"], "embedding_std": 1.0,
    }


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_there_or_listed_as_reduced(cell):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"
        )
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differs == set(cell.config["reduced"])
    published = row["config"]["layer_types"]
    assert [published[0]] + published[4:8] == cell.config["layer_types"]


def test_the_traffic_is_the_issues(cell):
    job = cell.workload["job_params"]
    assert (job["batch"], job["seq"]) == (1, 8192)
    assert job["optimizer"] == {"lr": 1e-5, "clip_grad_norm": 1.0}
    assert (job["precision"], job["policy"], job["mesh"], job["remat"]) == (
        "bf16", "DDP", {"dp": 1}, True
    )
    assert set(job) == {
        "mesh", "policy", "precision", "compute_dtype", "optimizer", "batch",
        "seq", "remat", "reference_query_chunk",
    }
    workload = cell.workload
    assert workload["job"] == "trainstep_attention_checked"
    assert workload["throughput_metric"] == "tokens_per_s"
    assert workload["follow_steps"] == 3
    assert set(workload["tolerance"]) == {
        "loss_abs", "grad_norm_rel", "grad_leaf_rel", "update_leaf_rel",
        "router_score_rms", "expert_layer_rel", "attention_rel", "why",
    }


def test_the_familys_arithmetic_is_the_issues(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    p = family.layer_params(config)
    # "attention 27.26 M a layer (q, o, gate 8.39 M each, k, v 1.05 M each)"
    assert round(p["attention"] / 1e6, 2) == 27.26
    assert round(p["dense_mlp"] / 1e6, 2) == 37.75
    assert round(p["expert"] / 1e6, 2) == 6.29
    assert round(p["router"] / 1e6, 2) == 0.26
    sparse = p["attention"] + p["router"] + 9 * p["expert"]
    total = (
        p["attention"] + p["dense_mlp"] + 4 * sparse + 2 * 25024 * 2048
    )
    assert round(sparse / 1e6, 1) == 84.1
    assert round(total / 1e6, 1) == 504.1
    seq = job["seq"]
    # "a sliding layer does 14.7 M of 33.6 M pairs (0.44)"
    assert round(family.visible_pairs(seq, 2048) / 1e6, 1) == 14.7
    assert round(family.visible_pairs(seq, None) / 1e6, 1) == 33.6
    assert family.layer_windows(config) == [2048] * 4 + [None]
    full = family.attention_cost(config, 1, seq, None)
    band = family.attention_cost(config, 1, seq, 2048)
    # q, out of 32 heads and k, v of 4, in bf16
    assert full["forward"][1] == band["forward"][1] == (
        seq * 128 * 2 * (2 * 32 + 2 * 4)
    )
    assert full["backward"][0] == 2 * full["forward"][0]
    # forward MACs a token: "core 92 M, its five projections 136 M of 356 M"
    core = (full["forward"][0] + 4 * band["forward"][0]) / 2 / seq
    assert round(core / 1e6) == 92 and round(5 * p["attention"] / 1e6) == 136
    per_token = family.train_flops_per_token(config, seq)
    assert round(per_token / 6 / 1e6) == 356
    # forward, the rematerialised forward, backward: "a step is 23.4 TFLOP"
    assert round(per_token * seq * 4 / 3 / 1e12, 1) == 23.4
    flops, nbytes = family.grouped_matmul_cost(config, 16384, 32)
    assert flops == 3 * 2 * 16384 * 2048 * 1024
    assert nbytes > 32 * 3 * 2048 * 1024 * 2  # at least every active weight
    costs = family.kernel_costs(config, job, 16384, 32)
    # remat: the forward runs twice, the backward is twice a forward
    assert costs["grouped_matmul"][0] == 4 * flops
    assert costs["attention_sliding"][0] == 4 * 4 * band["forward"][0]
    assert costs["attention"][0] == (
        4 * full["forward"][0] + costs["attention_sliding"][0]
    )


def test_the_family_builds_the_model_the_configuration_states(cell, family):
    cfg = family.model_config(cell.config, cell.workload["job_params"])
    assert (cfg.num_experts, cfg.expert_layer.held) == (128, tuple(range(8)))
    assert [cfg.window(i) for i in range(5)] == [2048] * 4 + [None]
    assert (cfg.num_dense_layers, cfg.num_hidden_layers) == (1, 5)
    assert (cfg.expert_layer.routed_scaling_factor,
            cfg.expert_layer.bias_update_rate) == (2.826, 0.001)
    assert cfg.remat is True and str(cfg.dtype) == "bfloat16"
    with pytest.raises(ValueError, match="group limits"):
        family.model_config({**cell.config, "n_group": 2}, {})


HLO = """HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}

%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0} parameter(0)
  ROOT %mul.1 = bf16[16,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/attn/qk_norm/q_norm/mul"}
}

ENTRY %main.9 (arg0: bf16[16,8]) -> bf16[16,8] {
  %arg0 = bf16[16,8]{1,0} parameter(0), metadata={op_name="batch[0]"}
  %fusion.1 = bf16[16,8]{1,0} fusion(%arg0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/attn/qk_norm/q_norm/mul"}
  %core.1 = bf16[16,8]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/attn/attention/attention_sliding/pallas_call"}
  %fusion.2 = bf16[16,8]{1,0} fusion(%core.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/attn/attention_gate/gate_proj/dot_general"}
  %fusion.3 = bf16[16,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/transpose(jvp(Afmoe))/layers_1/attn/attention_gate/mul"}
  %fusion.4 = bf16[16,8]{1,0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/post_norm/post_attention_layernorm/mul"}
  ROOT %fusion.5 = bf16[16,8]{1,0} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(Afmoe)/layers_1/attn/o_proj/dot_general"}
}
"""


def op(name, start, end):
    kind = name.split(".")[0]
    text = "fusion(%x), kind=kLoop" if kind == "fusion" else "custom-call(%x)"
    return Event(f"%{name} = bf16[16,8]{{1,0}} {text}", start, end)


def hand_made_trace():
    order = [
        ("fusion.1", 1.0), ("core.1", 8.0), ("fusion.2", 3.0),
        ("fusion.3", 0.5), ("fusion.4", 2.0), ("fusion.5", 4.0),
    ]
    ops, t = [], 0.0
    for name, seconds in order:
        ops.append(op(name, t, t + seconds))
        t += seconds
    return Trace(
        ops={0: ops}, async_ops={},
        modules={0: [Event("jit__step(1)", 0.0, t)]}, host_spans=[],
    ), t


def test_named_scopes_on_a_hand_made_trace():
    """The scopes are the caller's: the same trace read for two tuples of
    them, a kernel under one, an op under two counted once in ``any_s``."""
    programs = {"jit__step": pt.parse_hlo(HLO)}
    trace, t = hand_made_trace()
    gate = named_scopes.reduce_named(
        trace, programs, 0.0, t, ("qk_norm", "attention_gate")
    )
    assert gate["scope_s"] == {"qk_norm": 1.0, "attention_gate": 3.5}
    assert gate["any_s"] == 4.5
    # every fusion of the text calls one computation whose one instruction
    # is under qk_norm: named for their own roots, they all hold it
    assert gate["touched_s"] == {"qk_norm": 10.5, "attention_gate": 3.5}
    assert gate["kernel_s"] == {"qk_norm": 0.0, "attention_gate": 0.0}
    nested = named_scopes.reduce_named(
        trace, programs, 0.0, t, ("attention", "attention_sliding", "attn")
    )
    # the core is under all three, the norms, the gate and o_proj under attn
    assert nested["scope_s"] == {
        "attention": 8.0, "attention_sliding": 8.0, "attn": 16.5,
    }
    assert nested["any_s"] == 16.5
    assert nested["kernel_s"]["attention_sliding"] == 8.0
    assert nested["kernel_events"] == {
        "attention": 1, "attention_sliding": 1, "attn": 1,
    }
    # clipped to the window
    half = named_scopes.reduce_named(
        trace, programs, 0.0, 10.5, ("attention_gate", "post_norm")
    )
    assert half["scope_s"] == {"attention_gate": 1.5, "post_norm": 0.0}
    assert named_scopes.reduce_named(
        trace, programs, 0.0, t, ("no_such_scope",)
    )["any_s"] == 0.0


def test_the_two_readers_read_their_scopes(monkeypatch):
    programs = {"jit__step": pt.parse_hlo(HLO)}
    trace, t = hand_made_trace()
    window = {"steps": 2, "lo": 0.0, "hi": t}
    monkeypatch.setattr(
        named_scopes, "_CACHE", {"joined": (trace, programs, window)}
    )
    ctx = types.SimpleNamespace(counters={}, device_kind="TPU v5 lite", chips=1)
    read = lambda name: cells.load_module(  # noqa: E731
        "layer_metrics", name, (cells.HERE,)
    ).read(ctx)
    assert read("attention_gate_ms_per_step") == 1e3 * 4.5 / 2
    assert read("post_norm_ms_per_step") == 1e3 * 2.0 / 2
    # made once for each tuple of scopes
    assert set(named_scopes._CACHE) == {
        "joined", ("qk_norm", "attention_gate"), ("post_norm",),
    }


@pytest.mark.parametrize("reader", [
    "attention_gate_ms_per_step", "post_norm_ms_per_step",
])
def test_a_reader_with_nothing_to_read_returns_none(reader, monkeypatch):
    """No profile (a run without a trace): None, and nothing raises; a
    trace of a program that has no such scope (the parent's, another
    model's): zeros, so None."""
    monkeypatch.setenv("GRAFT_RUN_DIR", "/nonexistent/run")
    monkeypatch.setattr(named_scopes, "_CACHE", {})
    ctx = types.SimpleNamespace(counters={}, device_kind=None, chips=1)
    module = cells.load_module("layer_metrics", reader, (cells.HERE,))
    assert module.read(ctx) is None
    other = HLO.replace("qk_norm", "norms").replace(
        "attention_gate", "gate"
    ).replace("post_norm", "norm_after")
    trace, t = hand_made_trace()
    monkeypatch.setattr(named_scopes, "_CACHE", {"joined": (
        trace, {"jit__step": pt.parse_hlo(other)},
        {"steps": 3, "lo": 0.0, "hi": t},
    )})
    assert module.read(ctx) is None


def tiny_probe(family):
    """The rehearsal's model on a batch of its own, bf16 as the policy casts
    it, kernels interpreted: ``(tiny cell, reference's arch, float32 params,
    zero biases, probe)``."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import afmoe as reference
    from pytorch_distributedtraining_tpu.models import afmoe

    tiny = cells.load_cell("tiny-trinity.train", REHEARSAL)
    job = tiny.workload["job_params"]
    model = afmoe.Afmoe(family.model_config(tiny.config, job), interpret=True)
    tokens = jnp.asarray(next(family.task(tiny.config, job).batches(3))[0])
    variables = model.init(jax.random.PRNGKey(3), tokens)
    params, bias = variables["params"], variables[afmoe.ROUTER_STATE]
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    probe = model.apply(
        {"params": cast, afmoe.ROUTER_STATE: bias}, tokens,
        mutable=[afmoe.MOE_PROBE],
    )[1][afmoe.MOE_PROBE]
    return tiny, reference.arch_of(tiny.config), params, bias, probe


@pytest.fixture(scope="module")
def readings():
    """``benchmarks/afmoe_precision_readings.py``: the controls the chip's
    upper readings are taken with, planted here on the rehearsal's model."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import afmoe_precision_readings
    finally:
        sys.path.pop(0)
    return afmoe_precision_readings


@pytest.mark.parametrize("lower, number, sound_under, control_over", [
    ({"router": "bfloat16"}, "router_score_rms", 1e-7, 1e-5),
    ({"operands": "float8_e4m3fn"}, "expert_layer_rel", 0.01, 0.03),
])
def test_the_layer_distances_tell_a_lower_precision(
    family, readings, lower, number, sound_under, control_over
):
    """``expert_layer_distances`` on the rehearsal's model: the program's
    own layers read under a limit that the reference's layers, computed one
    precision lower on the same inputs, read over."""
    import jax.numpy as jnp

    from chipbench.reference import afmoe as reference

    _, arch, params, bias, probe = tiny_probe(family)
    sparse = {k: {"moe": v["moe"]} for k, v in probe.items() if "moe" in v}
    assert sorted(sparse) == ["layers_1", "layers_2", "layers_3"]
    lowered = readings.lowered_layers(
        reference, arch, jnp.bfloat16,
        {k: jnp.dtype(v) for k, v in lower.items()}, params, bias, sparse,
    )
    read = lambda found: float(family.expert_layer_distances(  # noqa: E731
        reference, arch, jnp.bfloat16, params, bias, found
    )[number])
    assert read(sparse) < sound_under < control_over < read(lowered)


@pytest.mark.parametrize("fault", [
    "fp8 e4m3 operands", "a band a key short", "a band a key long",
    "no band: causal alone", "rotary left off the sliding layers",
])
def test_the_attention_distances_tell_a_lower_precision_and_a_planted_fault(
    family, readings, fault
):
    """``attention_distances`` on the rehearsal's model, each core on its
    own q, k, v after the head norms and rotary: the program's cores read
    under a limit that the reference's attention with fp8 operands, under a
    band a key off or on q and k with the rotary taken off again reads
    over; a fault of the sliding layers leaves the full layer's reading
    where it was."""
    from chipbench.reference import afmoe as reference

    tiny, arch, _, _, probe = tiny_probe(family)
    chunk = tiny.workload["job_params"]["reference_query_chunk"]
    sound = family.attention_distances(reference, arch, chunk, probe)
    assert set(sound) == {
        "attention_rel", "attention_global_rel", "attention_sliding_rel",
    }
    assert float(sound["attention_rel"]) < 0.004
    how = readings.ATTENTION_FAULTS[fault]
    read = family.attention_distances(reference, arch, chunk, readings.faulty_cores(
        reference, arch, chunk, how, probe
    ))
    assert float(read["attention_sliding_rel"]) > 0.02
    if "operands" in how:
        assert float(read["attention_global_rel"]) > 0.02
    else:
        assert float(read["attention_global_rel"]) < 1e-6


def test_the_reference_moves_the_biases_by_its_own_loads(family):
    """``reference_state``: after a step every expert layer's bias has moved
    by the coefficient, up where the reference's own routing loaded an
    expert under the mean and down where over; the dense layer has none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tiny = cells.load_cell("tiny-trinity.train", REHEARSAL)
    task = family.task(tiny.config, tiny.workload["job_params"])
    params, model_state = task.init_fn(jax.random.PRNGKey(5))
    batch = jax.tree.map(jnp.asarray, next(task.batches(5)))
    moved = task.reference_state(params, model_state, batch)["router_state"]
    assert sorted(moved) == ["layers_1", "layers_2", "layers_3"]
    for name, layer in moved.items():
        step = np.asarray(layer["moe"]["bias"])
        assert step.shape == (8,)
        assert {round(float(x), 6) for x in np.abs(step)} <= {0.0, 0.001}
        assert step.min() < 0 < step.max()  # some over the mean, some under
    # the program's own step (bf16 operands) moves all but a few the same way
    _, aux = task.loss_fn(params, batch, None, model_state)
    same = np.concatenate([
        np.asarray(a) == np.asarray(b) for a, b in zip(
            jax.tree.leaves(aux["model_state"]["router_state"]),
            jax.tree.leaves(moved),
        )
    ])
    assert same.mean() >= 0.75
    loss, grads = task.reference_grads(params, model_state, batch)
    assert np.isfinite(float(loss)) and "embed_tokens" in grads


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "trinity-mini"
    assert entry["traffic"] == "train-8k" and len(entry["why"]) <= 200
    assert manifest["workloads"][-1] == entry  # appended, nothing moved
    config = manifest["configs"][-1]
    assert config["name"] == "trinity-mini"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    )
    new = manifest["per_layer"][-2:]
    assert [m["name"] for m in new] == [
        "attention_gate_ms_per_step", "post_norm_ms_per_step",
    ]
    assert all(
        m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        and m["source"] == "device_trace" for m in new
    )
    listed = {
        m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
        if CELL in m.get("workloads", [])
    }
    assert listed == {
        "tokens_per_s", "input_wait_ms_per_batch.tokens",
        "device_ms_per_step.tokens", "mfu_pct.tokens",
        "device_idle_pct.tokens", "peak_hbm_gb.tokens",
        "window_stall_pct.tokens", "forward_ms_per_step.tokens",
        "backward_ms_per_step.tokens", "optimizer_ms_per_step.tokens",
        "attention_ms_per_step.tokens", "matmul_share_pct.tokens",
        "recompute_ms_per_step", "attention_roofline_pct",
        "sliding_attention_roofline_pct", "grouped_matmul_roofline_pct",
        "expert_ms_per_step", "route_ms_per_step",
        "expert_load_max_over_mean", "dropped_assignments",
        "sliding_attention_ms_per_step", "global_attention_ms_per_step",
        "attention_gate_ms_per_step", "post_norm_ms_per_step",
    }
    # every reader of the cell has its file
    cell = cells.load_cell(CELL)
    for metric in cell.per_layer:
        cells.find(
            "layer_metrics", cells.reader_name(metric["name"]) + ".py",
            cell.roots,
        )


def test_the_new_cell_rehearsed_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "tiny-trinity.train", "--seed", "2900000035", "--seconds", "2",
         "--trace", "1", "--rehearse", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, notes = json.loads(lines[-1]), json.loads(lines[-2])
    assert notes["problems"] == []
    assert line["correct"] is True and line["failed"] == 0
    # each number compared beside its limit, last in the line
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {
        "loss_abs", "grad_norm_rel", "grad_leaf_rel", "update_leaf_rel",
        "router_score_rms", "expert_layer_rel", "dropped_assignments",
        "attention_rel", "compilations_in_window", "steps_failed",
    }
    assert line["compared"]["update_leaf_rel"]["value"] < 0.1
    found = notes["setup"]["reference"]
    assert 0 < found["attention_sliding_rel"] <= found["attention_rel"] < 0.1
    assert found["picks_agree"] > 0.9
    assert line["attempted"] > 4 and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}
    # no device plane on the CPU: the counters' readers and the host's read
    assert set(line["rehearsed_metrics"]) == {
        "cache_misses", "compile_s", "dropped_assignments",
        "expert_load_max_over_mean", "input_wait_ms_per_batch.tokens",
        "window_stall_pct.tokens",
    }
    # the routing's counters by step, beside the losses
    with open(os.path.join(
        ROOT, "chiprun_out", "tiny-trinity.train.2900000035", "counters.json"
    )) as f:
        per_step = json.load(f)["per_step"]
    assert len(per_step["assignments_landed"]) == line["attempted"]
    assert sum(per_step["dropped_assignments"]) == 0
