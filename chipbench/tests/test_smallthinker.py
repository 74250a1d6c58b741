"""What PR 32 added to the benchmark, on the CPU: the family's arithmetic
against the issue's, the configuration file against its source's widths,
``attention_kinds`` on a hand-made trace and a small HLO text, the readers
where there is nothing to read, and the new cell rehearsed through the
harness's own command line from a rehearsal directory of its own."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import (
    attention_kinds, cells, program_trace as pt, run, seeded,
)
from chipbench.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal_smallthinker")
CELL = "smallthinker-21b-a3b.train-16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return cells.load_module("families", cell.config["family"], cell.roots)


def test_the_configuration_keeps_every_published_width(cell):
    config = cell.config
    published = {
        "hidden_size": 2560, "num_attention_heads": 28,
        "num_key_value_heads": 4, "head_dim": 128, "moe_ffn_hidden_size": 768,
        "moe_num_primary_experts_published": 64,
        "moe_num_active_primary_experts": 6, "sliding_window_size": 4096,
        "max_position_embeddings": 16384, "rms_norm_eps": 1e-6,
        "rope_theta": 1500000, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "rope_scaling": None, "model_name": "smallthinker_21b_instruct",
    }
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout",
    ])
    assert sorted(config["reduced_why"]) == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 8, 18992)
    # one whole period of the published pattern
    assert config["rope_layout"] == config["sliding_window_layout"] == [
        0, 1, 1, 1
    ]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["num_hidden_layers_published"] == 52
    assert "8 chips share each layer" in config["deployment"]
    assert {
        "rotary_layout", "initializer_range", "router_input", "router_dtype",
        "auxiliary_loss", "dropout",
    } <= set(config["assumed"])


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_there_or_listed_as_reduced(cell):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "SmallThinker-21BA3B-Instruct"
        )
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differs == set(cell.config["reduced"])
    assert row["config"]["rope_layout"][:4] == cell.config["rope_layout"]


def test_the_traffic_is_the_issues(cell):
    job = cell.workload["job_params"]
    assert (job["batch"], job["seq"]) == (1, 16384)
    assert job["optimizer"] == {"lr": 1e-5, "clip_grad_norm": 1.0}
    assert (job["precision"], job["policy"], job["mesh"], job["remat"]) == (
        "bf16", "DDP", {"dp": 1}, True
    )
    assert cell.workload["trace_seconds"] == 3.0
    # the issue's four bullets and no parameter of the mix beside them
    assert set(job) == {
        "mesh", "policy", "precision", "compute_dtype", "optimizer", "batch",
        "seq", "remat", "reference_query_chunk",
    }
    # trainstep_counted itself, and the attention cores held as well
    assert cell.workload["job"] == "trainstep_attention_checked"
    assert cell.workload["throughput_metric"] == "tokens_per_s"


def test_ids_are_drawn_evenly_and_independently_from_the_seed(family):
    first = seeded.even_batches(2**31 + 7, 1, 4096, 18992)
    tokens, targets = next(first)
    again = next(seeded.even_batches(2**31 + 7, 1, 4096, 18992))[0]
    other = next(seeded.even_batches(2**31 + 8, 1, 4096, 18992))[0]
    assert tokens.shape == targets.shape == (1, 4096)
    assert (tokens[:, 1:] == targets[:, :-1]).all()
    assert (tokens == again).all() and not (tokens == other).all()
    assert 0 <= tokens.min() and tokens.max() < 18992
    # even: no id is hot (Zipf(1.1)'s hottest is 14.6% of the tokens)
    counts = {}
    for t in tokens.ravel().tolist():
        counts[t] = counts.get(t, 0) + 1
    assert max(counts.values()) <= 5
    assert not (next(first)[0] == tokens).all()  # a fresh draw every step


def test_the_familys_arithmetic_is_the_issues(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    p = family.layer_params(config)
    assert round(p["attention"] / 1e6, 2) == 20.97
    assert round(p["router"] / 1e6, 2) == 0.16
    assert round(8 * p["expert"] / 1e6, 2) == 47.19
    seq = job["seq"]
    # "a window layer does 58.7 M of the 134.2 M causal pairs"
    assert round(family.visible_pairs(seq, 4096) / 1e6, 1) == 58.7
    assert round(family.visible_pairs(seq, None) / 1e6, 1) == 134.2
    assert family.visible_pairs(8, 3) == 1 + 2 + 3 * 6
    assert family.layer_windows(config) == [None, 4096, 4096, 4096]
    full = family.attention_cost(config, 1, seq, None)
    band = family.attention_cost(config, 1, seq, 4096)
    # forward MFLOP a token: "full layer 117, window layers 51 each"
    assert round(full["forward"][0] / seq / 1e6) == 117
    assert round(band["forward"][0] / seq / 1e6) == 51
    assert full["backward"][0] == 2 * full["forward"][0]
    # q, out of 28 heads and k, v of 4, in bf16
    assert full["forward"][1] == band["forward"][1] == (
        seq * 128 * 2 * (2 * 28 + 2 * 4)
    )
    # "the attention core is 68 of a layer's 119 forward MFLOP a token"
    core = (full["forward"][0] + 3 * band["forward"][0]) / 4 / seq
    layer = core + 2 * (p["attention"] + p["router"] + 0.75 * p["expert"])
    assert round(core / 1e6) == 68 and round(layer / 1e6) == 119
    per_step = family.train_flops_per_token(config, seq) * seq
    assert 28.0e12 < per_step < 28.4e12
    flops, nbytes = family.grouped_matmul_cost(config, 12288, 8)
    assert flops == 3 * 2 * 12288 * 2560 * 768
    assert nbytes > 8 * 3 * 2560 * 768 * 2  # at least every active weight
    assert family.grouped_matmul_cost(config, 0, 0) == (0, 0)
    costs = family.kernel_costs(config, job, 4 * 12288, 32)
    # remat: the forward runs twice, the backward is twice a forward
    assert costs["grouped_matmul"][0] == 4 * 3 * 2 * 4 * 12288 * 2560 * 768
    assert costs["attention_sliding"][0] == 3 * 4 * band["forward"][0]
    assert costs["attention"][0] == (
        4 * full["forward"][0] + costs["attention_sliding"][0]
    )


HLO = """HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}

%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0} parameter(0)
  ROOT %mul.1 = bf16[16,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_1/attn/attention/attention_sliding/transpose"}
}

ENTRY %main.9 (arg0: bf16[16,8]) -> bf16[16,8] {
  %arg0 = bf16[16,8]{1,0} parameter(0), metadata={op_name="batch[0]"}
  %fusion.1 = bf16[16,8]{1,0} fusion(%arg0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_0/router/dot_general"}
  %global.1 = bf16[16,8]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_0/attn/attention/attention_global/pallas_call"}
  %fusion.2 = bf16[16,8]{1,0} fusion(%global.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_1/attn/attention/attention_sliding/transpose"}
  %sliding.1 = bf16[16,8]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_1/attn/attention/attention_sliding/pallas_call"}
  %sliding.2 = bf16[16,8]{1,0} custom-call(%sliding.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/transpose(jvp(SmallThinker))/layers_1/attn/attention/attention_sliding/pallas_call"}
  ROOT %fusion.3 = bf16[16,8]{1,0} fusion(%sliding.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/jvp(SmallThinker)/layers_1/attn/o_proj/dot_general"}
}
"""


def op(name, start, end):
    kind = name.split(".")[0]
    text = "fusion(%x), kind=kLoop" if kind == "fusion" else "custom-call(%x)"
    return Event(f"%{name} = bf16[16,8]{{1,0}} {text}", start, end)


def test_attention_kinds_on_a_hand_made_trace():
    module = pt.parse_hlo(HLO)
    order = [
        ("fusion.1", 1.0), ("global.1", 8.0), ("fusion.2", 0.5),
        ("sliding.1", 3.0), ("sliding.2", 6.0), ("fusion.3", 2.0),
    ]
    ops, t = [], 0.0
    for name, seconds in order:
        ops.append(op(name, t, t + seconds))
        t += seconds
    trace = Trace(
        ops={0: ops}, async_ops={},
        modules={0: [Event("jit__step(1)", 0.0, t)]}, host_spans=[],
    )
    got = attention_kinds.reduce_kinds(trace, {"jit__step": module}, 0.0, t)
    assert got["scope_s"] == {
        "attention_sliding": 9.5, "attention_global": 8.0,
    }
    assert got["kernel_s"] == {
        "attention_sliding": 9.0, "attention_global": 8.0,
    }
    assert got["kernel_events"] == {
        "attention_sliding": 2, "attention_global": 1,
    }
    # clipped to the window
    half = attention_kinds.reduce_kinds(trace, {"jit__step": module}, 0.0, 5.0)
    assert half["scope_s"]["attention_global"] == 4.0
    assert half["scope_s"]["attention_sliding"] == 0.0
    # the readers over it: a third of the window layers' against the full one
    found = {"steps": 2, **got}
    attention_kinds._CACHE["analysis"] = found
    try:
        ctx = types.SimpleNamespace(
            counters={"kernel_costs": lambda rows, active: {
                "attention_sliding": (197e12 * 0.9, 819e9 * 0.5),
            }},
            device_kind="TPU v5 lite", chips=1,
        )
        read = lambda name: cells.load_module(  # noqa: E731
            "layer_metrics", name, (cells.HERE,)
        ).read(ctx)
        assert read("sliding_attention_ms_per_step") == 1e3 * 9.5 / 2
        assert read("global_attention_ms_per_step") == 1e3 * 8.0 / 2
        # 0.9 s of FLOPs at the peak against 4.5 s of kernels a step
        assert read("sliding_attention_roofline_pct") == pytest.approx(20.0)
        ctx.counters["kernel_costs"] = lambda rows, active: {"attention": (1, 1)}
        assert read("sliding_attention_roofline_pct") is None
    finally:
        attention_kinds._CACHE.clear()


@pytest.mark.parametrize("reader", [
    "sliding_attention_ms_per_step", "global_attention_ms_per_step",
    "sliding_attention_roofline_pct",
])
def test_a_reader_with_nothing_to_read_returns_none(reader, monkeypatch):
    """No profile, no costs (the parent's program, another family): None,
    and nothing raises."""
    monkeypatch.setenv("GRAFT_RUN_DIR", "/nonexistent/run")
    monkeypatch.setattr(attention_kinds, "_CACHE", {})
    ctx = types.SimpleNamespace(counters={}, device_kind=None, chips=1)
    module = cells.load_module("layer_metrics", reader, (cells.HERE,))
    assert module.read(ctx) is None
    # a trace of a program that has no such scope: zeros, so None
    monkeypatch.setattr(attention_kinds, "_CACHE", {"analysis": {
        "steps": 3,
        "scope_s": dict.fromkeys(attention_kinds.KINDS, 0.0),
        "kernel_s": dict.fromkeys(attention_kinds.KINDS, 0.0),
    }})
    ctx = types.SimpleNamespace(
        counters={"kernel_costs": lambda r, a: {"attention": (1, 1)}},
        device_kind="TPU v5 lite", chips=1,
    )
    assert module.read(ctx) is None


def tiny_probe(family):
    """The rehearsal's model on a batch of its own, bf16 as the policy casts
    it, kernels interpreted: ``(tiny cell, reference's arch, float32 params, probe)``."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import smallthinker as reference
    from pytorch_distributedtraining_tpu.models import smallthinker as st

    tiny = cells.load_cell("tiny-smallthinker.train", REHEARSAL)
    job = tiny.workload["job_params"]
    model = st.SmallThinker(family.model_config(tiny.config, job), interpret=True)
    tokens = jnp.asarray(next(family.task(tiny.config, job).batches(3))[0])
    params = model.init(jax.random.PRNGKey(3), tokens)["params"]
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    probe = model.apply(
        {"params": cast}, tokens, mutable=[st.MOE_PROBE]
    )[1][st.MOE_PROBE]
    return tiny, reference.arch_of(tiny.config), params, probe


@pytest.mark.parametrize("lower, number, sound_under, control_over", [
    ({"router": "bfloat16"}, "router_score_rms", 1e-7, 1e-6),
    ({"operands": "float8_e4m3fn"}, "expert_layer_rel", 0.01, 0.03),
])
def test_the_layer_distances_tell_a_lower_precision(
    family, lower, number, sound_under, control_over
):
    """``expert_layer_distances`` on the rehearsal's model: the program's
    own layers read under a limit that the reference's layers, computed one
    precision lower on the same inputs, read over (a bf16 router by its
    logits, fp8 operands by the layer's output)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import smallthinker as reference

    _, arch, params, probe = tiny_probe(family)
    lower = {k: jnp.dtype(v) for k, v in lower.items()}
    lowered = {}
    with jax.default_matmul_precision("highest"):
        for name, layer in probe.items():
            got = layer["moe"]
            x = got["router_input"].astype(jnp.float32)
            u = got["input"].astype(jnp.float32)
            w_r = params[name]["router"]["kernel"].astype(jnp.bfloat16).astype(
                jnp.float32
            )
            router = lower.get("router")
            sel, w = reference.route(x, w_r, arch, router)
            lowered[name] = {"moe": {
                "router_input": x, "input": u,
                "scores": reference.router_logits(x, w_r, router),
                "picks": sel,
                "output": reference.expert_layer(
                    u, params[name]["moe"], sel, w, arch, lower.get("operands")
                ),
            }}
    read = lambda found: float(family.expert_layer_distances(  # noqa: E731
        reference, arch, jnp.bfloat16, params, found
    )[number])
    assert read(probe) < sound_under < control_over < read(lowered)


@pytest.mark.parametrize("fault, number", [
    ({"operands": "float8_e4m3fn"}, "attention_rel"),
    ({"window": -1}, "attention_sliding_rel"),  # the band a key short
    ({"window": 1}, "attention_sliding_rel"),  # and a key long
    ({"window": None}, "attention_sliding_rel"),  # masked by causality alone
])
def test_the_attention_distances_tell_a_lower_precision_and_a_wrong_band(
    family, fault, number
):
    """``attention_distances`` on the rehearsal's model: the program's own
    cores read under a limit that the reference's attention, computed with
    fp8 operands or under a band a key off on the same q, k, v, reads
    over; a fault of the window layers leaves the full layer's reading
    where it was."""
    import jax.numpy as jnp

    from chipbench.reference import smallthinker as reference

    tiny, arch, _, probe = tiny_probe(family)
    chunk = tiny.workload["job_params"]["reference_query_chunk"]
    sound = family.attention_distances(reference, arch, chunk, probe)
    assert set(sound) == {
        "attention_rel", "attention_global_rel", "attention_sliding_rel",
    }
    assert float(sound["attention_rel"]) < 0.004

    faulty = {}
    for name, layer in probe.items():
        q, k, v = (layer["attn"][x].astype(jnp.float32) for x in "qkv")
        window = arch["window"] if arch["windowed"][int(name[-1])] else None
        if window is not None and "window" in fault:
            window = fault["window"] and window + fault["window"]
        out = reference.banded_attention(
            q, k, v, window, chunk, fault.get("operands")
        )
        faulty[name] = {"attn": {**layer["attn"], "output": out}}
    read = family.attention_distances(reference, arch, chunk, faulty)
    assert float(read[number]) > 0.02
    if "window" in fault:
        assert float(read["attention_global_rel"]) < 1e-6


def test_the_job_holds_the_attention_cores_beside_the_expert_layers():
    job_module = cells.load_module(
        "jobs", "trainstep_attention_checked", (cells.HERE,)
    )
    tolerance = {"loss_abs": 1.0, "grad_norm_rel": 1.0, "attention_rel": 0.01,
                 "router_score_rms": 1e-3, "expert_layer_rel": 1e-2}
    env = types.SimpleNamespace(
        cell=types.SimpleNamespace(workload={"tolerance": tolerance}),
        counters={"dropped_assignments": 0},
    )
    found = {
        "step0": {"loss": 1.0, "grad_norm": 1.0},
        "reference": {"loss": 1.0, "grad_norm": 1.0, "attention_rel": 3e-3,
                      "router_score_rms": 0.0, "expert_layer_rel": 4e-3},
    }
    job = job_module.Job(env)

    def over():
        assert job.check(found, None) == []
        return [p.split()[0] for p in run.over_limit(env.counters["compared"])]

    assert over() == []
    found["reference"]["attention_rel"] = 0.03  # a band a key off
    assert over() == ["attention_rel"]
    found["reference"].update(attention_rel=3e-3, expert_layer_rel=0.07)
    assert over() == ["expert_layer_rel"]
    assert (job_module.STEP_MODULES, job_module.plan) == (
        job_module.trainstep_counted.STEP_MODULES,
        job_module.trainstep_counted.plan,
    )


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "smallthinker-21b-a3b"
    assert entry["traffic"] == "train-16k" and len(entry["why"]) <= 200
    config = next(
        c for c in manifest["configs"] if c["name"] == "smallthinker-21b-a3b"
    )
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout",
    ])
    # "lists the cell", not "lists the cell alone": the next sparse cell joins
    # these lists as this one joined glm-4.7-flash.train-4k's
    listed = {
        m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", [])
    }
    assert {
        "sliding_attention_ms_per_step", "global_attention_ms_per_step",
        "sliding_attention_roofline_pct",
    } <= listed
    shared = {
        m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
        if CELL in m.get("workloads", []) and len(m["workloads"]) > 1
    }
    assert shared >= {
        "tokens_per_s", "input_wait_ms_per_batch.tokens",
        "device_ms_per_step.tokens", "mfu_pct.tokens",
        "device_idle_pct.tokens", "peak_hbm_gb.tokens",
        "window_stall_pct.tokens", "forward_ms_per_step.tokens",
        "backward_ms_per_step.tokens", "optimizer_ms_per_step.tokens",
        "attention_ms_per_step.tokens", "matmul_share_pct.tokens",
        "recompute_ms_per_step", "attention_roofline_pct",
        "grouped_matmul_roofline_pct", "expert_ms_per_step",
        "route_ms_per_step", "expert_load_max_over_mean",
        "dropped_assignments",
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
         "tiny-smallthinker.train", "--seed", "2900000033", "--seconds", "2",
         "--trace", "1", "--rehearse", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, notes = json.loads(lines[-1]), json.loads(lines[-2])
    # ids drawn afresh and evenly every step hold nothing a toy could learn
    # once it is warm: the reference follows the first steps instead
    assert notes["problems"] == []
    assert line["correct"] is True and line["failed"] == 0
    # each number compared beside its limit, last in the line
    assert list(line)[-1] == "compared"
    assert line["compared"]["attention_rel"]["limit"] == 0.1
    assert "loss_last_tenth_less_first" not in line["compared"]
    assert line["compared"]["update_leaf_rel"]["value"] < 0.1
    found = notes["setup"]["reference"]
    limits = cells.load_cell(
        "tiny-smallthinker.train", REHEARSAL
    ).workload["tolerance"]
    assert 0 < found["attention_sliding_rel"] <= found["attention_rel"]
    assert found["attention_rel"] < limits["attention_rel"]
    assert line["attempted"] > 4 and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}
    # no device plane on the CPU: the counters' readers and the host's read
    assert set(line["rehearsed_metrics"]) == {
        "cache_misses", "compile_s", "dropped_assignments",
        "expert_load_max_over_mean", "input_wait_ms_per_batch.tokens",
        "window_stall_pct.tokens",
    }
