"""What the LFM2-8B-A1B cell adds to the benchmark, on the CPU: the family's
arithmetic (507.8 M parameters, the shares of a token's work), the
configuration file against its source's widths, the forwards a trace ran
counted from a hand-made trace and a small HLO text, the two readers over it
where there is something and where there is nothing to read, the family's
distances telling a lower precision and a planted fault, and the new cell
rehearsed through the harness's own command line from a rehearsal directory
of its own. ``tests/test_chipbench_lfm2_moe.py`` imports these, so tier-1
counts them."""

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
REHEARSAL = os.path.join(HERE, "rehearsal_lfm2")
CELL = "lfm2-8b-a1b.train-4k"
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl
REDUCED = [
    "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
    "vocab_size",
]
READERS = ["short_conv_ms_per_step", "short_conv_roofline_pct"]


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
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 7168, "moe_intermediate_size": 1792,
        "num_experts_published": 32, "num_experts_per_tok": 4,
        "rope_theta": 1000000, "norm_eps": 1e-5, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "model_type": "lfm2_moe", "max_position_embeddings": 128000,
    }
    assert {k: config[k] for k in published} == published
    assert config["hidden_size"] // config["num_attention_heads"] == 64
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 16384)
    assert config["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv",
    ]
    assert config["vocab_size"] * 4 == config["vocab_size_published"] == 65536
    assert (config["num_hidden_layers_published"],
            config["num_dense_layers_published"]) == (24, 2)
    assert "4 chips share each layer" in config["deployment"]
    assert "507.8 M parameters" in config["deployment"]
    assumed = config["assumed"]
    assert {
        "tie_word_embeddings", "bias_update", "router_dtype", "auxiliary_loss",
        "dense_width", "qk_norm", "short_conv", "initializer_range",
        "positions",
    } <= set(assumed)
    assert config["tie_word_embeddings"] is True
    assert config["initializer"] == {
        "range": 0.02, "residual_outputs": ["out_proj", "down_proj", "experts_down"],
        "residual_layers": 24, "embedding": ["embed_tokens"],
        "embedding_std": 0.02,
    }


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_there_or_listed_as_reduced(cell):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"
        )
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differs == set(cell.config["reduced"])
    published = row["config"]["layer_types"]
    assert [published[i] for i in (0, 2, 3, 4, 5)] == cell.config["layer_types"]


def test_the_traffic_is_the_cells(cell):
    job = cell.workload["job_params"]
    assert (job["batch"], job["seq"]) == (2, 4096)
    assert job["optimizer"] == {"lr": 1e-5, "clip_grad_norm": 1.0}
    assert (job["precision"], job["policy"], job["mesh"], job["remat"]) == (
        "bf16", "DDP", {"dp": 1}, True
    )
    assert job["reference_query_chunk"] == 256
    workload = cell.workload
    assert workload["job"] == "trainstep_conv_checked"
    assert workload["throughput_metric"] == "tokens_per_s"
    assert workload["follow_steps"] == 3
    assert set(workload["tolerance"]) == {
        "loss_abs", "grad_norm_rel", "grad_leaf_rel", "update_leaf_rel",
        "router_score_rms", "expert_layer_rel", "attention_rel",
        "short_conv_rel", "why",
    }


def test_the_familys_arithmetic_is_the_models(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    p = family.layer_params(config)
    million = lambda x: round(x / 1e6, 2)  # noqa: E731
    taps = 2048 * 3
    assert million(p["conv"] + taps) == 16.78
    assert million(p["attention"]) == 10.49
    assert million(p["dense_mlp"]) == 44.04
    assert million(p["expert"]) == 11.01 and million(8 * p["expert"]) == 88.08
    assert p["router"] == 65536
    dense = p["conv"] + taps + p["dense_mlp"]
    attention_layer = p["attention"] + p["router"] + 8 * p["expert"]
    conv_layer = p["conv"] + taps + p["router"] + 8 * p["expert"]
    embedding = 16384 * 2048
    assert (million(dense), million(attention_layer), million(conv_layer),
            million(embedding)) == (60.82, 98.63, 104.93, 33.55)
    total = dense + attention_layer + 3 * conv_layer + embedding
    assert round(total / 1e6, 1) == 507.8
    assert family.layer_counts(config) == {
        "attention": 1, "conv": 4, "dense": 1, "sparse": 4,
    }
    # forward MACs a token: 208 M, and their shares by kind of layer
    seq = job["seq"]
    per_token = family.train_flops_per_token(config, seq)
    assert round(per_token / 6 / 1e6) == 208
    shares = {
        "conv": 4 * p["conv"], "dense": p["dense_mlp"],
        "experts": 4 * p["expert"], "head": 2048 * 16384,
        "projections": p["attention"],
        "core": 2 * 32 * 64 * family.visible_pairs(seq, None) / seq,
    }
    assert {k: round(100 * v / (per_token / 6)) for k, v in shares.items()} == {
        "conv": 32, "dense": 21, "experts": 21, "head": 16, "projections": 5,
        "core": 4,
    }
    assert round(per_token / 1e9, 2) == 1.25
    conv = family.short_conv_cost(config, job)
    elements = 2 * 4096 * 2048
    assert conv["forward"][1] == 8 * elements + 2048 * 3 * 2
    assert conv["backward"][1] == 14 * elements + 2048 * 3 * 2 + 2 * 3 * 2048 * 4
    # about 0.5 GB a conv layer and step with the rematerialised forward
    assert round((2 * conv["forward"][1] + conv["backward"][1]) / 1e9, 2) == 0.50


def test_kernel_costs_count_the_forwards_the_trace_ran(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    core = family.attention_cost(family.with_head_dim(config), 2, 4096, None)
    conv = family.short_conv_cost(config, job)
    gmm = family.grouped_matmul_cost(config, 32768, 32)
    # a traced step as the compiled text has it (tests/test_chip_compile.py):
    # the attention kernel's forward once (its residuals kept), each conv
    # layer's and each expert layer's forward twice (rematerialised)
    calls = {
        "attention": {"forward": 1, "backward": 1},
        "experts": {"forward": 24, "backward": 24},
        "short_conv": {"forward": 8, "backward": 4},
    }
    assert family.forwards_ran(config, calls) == {
        "attention": 1, "experts": 2, "short_conv": 2,
    }
    costs = family.kernel_costs(config, job, 32768, 32, calls)
    assert costs["attention"] == tuple(
        f + b for f, b in zip(core["forward"], core["backward"])
    )
    assert costs["short_conv"] == tuple(
        4 * (2 * f + b) for f, b in zip(conv["forward"], conv["backward"])
    )
    assert costs["grouped_matmul"] == tuple(4 * x for x in gmm)
    # with nothing traced: one forward of each, the least a step needs
    assert family.forwards_ran(config, None) == {
        "attention": 1, "experts": 1, "short_conv": 1,
    }


def test_the_family_builds_the_model_the_configuration_states(cell, family):
    cfg = family.model_config(cell.config, cell.workload["job_params"])
    assert (cfg.num_experts, cfg.held) == (32, tuple(range(8)))
    assert cfg.head_dim == 64 and cfg.num_key_value_heads == 8
    assert (cfg.num_dense_layers, cfg.num_hidden_layers) == (1, 5)
    assert (cfg.routed_scaling_factor, cfg.bias_update_rate) == (1.0, 0.001)
    assert cfg.remat is True and str(cfg.dtype) == "bfloat16"
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv", "conv")


CONV_LAYERS = (0, 2, 3, 4)


def hlo_text(scope="short_conv"):
    """A small compiled text of the cell's kernels a step: each conv layer's
    convolution forward, rematerialised forward and backward, the attention
    layer's forward and backward, and one fusion under the conv scope."""
    fwd, bwd = "jit(_step)/jvp(Lfm2Moe)", "jit(_step)/transpose(jvp(Lfm2Moe))"
    kernels = []
    for i in CONV_LAYERS:
        kernels += [
            (f"conv.{i}0", f"{fwd}/layers_{i}/conv/{scope}/pallas_call"),
            (f"conv.{i}1",
             f"{bwd}/layers_{i}/conv/rematted_computation/{scope}/pallas_call"),
            (f"conv.{i}2", f"{bwd}/layers_{i}/conv/{scope}/pallas_call"),
        ]
    kernels += [
        ("core.1", f"{fwd}/layers_1/self_attn/attention/pallas_call"),
        ("core.2", f"{bwd}/layers_1/self_attn/attention/pallas_call"),
    ]
    lines = [
        "HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}",
        "",
        "%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {",
        "  %p0.1 = bf16[16,8]{1,0} parameter(0)",
        f'  ROOT %mul.1 = bf16[16,8]{{1,0}} multiply(%p0.1, %p0.1), metadata={{op_name="{fwd}/layers_2/conv/{scope}/mul"}}',
        "}",
        "",
        "ENTRY %main.9 (arg0: bf16[16,8]) -> bf16[16,8] {",
        '  %arg0 = bf16[16,8]{1,0} parameter(0), metadata={op_name="batch[0]"}',
        f'  %fusion.1 = bf16[16,8]{{1,0}} fusion(%arg0), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{fwd}/layers_2/conv/in_proj/dot_general"}}',
    ]
    last = "fusion.1"
    for name, op_name in kernels:
        lines.append(
            f'  %{name} = bf16[16,8]{{1,0}} custom-call(%{last}), '
            f'custom_call_target="tpu_custom_call", metadata={{op_name="{op_name}"}}'
        )
        last = name
    lines.append(
        f'  ROOT %fusion.2 = bf16[16,8]{{1,0}} fusion(%{last}), kind=kLoop, '
        f'calls=%fused_computation.1, metadata={{op_name="{fwd}/layers_2/conv/{scope}/mul"}}'
    )
    return "\n".join(lines + ["}", ""]), [name for name, _ in kernels]


def op(name, start, end):
    kind = name.split(".")[0]
    text = "fusion(%x), kind=kLoop" if kind == "fusion" else "custom-call(%x)"
    return Event(f"%{name} = bf16[16,8]{{1,0}} {text}", start, end)


def joined_cache(steps=2, scope="short_conv"):
    """``steps`` whole executions of the step program in a hand-made trace
    (and the two the window leaves out): a conv kernel 1 s forward, 2 s
    backward, the attention kernels 3 s, the fusions 1 and 0.5 s."""
    text, kernels = hlo_text(scope)
    seconds = {
        name: 2.0 if name.startswith("conv") and name.endswith("2") else
        3.0 if name.startswith("core") else 1.0 for name in kernels
    }
    order = [("fusion.1", 1.0), *seconds.items(), ("fusion.2", 0.5)]
    ops, modules, t = [], [], 0.0
    for _ in range(steps + 2):
        start = t
        for name, length in order:
            ops.append(op(name, t, t + length))
            t += length
        modules.append(Event("jit__step(1)", start, t))
    trace = Trace(ops={0: ops}, async_ops={}, modules={0: modules},
                  host_spans=[])
    return {"joined": (
        trace, {"jit__step": pt.parse_hlo(text)},
        {"steps": steps, "lo": modules[1].start, "hi": modules[-1].start},
    )}


def test_the_forwards_are_counted_from_the_trace(family, monkeypatch):
    """``kernel_calls``: custom-calls under each scope a step by phase, the
    rematerialised one a forward; nothing to join, None."""
    monkeypatch.setattr(named_scopes, "_CACHE", joined_cache(steps=3))
    assert family.kernel_calls() == {
        "attention": {"forward": 1.0, "backward": 1.0},
        "experts": {"forward": 0.0, "backward": 0.0},
        "short_conv": {"forward": 8.0, "backward": 4.0},
    }
    monkeypatch.setattr(named_scopes, "_CACHE", {"joined": None})
    assert family.kernel_calls() is None


def test_the_two_readers_read_their_scope(cell, family, monkeypatch):
    monkeypatch.setattr(named_scopes, "_CACHE", joined_cache(steps=2))
    job = cell.workload["job_params"]
    per_step = {"assignments_landed": [32768.0] * 6, "experts_active": [32.0] * 6}
    ctx = types.SimpleNamespace(
        counters={
            "routing_per_step": per_step,
            "kernel_costs": lambda rows, active: family.kernel_costs(
                cell.config, job, rows, active
            ),
        },
        device_kind="TPU v5 lite", chips=1,
    )
    read = lambda name: cells.load_module(  # noqa: E731
        "layer_metrics", name, (cells.HERE,)
    ).read(ctx)
    # under short_conv: the twelve kernels and the fusion whose root is there
    assert read("short_conv_ms_per_step") == pytest.approx(1e3 * 16.5)
    # 2 forwards a conv layer ran: the cost of four layers against the
    # kernels' 16 s a step
    conv = family.short_conv_cost(cell.config, job)
    least = 4 * (2 * conv["forward"][1] + conv["backward"][1]) / 819e9
    assert read("short_conv_roofline_pct") == pytest.approx(100 * least / 16.0)
    # made once for the tuple of scopes both read
    assert ("short_conv",) in named_scopes._CACHE


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_with_nothing_to_read_returns_none(reader, monkeypatch):
    """No profile (a run without a trace): None, and nothing raises; a
    trace of a program that has no such scope (the parent's, any other
    model's): zeros, so None."""
    monkeypatch.setenv("GRAFT_RUN_DIR", "/nonexistent/run")
    monkeypatch.setattr(named_scopes, "_CACHE", {})
    ctx = types.SimpleNamespace(counters={}, device_kind=None, chips=1)
    module = cells.load_module("layer_metrics", reader, (cells.HERE,))
    assert module.read(ctx) is None
    monkeypatch.setattr(named_scopes, "_CACHE", joined_cache(2, "mixer"))
    ctx = types.SimpleNamespace(
        counters={"routing_per_step": {"assignments_landed": [1.0],
                                       "experts_active": [1.0]},
                  "kernel_costs": lambda rows, active: {}},
        device_kind="TPU v5 lite", chips=1,
    )
    assert module.read(ctx) is None


def tiny_probe(family):
    """The rehearsal's model on a batch of its own, bf16 as the policy casts
    it, kernels interpreted: ``(tiny cell, reference's arch, float32 params,
    zero biases, probe)``."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import lfm2_moe as reference
    from pytorch_distributedtraining_tpu.models import lfm2_moe

    tiny = cells.load_cell("tiny-lfm2.train", REHEARSAL)
    job = tiny.workload["job_params"]
    model = lfm2_moe.Lfm2Moe(family.model_config(tiny.config, job), interpret=True)
    tokens = jnp.asarray(next(family.task(tiny.config, job).batches(3))[0])
    variables = model.init(jax.random.PRNGKey(3), tokens)
    params, bias = variables["params"], variables[lfm2_moe.ROUTER_STATE]
    params = jax.tree_util.tree_map_with_path(  # taps that a fault can move
        lambda path, leaf: leaf * 25.0 if path[-1].key == "weight" else leaf,
        params,
    )
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    probe = model.apply(
        {"params": cast, lfm2_moe.ROUTER_STATE: bias}, tokens,
        mutable=[lfm2_moe.MOE_PROBE],
    )[1][lfm2_moe.MOE_PROBE]
    return tiny, reference.arch_of(tiny.config), params, bias, probe


@pytest.fixture(scope="module")
def readings():
    """``benchmarks/lfm2_precision_readings.py``: the controls the chip's
    upper readings are taken with, planted here on the rehearsal's model."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import lfm2_precision_readings
    finally:
        sys.path.pop(0)
    return lfm2_precision_readings


@pytest.mark.parametrize("lower, number, sound_under, control_over", [
    ({"router": "bfloat16"}, "router_score_rms", 1e-7, 1e-5),
    ({"operands": "float8_e4m3fn"}, "expert_layer_rel", 0.01, 0.03),
])
def test_the_layer_distances_tell_a_lower_precision(
    family, readings, lower, number, sound_under, control_over
):
    import jax.numpy as jnp

    from chipbench.reference import lfm2_moe as reference

    _, arch, params, bias, probe = tiny_probe(family)
    sparse = readings.pick(probe, "moe", "moe")
    assert sorted(sparse) == ["layers_1", "layers_2", "layers_3", "layers_4"]
    lowered = readings.lowered_layers(
        reference, arch, jnp.bfloat16,
        {k: jnp.dtype(v) for k, v in lower.items()}, params, bias, sparse,
    )
    read = lambda found: float(family.expert_layer_distances(  # noqa: E731
        reference, arch, jnp.bfloat16, params, bias, found
    )[number])
    assert read(sparse) < sound_under < control_over < read(lowered)


@pytest.mark.parametrize("fault", ["fp8 e4m3 operands", "rotary left off"])
def test_the_attention_distance_tells_a_planted_fault(family, readings, fault):
    from chipbench.reference import lfm2_moe as reference

    tiny, arch, _, _, probe = tiny_probe(family)
    chunk = tiny.workload["job_params"]["reference_query_chunk"]
    cores = readings.pick(probe, "self_attn", "attn")
    assert sorted(cores) == ["layers_1"]
    sound = family.attention_distances(reference, arch, chunk, cores)
    assert set(sound) == {"attention_rel", "attention_global_rel"}
    assert float(sound["attention_rel"]) < 0.005
    read = family.attention_distances(reference, arch, chunk, readings.faulty_cores(
        reference, arch, chunk, readings.ATTENTION_FAULTS[fault], cores
    ))
    assert float(read["attention_rel"]) > 0.02


@pytest.mark.parametrize("fault", [
    "fp8 e4m3 operands", "taps reversed", "a step late",
])
def test_the_conv_distance_tells_a_planted_fault(family, readings, fault):
    """``short_conv_distances`` on each conv mixer's own ``bcx`` and taps:
    the program's read under 0.5%; the reference with fp8 operands, with
    its taps reversed or a step late reads over 2%."""
    import jax.numpy as jnp

    from chipbench.reference import lfm2_moe as reference

    _, _, params, _, probe = tiny_probe(family)
    convs = readings.pick(probe, "conv", "conv")
    assert sorted(convs) == [f"layers_{i}" for i in (0, 2, 3, 4)]
    read = lambda found: float(family.short_conv_distances(  # noqa: E731
        reference, jnp.bfloat16, params, found
    )["short_conv_rel"])
    assert read(convs) < 0.005
    faulty = readings.faulty_convs(
        reference, jnp.bfloat16, readings.CONV_FAULTS[fault], params, convs
    )
    assert read(faulty) > 0.02


def test_the_reference_moves_the_biases_by_its_own_loads(family):
    import jax
    import jax.numpy as jnp
    import numpy as np

    tiny = cells.load_cell("tiny-lfm2.train", REHEARSAL)
    task = family.task(tiny.config, tiny.workload["job_params"])
    params, model_state = task.init_fn(jax.random.PRNGKey(5))
    batch = jax.tree.map(jnp.asarray, next(task.batches(5)))
    moved = task.reference_state(params, model_state, batch)["router_state"]
    assert sorted(moved) == [f"layers_{i}" for i in range(1, 5)]
    for layer in moved.values():
        step = np.asarray(layer["moe"]["bias"])
        assert step.shape == (8,)
        assert {round(float(x), 6) for x in np.abs(step)} <= {0.0, 0.001}
        assert step.min() < 0 < step.max()
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
    assert "lm_head" not in grads  # the head is the embedding


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [w["name"] for w in manifest["workloads"]]
    entry = manifest["workloads"][names.index(CELL)]
    assert entry["chips"] == 1 and entry["config"] == "lfm2-8b-a1b"
    assert entry["traffic"] == "train-4k" and len(entry["why"]) <= 200
    # appended after the cells already there, nothing moved
    assert names.index(CELL) == names.index("trinity-mini.train-8k") + 1
    configs = [c["name"] for c in manifest["configs"]]
    config = manifest["configs"][configs.index("lfm2-8b-a1b")]
    assert configs.index("lfm2-8b-a1b") == configs.index("trinity-mini") + 1
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    )
    metrics = [m["name"] for m in manifest["per_layer"]]
    at = metrics.index("setup_outside_program_s") + 1
    assert metrics[at:at + 2] == READERS
    new = manifest["per_layer"][at:at + 2]
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
        "grouped_matmul_roofline_pct", "expert_ms_per_step",
        "route_ms_per_step", "expert_load_max_over_mean",
        "dropped_assignments", "startup_trace_s", "startup_lower_s",
        "startup_executable_s", "startup_state_s", "startup_input_s",
        "setup_outside_program_s", *READERS,
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
         "tiny-lfm2.train", "--seed", "2900000039", "--seconds", "2",
         "--trace", "1", "--rehearse", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, notes = json.loads(lines[-1]), json.loads(lines[-2])
    assert notes["problems"] == []
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {
        "loss_abs", "grad_norm_rel", "grad_leaf_rel", "update_leaf_rel",
        "router_score_rms", "expert_layer_rel", "dropped_assignments",
        "attention_rel", "short_conv_rel", "compilations_in_window",
        "steps_failed",
    }
    found = notes["setup"]["reference"]
    assert 0 < found["short_conv_rel"] < 0.01
    assert 0 < found["attention_rel"] < 0.01
    assert found["picks_agree"] > 0.9
    assert line["attempted"] > 4 and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}
    with open(os.path.join(
        ROOT, "chiprun_out", "tiny-lfm2.train.2900000039", "counters.json"
    )) as f:
        per_step = json.load(f)["per_step"]
    assert len(per_step["assignments_landed"]) == line["attempted"]
    assert sum(per_step["dropped_assignments"]) == 0
