"""What the Keye-VL-2.0-30B-A3B cell adds to the benchmark, on the CPU: the
family's arithmetic (465.4 M parameters, the selected and the causal pairs),
the configuration file against its source's widths, the sparse kernels'
seconds read by their own names from a hand-made trace and a small HLO
text, the four readers over it where there is something and where there is
nothing to read, the family's distances telling planted faults, and the new
cell rehearsed through the harness's own command line from a rehearsal
directory of its own. ``tests/test_chipbench_keye_vl2.py`` imports these, so
tier-1 counts them."""

import functools
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
REHEARSAL = os.path.join(HERE, "rehearsal_keye")
CELL = "keye-vl-2.0-30b-a3b.train-16k"
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
READERS = [
    "indexer_ms_per_step", "sparse_attention_ms_per_step",
    "indexer_roofline_pct", "sparse_attention_roofline_pct",
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
        "num_key_value_heads": 4, "head_dim": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "num_experts_published": 128, "intermediate_size": 6144,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                         "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "tie_word_embeddings": False, "model_type": "KeyeVL2",
    }
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_local_experts"], config["vocab_size"]) == (
        4, 16, 16, 18992
    )
    assert config["vocab_size_published"] == 8 * config["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_there_or_listed_as_reduced(cell):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B"
        )
    assert cell.config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.config.get(k) != v}
    assert differs == set(cell.config["reduced"])


def test_the_traffic_is_the_cells(cell):
    job = cell.workload["job_params"]
    assert (job["batch"], job["seq"]) == (1, 16384)
    assert job["optimizer"] == {"lr": 1e-5, "clip_grad_norm": 1.0}
    assert (job["precision"], job["policy"], job["mesh"], job["remat"]) == (
        "bf16", "DDP", {"dp": 1}, True
    )
    workload = cell.workload
    assert workload["job"] == "trainstep_sparse_checked"
    assert workload["throughput_metric"] == "tokens_per_s"
    assert workload["follow_steps"] == 3
    assert set(workload["tolerance"]) == {
        "loss_abs", "grad_norm_rel", "grad_leaf_rel", "update_leaf_rel",
        "router_score_rms", "expert_layer_rel", "sparse_attention_rel",
        "indexer_score_rel", "selection_agree", "indexer_kl_abs", "why",
    }


def test_the_familys_arithmetic_is_the_models(cell, family):
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.keye_vl2 import KeyeVL2

    config, job = cell.config, cell.workload["job_params"]
    cfg = family.model_config(config, job)
    shapes = jax.eval_shape(
        lambda: KeyeVL2(cfg, interpret=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 512), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 465_391_104
    # the selection hands the core 31.46 M of the 134.2 M causal pairs
    assert family.selected_pairs(16384, 2048) == 31_458_304
    assert family.visible_pairs(16384, None) == 134_225_920
    assert family.selected_pairs(100, 2048) == family.visible_pairs(100, None)
    # the mechanism's forward MACs a layer: scoring 137 G, the core 258 G
    flops, nbytes = family.indexer_cost(config, job)
    assert flops / 2 == 134_225_920 * 16 * 64
    assert nbytes > 16384 * 16384 // 8  # the selection's bits
    core = family.sparse_attention_cost(config, job)
    assert core["forward"][0] / 2 == 31_458_304 * 32 * 128 * 2
    assert core["backward"][0] == 2 * core["forward"][0]
    per_token = family.train_flops_per_token(config, 16384)
    layer = family.layer_params(config)
    assert layer["indexer"] == 2048 * (1024 + 64 + 16)
    matmuls = 2 * (layer["attention"] + layer["indexer"] + layer["router"]
                   + layer["expert"])  # 8 of 128 a token, 16 held: 1 expert
    core_token = 2 * 2 * 32 * 128 * 31_458_304 / 16384
    scoring_token = 2 * 16 * 64 * 134_225_920 / 16384
    assert per_token == pytest.approx(3 * (
        4 * (matmuls + core_token + scoring_token) + 2 * 2048 * 18992
    ))


def test_the_grouped_matmuls_cost_what_the_rows_that_landed_need(cell, family):
    """Each held expert's three matrices at 2,048 x 768; the forward twice
    (the layer is rematerialised) and the backward twice the forward."""
    rows, active = 4 * 16384.0, 64.0
    flops, nbytes = family.kernel_costs(
        cell.config, cell.workload["job_params"], rows, active
    )["grouped_matmul"]
    assert flops == 4 * 3 * 2 * rows * 2048 * 768
    assert nbytes == 4 * (
        active * 3 * 2048 * 768 + rows * (2 * 2048 + 3 * 768 + 2048)
    ) * 2


def test_the_family_builds_the_model_the_configuration_states(cell, family):
    cfg = family.model_config(cell.config, cell.workload["job_params"])
    assert (cfg.num_experts, cfg.held) == (128, tuple(range(16)))
    assert (cfg.indexer_topk, cfg.indexer_block) == (2048, 512)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim) == (16, 64)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7
    assert cfg.remat is True and str(cfg.dtype) == "bfloat16"


FWD, BWD = "jit(_step)/jvp(KeyeVL2)", "jit(_step)/transpose(jvp(KeyeVL2))"
KERNELS = [
    ("sparse_select.1", f"{FWD}/layers_0/self_attn/indexer/pallas_call", 1.0),
    ("sparse_core_fwd.2",
     f"{FWD}/layers_0/self_attn/attention/sparse_attention/pallas_call", 2.0),
    ("sparse_kl_fwd.3", f"{FWD}/layers_0/self_attn/indexer/pallas_call", 0.5),
    ("sparse_core_dq.4",
     f"{BWD}/layers_0/self_attn/attention/sparse_attention/pallas_call", 3.0),
    ("sparse_core_dkv.5",
     f"{BWD}/layers_0/self_attn/attention/sparse_attention/pallas_call", 4.0),
]


def joined_cache(steps=2, kernels=KERNELS):
    """``steps`` whole executions of a step program in a hand-made trace
    (and the two the window leaves out): each kernel of one layer as long as
    ``kernels`` says, and a fusion of 1 s under ``indexer``."""
    lines = [
        "HloModule jit__step, is_scheduled=true, entry_computation_layout={()->()}",
        "",
        "%fused_computation.1 (p0.1: bf16[16,8]) -> bf16[16,8] {",
        "  %p0.1 = bf16[16,8]{1,0} parameter(0)",
        f'  ROOT %mul.1 = bf16[16,8]{{1,0}} multiply(%p0.1, %p0.1), metadata={{op_name="{FWD}/layers_0/self_attn/indexer/mul"}}',
        "}",
        "",
        "ENTRY %main.9 (arg0: bf16[16,8]) -> bf16[16,8] {",
        '  %arg0 = bf16[16,8]{1,0} parameter(0), metadata={op_name="batch[0]"}',
        f'  %fusion.1 = bf16[16,8]{{1,0}} fusion(%arg0), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{FWD}/layers_0/self_attn/indexer/mul"}}',
    ]
    last = "fusion.1"
    for name, op_name, _ in kernels:
        lines.append(
            f'  %{name} = bf16[16,8]{{1,0}} custom-call(%{last}), '
            f'custom_call_target="tpu_custom_call", metadata={{op_name="{op_name}"}}'
        )
        last = name
    lines += [f"  ROOT %copy.1 = bf16[16,8]{{1,0}} copy(%{last})", "}", ""]
    text = "\n".join(lines)

    def op(name, start, end):
        kind = "fusion(%x), kind=kLoop" if name.startswith("fusion") else "custom-call(%x)"
        return Event(f"%{name} = bf16[16,8]{{1,0}} {kind}", start, end)

    order = [("fusion.1", 1.0)] + [(n, s) for n, _, s in kernels]
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


def _ctx(cell, family):
    config, job = cell.config, cell.workload["job_params"]
    return types.SimpleNamespace(
        counters={"sparse_roofline": functools.partial(
            family.roofline, config, job
        )},
        device_kind="TPU v5 lite", chips=1,
    )


def _read(name, ctx):
    return cells.load_module("layer_metrics", name, (cells.HERE,)).read(ctx)


def test_the_kernels_are_read_by_their_own_names(cell, family, monkeypatch):
    monkeypatch.setattr(named_scopes, "_CACHE", joined_cache(steps=2))
    found = family.kernel_seconds()
    assert found["sparse_select"] == {"seconds": 1.0, "calls": 1.0}
    assert found["sparse_core_dkv"] == {"seconds": 4.0, "calls": 1.0}
    ctx = _ctx(cell, family)
    # the selection and the KL's value, and the fusion under the scope
    assert _read("indexer_ms_per_step", ctx) == pytest.approx(1e3 * 2.5)
    assert _read("sparse_attention_ms_per_step", ctx) == pytest.approx(1e3 * 9.0)
    config, job = cell.config, cell.workload["job_params"]
    # one layer's kernels were traced against the cell's four layers' work
    flops, nbytes = family.indexer_cost(config, job)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("indexer_roofline_pct", ctx) == pytest.approx(100 * least / 1.0)
    core = family.sparse_attention_cost(config, job)
    work = 4 * (0.25 * core["forward"][0] + core["backward"][0])
    assert _read("sparse_attention_roofline_pct", ctx) == pytest.approx(
        100 * (work / 197e12) / 9.0
    )


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_with_nothing_to_read_returns_none(cell, family, reader,
                                                    monkeypatch):
    """No profile (a run without a trace): None, and nothing raises; a
    trace of a program without the sparse kernels (the parent's, any other
    model's): None; no roofline in the job's counters: None."""
    monkeypatch.setenv("GRAFT_RUN_DIR", "/nonexistent/run")
    monkeypatch.setattr(named_scopes, "_CACHE", {})
    assert _read(reader, _ctx(cell, family)) is None
    other = [("core.2", f"{FWD}/layers_0/self_attn/attention/pallas_call", 2.0)]
    monkeypatch.setattr(named_scopes, "_CACHE", joined_cache(2, other))
    if reader != "indexer_ms_per_step":  # the fusion is under indexer
        assert _read(reader, _ctx(cell, family)) is None
    ctx = types.SimpleNamespace(counters={}, device_kind="TPU v5 lite", chips=1)
    if reader.endswith("roofline_pct"):
        assert _read(reader, ctx) is None


def tiny_probe(family):
    """The rehearsal's model on a batch of its own, bf16 as the policy casts
    it, kernels interpreted: ``(reference's arch, probe)``."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import keye_vl2 as reference
    from pytorch_distributedtraining_tpu.models import keye_vl2

    tiny = cells.load_cell("tiny-keye.train", REHEARSAL)
    job = tiny.workload["job_params"]
    model = keye_vl2.KeyeVL2(family.model_config(tiny.config, job), interpret=True)
    tokens = jnp.asarray(next(family.task(tiny.config, job).batches(3))[0])
    params = model.init(jax.random.PRNGKey(3), tokens)["params"]
    params = jax.tree.map(lambda a: (a * 5.0).astype(jnp.bfloat16), params)
    probe = model.apply(
        {"params": params}, tokens, mutable=[keye_vl2.MOE_PROBE]
    )[1][keye_vl2.MOE_PROBE]
    return reference, reference.arch_of(tiny.config), probe


@pytest.fixture(scope="module")
def probed(family):
    return tiny_probe(family)


def test_the_distances_tell_planted_faults(family, probed):
    reference, arch, probe = probed
    sound = family.sparse_distances(reference, arch, probe)
    assert float(sound["sparse_attention_rel"]) < 0.02
    assert float(sound["indexer_score_rel"]) < 1e-4
    assert float(sound["indexer_kl_abs"]) < 1e-3
    assert float(sound["selection_agree"]) > 0.95

    def planted(**change):
        faulty = {
            name: {**layer, "self_attn": {**layer["self_attn"], **{
                k: f(layer["self_attn"]) for k, f in change.items()
            }}} for name, layer in probe.items()
        }
        return family.sparse_distances(reference, arch, faulty)

    # a selection with keys added to every other query (the causal past
    # and the future alike): the core read other keys than these and the
    # reference's top-k holds few of the new ones
    widened = planted(selection=lambda a: a["selection"] | 0x55555555)
    assert float(widened["sparse_attention_rel"]) > 10 * float(
        sound["sparse_attention_rel"]
    )
    assert float(widened["selection_agree"]) < 0.9
    # the KL a tenth off, and lse_I shifted by a hundredth
    assert float(planted(kl=lambda a: a["kl"] * 1.1)["indexer_kl_abs"]) > 10 * float(
        sound["indexer_kl_abs"]
    )
    shifted = planted(lse_i=lambda a: a["lse_i"] + 0.01)
    assert float(shifted["indexer_score_rel"]) > 100 * float(
        sound["indexer_score_rel"]
    )


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [w["name"] for w in manifest["workloads"]]
    entry = manifest["workloads"][names.index(CELL)]
    assert entry["chips"] == 1 and entry["config"] == "keye-vl-2.0-30b-a3b"
    assert entry["traffic"] == "train-16k" and len(entry["why"]) <= 200
    # appended after the cells already there, nothing moved
    assert names.index(CELL) == names.index("lfm2-8b-a1b.train-4k") + 1
    configs = [c["name"] for c in manifest["configs"]]
    config = manifest["configs"][configs.index("keye-vl-2.0-30b-a3b")]
    assert configs.index("keye-vl-2.0-30b-a3b") == configs.index("lfm2-8b-a1b") + 1
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    )
    metrics = [m["name"] for m in manifest["per_layer"]]
    at = metrics.index("short_conv_roofline_pct") + 1
    assert metrics[at:at + 4] == READERS
    new = manifest["per_layer"][at:at + 4]
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
        "recompute_ms_per_step", "grouped_matmul_roofline_pct",
        "expert_ms_per_step", "route_ms_per_step",
        "expert_load_max_over_mean", "dropped_assignments",
        "startup_trace_s", "startup_lower_s", "startup_executable_s",
        "startup_state_s", "startup_input_s", "setup_outside_program_s",
        *READERS,
    }
    # the two runtime metrics list no cells, so every cell reports them
    for m in manifest["per_layer"]:
        if m["name"] in ("compile_s", "cache_misses"):
            assert "workloads" not in m and m["moves"] == "setup_s"
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
         "tiny-keye.train", "--seed", "2900000042", "--seconds", "2",
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
        "sparse_attention_rel", "indexer_score_rel", "indexer_kl_abs",
        "selection_disagree", "selected_keys_missing",
        "compilations_in_window", "steps_failed",
    }
    found = notes["setup"]["reference"]
    assert 0 < found["sparse_attention_rel"] < 0.01
    assert found["selection_agree"] > 0.95
    assert line["compared"]["selected_keys_missing"]["value"] == 0
    assert line["attempted"] > 4 and line["device"]["platform"] == "cpu"
    with open(os.path.join(
        ROOT, "chiprun_out", "tiny-keye.train.2900000042", "counters.json"
    )) as f:
        per_step = json.load(f)["per_step"]
    assert len(per_step["selected_keys"]) == line["attempted"]
    assert per_step["indexer_kl"][0] > 0
