"""Compile for a TPU that is described, not attached.

The TPU compiler is installed beside jax and compiles for a ``v5e:2x2`` it
only has a description of (on-chip-measurement guide, section 2, rehearsal
3). Nothing runs, so these say nothing about results or times — but what the
compiler refuses here (a tile Mosaic cannot lay out, more fast memory than a
kernel may use, a libtpu flag it does not know) it refuses on the chip too,
and here it costs no chip time.

The cheap cases are the kernels at the widths the chip runs; they stay in
tier-1. The whole-step compiles (about half a minute each) are marked
``slow``: run them before a chip call that depends on them::

    python -m pytest tests/test_chip_compile.py -m slow

Code that asks ``jax.devices()`` still sees the CPU here, so every case
hands the described devices and shapes to the jitted function itself.
"""

import dataclasses
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 — or skip the module where libtpu cannot give
    one. The persistent cache is off around these compiles: an executable
    for a chip that is not there is written but can never be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _on(device_or_sharding, shape, dtype):
    sh = device_or_sharding
    if not isinstance(sh, jax.sharding.Sharding):
        sh = SingleDeviceSharding(sh)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


# -- kernels at real widths (tier-1) -----------------------------------------

# SwinIR-S, batch 18 of 64x64: qkv [B*nW, n, 3c] as the projection writes it,
# 6 heads of 10; classical SwinIR-M: embed 180, 6 heads of 30
WINDOW = (18 * 64, 64, 180, 6)
WINDOW_M = (18 * 64, 64, 540, 6)
FLASH = (8, 1024, 12, 64)  # GPT-2 125M: [B, T, H, Dh]
# the GPT-2 cells' cores: 12 heads pack two to a lane block, 25 do not
CORE_125M, CORE_XL, CORE_HEAD_128 = (
    (12, 1024, 12, 64), (4, 1024, 25, 64), (4, 1024, 8, 128)
)
# GLM-4.7-Flash's cell: MLA's head of 192 + 64, blocks of 512; K and V ride
# whole in VMEM (2 MiB each), over the default scoped limit in the backward
FLASH_MLA, FLASH_MLA_BLOCK = (2, 4096, 20, 256), 512
GROUPED = (2 * 4096 * 4, 8, 2048, 1536)  # buffer rows, experts held, D, F
# SmallThinker-21B-A3B's cell: 28 query heads of 128 on 4 key-value heads,
# one sequence of 16,384, blocks of 512; K and V ride whole in VMEM (4 MiB
# each), Q, dO and the row statistics whole in dk/dv (the limit is raised)
FLASH_GQA, FLASH_GQA_KV, FLASH_GQA_BLOCK = (1, 16384, 28, 128), 4, 512
# Trinity-Mini's cell: 32 query heads of 128 on 4 key-value heads (8 each),
# one sequence of 8,192, blocks of 512: a band of 2,048 is 4 blocks and the
# two masked edges
FLASH_GQA8, FLASH_GQA8_WINDOW = (1, 8192, 32, 128), 2048
GPT2_HEADS, GPT2_HEAD_DIM, PAGE = 12, 64, 16


def _window(dev, *, mask, grad, dtype=jnp.float32, shape=None):
    from pytorch_distributedtraining_tpu.ops.pallas_window_attn import (
        window_attention_qkv,
    )

    bn, n, c3, heads = shape or WINDOW
    qkv = _on(dev, (bn, n, c3), dtype)
    bias = _on(dev, (heads, n, n), jnp.float32)
    m = _on(dev, (64, n, n), jnp.float32) if mask else None

    def fwd(qkv, bias, m):
        return window_attention_qkv(qkv, bias, m, False)

    if not grad:
        return fwd, (qkv, bias, m)
    return (
        jax.grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)), argnums=(0, 1)
        ),
        (qkv, bias, m),
    )


def _flash(dev, *, dtype, grad, shape=FLASH, block=128, kv_heads=None,
           window=None):
    from pytorch_distributedtraining_tpu.ops.pallas_attn import flash_attention

    qkv = _on(dev, shape, dtype)
    kv = qkv if kv_heads is None else _on(
        dev, (*shape[:2], kv_heads, shape[3]), dtype
    )

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, block, block, False, window)

    if not grad:
        return fwd, (qkv, kv, kv)
    return (
        jax.grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)), argnums=(0, 1, 2)
        ),
        (qkv, kv, kv),
    )


def _causal_qkv(dev, *, shape, grad):
    """The core the default GPT-2 runs on a TPU, over ``qkv`` as ``c_attn``
    wrote it: heads that pack into 128 lanes are read where they lie."""
    from pytorch_distributedtraining_tpu.ops.pallas_attn import (
        causal_attention_qkv,
    )

    b, t, heads, dh = shape
    qkv = _on(dev, (b, t, 3 * heads * dh), jnp.bfloat16)
    fwd = lambda qkv: causal_attention_qkv(qkv, heads)  # noqa: E731
    if not grad:
        return fwd, (qkv,)
    return jax.grad(lambda x: jnp.sum(fwd(x).astype(jnp.float32))), (qkv,)


def _grouped(dev, *, grad):
    """The expert layer's gate matmul over the worst-case buffer."""
    from pytorch_distributedtraining_tpu.ops.grouped_matmul import (
        grouped_matmul,
    )

    rows, groups, d, f = GROUPED
    args = (
        _on(dev, (rows, d), jnp.bfloat16), _on(dev, (groups, d, f), jnp.bfloat16),
        _on(dev, (groups,), jnp.int32),
    )
    if not grad:
        return grouped_matmul, args
    return (
        jax.grad(
            lambda x, w, n: jnp.sum(grouped_matmul(x, w, n).astype(jnp.float32)),
            argnums=(0, 1),
        ),
        args,
    )


def _int8_block():
    from pytorch_distributedtraining_tpu.models.generate import kv_scale_block
    from pytorch_distributedtraining_tpu.serve.kv_cache import kv_wire_format

    fmt = kv_wire_format("int8_block")
    return fmt, kv_scale_block(fmt, GPT2_HEADS, GPT2_HEAD_DIM)


def _paged_decode(dev):
    """The engine's decode-tick attention: 4 slots, one new token each,
    against int8 block-scaled pages for 1,024 positions a slot."""
    from pytorch_distributedtraining_tpu.models.generate import paged_attention

    fmt, blk = _int8_block()
    slots, max_pages = 4, 1024 // PAGE
    n_pages = 1 + slots * max_pages
    n_scales = GPT2_HEADS * GPT2_HEAD_DIM // blk
    pages = _on(
        dev, (n_pages, PAGE, GPT2_HEADS, GPT2_HEAD_DIM), fmt.payload_dtype
    )
    scales = _on(dev, (n_pages, PAGE, n_scales), jnp.float32)
    return (
        lambda q, kp, vp, tbl, ln, ks, vs: paged_attention(
            q, kp, vp, tbl, ln, k_scales=ks, v_scales=vs
        ),
        (
            _on(dev, (slots, 1, GPT2_HEADS, GPT2_HEAD_DIM), jnp.bfloat16),
            pages, pages, _on(dev, (slots, max_pages), jnp.int32),
            _on(dev, (slots,), jnp.int32), scales, scales,
        ),
    )


def _kv_quant_pair(dev):
    from pytorch_distributedtraining_tpu.models.generate import (
        dequantize_kv,
        quantize_kv,
    )

    fmt, blk = _int8_block()

    def roundtrip(x):
        payload, scales = quantize_kv(x, fmt, blk)
        return dequantize_kv(payload, scales, x.dtype)

    return roundtrip, (
        _on(dev, (4, 32, GPT2_HEADS, GPT2_HEAD_DIM), jnp.bfloat16),
    )


def _fp8_dot(dev):
    """GPT-2's MLP-in contraction ([B*T, 768] x [768, 3072]) with e4m3
    operands, forward and both transposed backward matmuls."""
    from pytorch_distributedtraining_tpu.precision import (
        FP8_DTYPES,
        fp8_dot_general,
    )

    dn = (((1,), (0,)), ((), ()))

    def loss(x, w, sx, sw):
        return jnp.sum(fp8_dot_general(x, w, sx, sw, dn, FP8_DTYPES["e4m3"]))

    scale = _on(dev, (), jnp.float32)
    return jax.grad(loss, argnums=(0, 1)), (
        _on(dev, (8 * 1024, 768), jnp.bfloat16),
        _on(dev, (768, 3072), jnp.bfloat16), scale, scale,
    )


KERNEL_CASES = {
    "window_fwd": (lambda d: _window(d, mask=False, grad=False), True),
    "window_fwd_shift_mask": (lambda d: _window(d, mask=True, grad=False), True),
    "window_bwd": (lambda d: _window(d, mask=True, grad=True), True),
    "window_bwd_no_mask": (lambda d: _window(d, mask=False, grad=True), True),
    "window_bwd_bf16": (
        lambda d: _window(d, mask=True, grad=True, dtype=jnp.bfloat16), True,
    ),
    "window_bwd_head_30": (
        lambda d: _window(d, mask=True, grad=True, shape=WINDOW_M), True,
    ),
    "flash_fwd_bf16": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=False), True,
    ),
    "flash_bwd_bf16": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True), True,
    ),
    "flash_bwd_f32": (lambda d: _flash(d, dtype=jnp.float32, grad=True), True),
    "flash_fwd_bf16_mla_head_256": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=False, shape=FLASH_MLA,
                         block=FLASH_MLA_BLOCK), True,
    ),
    "flash_bwd_bf16_mla_head_256": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True, shape=FLASH_MLA,
                         block=FLASH_MLA_BLOCK), True,
    ),
    "flash_bwd_bf16_gqa_16k_window_4096": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True, shape=FLASH_GQA,
                         block=FLASH_GQA_BLOCK, kv_heads=FLASH_GQA_KV,
                         window=4096), True,
    ),
    "flash_bwd_bf16_gqa_16k_full": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True, shape=FLASH_GQA,
                         block=FLASH_GQA_BLOCK, kv_heads=FLASH_GQA_KV), True,
    ),
    "flash_bwd_bf16_gqa8_8k_window_2048": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True, shape=FLASH_GQA8,
                         block=FLASH_GQA_BLOCK, kv_heads=FLASH_GQA_KV,
                         window=FLASH_GQA8_WINDOW), True,
    ),
    "flash_bwd_bf16_gqa8_8k_full": (
        lambda d: _flash(d, dtype=jnp.bfloat16, grad=True, shape=FLASH_GQA8,
                         block=FLASH_GQA_BLOCK, kv_heads=FLASH_GQA_KV), True,
    ),
    "core_qkv_fwd_gpt2_125m": (
        lambda d: _causal_qkv(d, shape=CORE_125M, grad=False), True,
    ),
    "core_qkv_bwd_gpt2_125m": (
        lambda d: _causal_qkv(d, shape=CORE_125M, grad=True), True,
    ),
    "core_qkv_bwd_gpt2_xl_25_heads": (
        lambda d: _causal_qkv(d, shape=CORE_XL, grad=True), True,
    ),
    "core_qkv_bwd_head_128": (
        lambda d: _causal_qkv(d, shape=CORE_HEAD_128, grad=True), True,
    ),
    "grouped_matmul_fwd": (lambda d: _grouped(d, grad=False), True),
    "grouped_matmul_bwd": (lambda d: _grouped(d, grad=True), True),
    "paged_decode_attention_int8": (_paged_decode, False),
    "kv_quantize_dequantize": (_kv_quant_pair, False),
    "fp8_dot_fwd_bwd": (_fp8_dot, False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(topo, case):
    build, is_pallas = KERNEL_CASES[case]
    dev = topo.devices[0]
    assert dev.device_kind == V5E_KIND
    fn, args = build(dev)
    _, text = _compile(fn, *args)
    if is_pallas:  # compiled by Mosaic, not interpreted and not replaced
        assert "tpu_custom_call" in text


def test_libtpu_accepts_latency_hiding_flags():
    """libtpu reads LIBTPU_INIT_ARGS when it is loaded and kills the process
    on a flag it does not know — so load it, in a process of its own, with
    exactly what ``runtime.initialize()`` would set."""
    code = (
        "import os\n"
        "from pytorch_distributedtraining_tpu.runtime import dist\n"
        "os.environ.pop('LIBTPU_INIT_ARGS', None)\n"
        "assert dist.enable_latency_hiding_scheduler()\n"
        "flags = os.environ['LIBTPU_INIT_ARGS'].split()\n"
        "assert flags == list(dist.LATENCY_HIDING_FLAGS), flags\n"
        "from jax.experimental import topologies\n"
        "try:\n"
        "    topologies.get_topology_desc(platform='tpu',\n"
        "                                 topology_name='v5e:2x2')\n"
        "except Exception as e:\n"
        "    print('SKIP', e); raise SystemExit(77)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               TPU_LOG_DIR="disabled")
    env.pop("GRAFT_OVERLAP", None)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
    )
    if out.returncode == 77:
        pytest.skip(f"cannot load libtpu here: {out.stdout[-300:]}")
    assert out.returncode == 0, out.stderr[-2000:]


def test_peak_table_knows_the_chip_and_refuses_strangers(monkeypatch):
    from pytorch_distributedtraining_tpu.observe.goodput import (
        PEAK_FLOPS,
        peak_flops,
    )

    monkeypatch.delenv("GRAFT_PEAK_FLOPS", raising=False)
    assert peak_flops("tpu", V5E_KIND) == PEAK_FLOPS[V5E_KIND] == 197e12
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_flops("tpu", "TPU v9 mega")
    with pytest.raises(ValueError, match="no peak"):
        peak_flops("tpu", "")
    with pytest.raises(ValueError, match="no peak"):
        peak_flops("gpu", "NVIDIA A100")


# -- whole programs at real widths (slow) ------------------------------------


def _abstract_state(init_fn, tx, mesh, policy):
    """What ``create_train_state`` would place on ``mesh``, as shapes with
    shardings: a described device cannot hold an array."""
    from pytorch_distributedtraining_tpu.parallel.spec import tree_shardings
    from pytorch_distributedtraining_tpu.parallel.state import TrainState

    def build(rng):
        params, model_state = init_fn(rng)
        return TrainState(
            step=jnp.int32(0), params=params, opt_state=tx.init(params),
            model_state=model_state, rng=rng, scaler=None,
        )

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    specs = TrainState(
        step=P(), params=policy.params_specs(shapes.params, mesh),
        opt_state=policy.opt_specs(shapes.opt_state, mesh),
        model_state=jax.tree.map(lambda _: P(), shapes.model_state),
        rng=P(), scaler=None,
    )
    return shapes, tree_shardings(specs, mesh)


def _with_shardings(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )


def _gpt2_step(
    mesh, policy, cfg, *, attn_fn=None, step_cls=None, batch=8, **kw
):
    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.models import GPT2, cross_entropy_loss
    from pytorch_distributedtraining_tpu.parallel import TrainStep
    from pytorch_distributedtraining_tpu.precision import Policy as Precision
    from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

    model = GPT2(cfg, **({"attn_fn": attn_fn} if attn_fn else {}))
    init_model = GPT2(cfg)  # same params; init needs no sharded attention
    fp8 = cfg.fp8 is not None

    def init_fn(rng):
        v = dict(init_model.init(rng, jnp.zeros((1, 8), jnp.int32)))
        return v.pop("params"), v

    def loss_fn(params, batch, rng, model_state):
        tok, tgt = batch
        if fp8:
            logits, new = model.apply(
                {"params": params, **model_state}, tok, mutable=["fp8"]
            )
            return cross_entropy_loss(logits, tgt), {"model_state": dict(new)}
        return cross_entropy_loss(model.apply({"params": params}, tok), tgt), {}

    tx = optim.adamw(lr=3e-4, clip_grad_norm=1.0)
    shapes, shardings = _abstract_state(init_fn, tx, mesh, policy)
    if step_cls is None:
        step = TrainStep(
            loss_fn, tx, mesh, policy, state_shardings=shardings,
            precision=Precision.from_name("bf16"), **kw,
        )
    else:
        step = step_cls(loss_fn, tx, mesh, policy, **kw)
    tokens = _on(
        NamedSharding(mesh, batch_spec(mesh)), (batch, 1024), jnp.int32
    )
    return step, _with_shardings(shapes, shardings), (tokens, tokens)


def _lower(step, state, batch):
    with step.mesh:
        compiled = step._jitted.lower(state, batch, jnp.float32(1.0)).compile()
    return compiled, compiled.as_text()


def _mesh(topo, n=None, **axes):
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    devices = topo.devices if n is None else topo.devices[:n]
    return make_mesh(MeshSpec(**axes), devices=devices)


@pytest.mark.parametrize("scan", [False, True])
def test_remat_cuts_the_planned_peak(topo, scan):
    """Per-block remat cuts the step's planned temporaries, unrolled and
    scanned, with the attention kernels' residuals among what ``full``
    keeps. Asked of the TPU's planner: XLA:CPU expands jax.checkpoint's
    barrier before it schedules and hoists an unrolled program's
    recomputation, so its plan for ``full`` is ``none``'s
    (``tests/test_remat_scan.py::test_trainstep_memory_monotonic`` holds the
    scanned layout there)."""
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.parallel import DDP

    temporaries = {}
    for remat in ("none", "full"):
        cfg = GPT2Config.tiny(
            n_embd=128, n_head=2, n_layer=4, n_positions=1024, remat=remat,
            scan_layers=scan,
        )
        step, state, batch = _gpt2_step(_mesh(topo, 1, dp=1), DDP(), cfg)
        compiled, text = _lower(step, state, batch)
        assert _kernel_calls(text) == (3 if scan else 3 * 4)
        temporaries[remat] = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries["full"] < 0.5 * temporaries["none"], temporaries


@pytest.mark.slow
def test_gpt2_125m_train_step_one_chip(topo):
    """``jax.grad`` of GPT-2 125M at [8, 1024] through TrainStep — and
    through the ``create_device_mesh`` branch of ``make_mesh``, which runs
    only for devices whose platform is ``tpu``."""
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.parallel import DDP

    step, state, batch = _gpt2_step(
        _mesh(topo, 1, dp=1), DDP(), GPT2Config.gpt2_125m()
    )
    compiled, text = _lower(step, state, batch)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    # the default model's core on a TPU is the blockwise kernels: a forward
    # and two backward (dq, dk/dv) a layer, and no T x T scores in HBM
    assert _kernel_calls(text) == 3 * 12
    assert _largest_scores(text) is None


@pytest.mark.slow
def test_gpt2_125m_zero3_on_four_chips(topo):
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.observe import hlo
    from pytorch_distributedtraining_tpu.parallel import FSDP

    step, state, batch = _gpt2_step(
        _mesh(topo, fsdp=4), FSDP(), GPT2Config.gpt2_125m()
    )
    _, text = _lower(step, state, batch)
    # XLA:TPU spells the gradient reduce-scatter as all-reduce/all-to-all
    counts = hlo.counts(text)
    assert counts.get("all-reduce") or counts.get("reduce-scatter"), counts
    # ZeRO-3 moves parameters (an activation's gather satisfied a bare
    # count while GSPMD kept the weights in place and gathered the batch):
    # whole kernels arrive, and no gather has the batch as its leading
    # dimension; each chip scores its own 2 of the 8 sequences
    gathered = _gathered_shapes(text)
    assert {(768, 2304), (768, 3072)} <= gathered, gathered
    assert not [s for s in gathered if s[0] == 8], gathered
    # each chip runs the kernels over its own 2 of the 8 sequences, placed
    # by shard_map over the mesh the step published
    assert _kernel_calls(text) == 3 * 12
    assert _largest_scores(text) is None


def _gathered_shapes(text):
    """Result dimensions (1s dropped) of the floating-point all-gathers."""
    from pytorch_distributedtraining_tpu.observe import hlo

    return {
        tuple(int(d) for d in dims.split(",") if d not in ("", "1"))
        for op in hlo.collective_inventory(text) if op.kind == "all-gather"
        for dims in re.findall(
            r"\bb?f\d+\[([0-9,]*)\]", op.line.split(" all-gather", 1)[0]
        )
    }


def _largest_scores(text):
    """The largest [B, H, T, T] tensor a device holds, or None where the
    program holds none (the attention core is the blockwise kernels)."""
    return max(
        (
            tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\bb?f\d+\[(\d+,\d+,1024,1024)\]", text)
        ),
        default=None,
    )


def _kernel_calls(text):
    """Mosaic kernels in a compiled program (a scanned body's count once)."""
    return len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text))


@pytest.mark.slow
def test_gpt2_xl_zero3_at_the_cells_sizes(topo):
    """``gpt2-xl.zero3-4chip`` as the benchmark runs it (16 x 1,024, scan +
    remat): each chip runs the attention kernels over its own 4 sequences
    and all 25 heads (forward, dq, dk/dv: three calls in the scanned bodies;
    four, the forward again, before a rematerialised layer kept the
    kernel's output and row statistics), gathers one scanned layer's
    kernels at a time, and plans under 6 GB of temporaries by
    ``memory_analysis`` (5.68: the 48 layers' kept residuals are 0.82 GB and
    this count takes what the scan hands its backward twice; 4.04 before,
    7.6 while the batch was gathered instead)."""
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.parallel import ZeRO3

    cfg = dataclasses.replace(
        GPT2Config.gpt2_xl(), scan_layers=True, remat=True
    )
    step, state, batch = _gpt2_step(_mesh(topo, fsdp=4), ZeRO3(), cfg, batch=16)
    compiled, text = _lower(step, state, batch)
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9
    assert _kernel_calls(text) == 3
    # the row statistics are kept as whole 128-lane rows: [.., 1024, 8] as
    # the kernels read it is padded to 16 times its bytes (2.6 GB here)
    assert "f32[48,6656,128]" in text
    assert not re.findall(r"f32\[48,4,26,1024,8\]", text)
    assert _largest_scores(text) is None
    # c_attn's sharded matmul lands its gathered column slices in a T-minor
    # ``qkv``; row-major (what the kernels' operands would hand back through
    # a pad alone) they land at lane offsets of 1,200: 59 ms a step
    landed = set(re.findall(
        r"bf16\[4,1024,4800\]\{([0-9,]+)[^ ]* dynamic-update-slice\(", text
    ))
    assert landed == {"1,2,0"}, landed
    gathered = _gathered_shapes(text)
    assert {(1600, 4800), (1600, 6400), (1600, 1600)} <= gathered, gathered
    assert not [s for s in gathered if s[0] == 16], gathered


def _cell_step(topo, name):
    """(compiled text, planned bytes) of a cell's step as the benchmark
    builds it (its family, its job's ``plan``) for one described chip."""
    from chipbench import cells
    from chipbench import plan as planner

    cell = cells.load_cell(name)
    family = cells.load_module("families", cell.config["family"], cell.roots)
    job = cells.load_module("jobs", cell.workload["job"], cell.roots)
    kept = {}

    def compile_and_keep(jitted, *args):
        compiled = jitted.lower(*args).compile()
        kept["text"], kept["memory"] = (
            compiled.as_text(), compiled.memory_analysis()
        )
        return {}

    original, planner.compile_plan = planner.compile_plan, compile_and_keep
    try:
        job.plan(cell, family, list(topo.devices)[:1])
    finally:
        planner.compile_plan = original
    mem = kept["memory"]
    return kept["text"], mem.temp_size_in_bytes + mem.argument_size_in_bytes


def _attention_calls(text):
    return [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "/attention/" in line
    ]


@pytest.mark.slow
def test_smallthinker_cell_step_one_chip(topo):
    """``smallthinker-21b-a3b.train-16k`` as the benchmark builds it (its
    family, its job's ``plan``): every attention core a kernel (per layer a
    forward, dq and dk/dv: 12; a rematerialised layer keeps the forward's
    output and row statistics, and ran it again, 16, before it did), no
    T x T scores, no copy of k or v for the seven query heads that share
    them, and at least a quarter of the chip filled by the step's own
    plan."""
    text, planned = _cell_step(topo, "smallthinker-21b-a3b.train-16k")
    assert 4e9 < planned < 15.75e9
    attention = _attention_calls(text)
    assert len(attention) == 12
    assert sum("/attention_sliding/" in line for line in attention) == 9
    assert sum("/attention_global/" in line for line in attention) == 3
    assert _kernel_calls(text) == 12 + 4 * 12  # and the grouped matmuls
    assert not re.findall(r"\[(?:\d+,)*16384,16384\]", text)
    # k and v stay at 4 heads: nothing of [.., 28, ..] is made from them
    assert not re.findall(
        r"bf16\[1,28,16384,128\]\S* broadcast\(|"
        r"bf16\[1,16384,4,7,128\]", text
    )


@pytest.mark.slow
def test_smallthinker_cell_step_moves_only_the_rows_that_land(topo):
    """The same step (PR 33): the held-experts layer's dispatch and combine
    are row loops over buffers that are allocated, not filled, and combine
    makes no float32 copy of the whole N x k buffer; the attention kernels
    are the 12 of the test above."""
    text, _ = _cell_step(topo, "smallthinker-21b-a3b.train-16k")
    assert "f32[16384,6,2560]" not in text
    buffers = [
        line for line in text.splitlines()
        if "AllocateBuffer" in line and "bf16[98304,2560]" in line
    ]
    assert buffers and all(
        "/dispatch/" in line or "/combine/" in line for line in buffers
    )
    assert len(_attention_calls(text)) == 12


@pytest.mark.slow
def test_glm_cell_step_one_chip(topo):
    """``glm-4.7-flash.train-4k`` as the benchmark builds it: five layers,
    each attention core a kernel run once forward and twice backward (15
    calls; 20 while the rematerialised forward ran again), no T x T scores,
    and at least a quarter of the chip filled by the step's own plan."""
    text, planned = _cell_step(topo, "glm-4.7-flash.train-4k")
    assert 4e9 < planned < 15.75e9
    assert len(_attention_calls(text)) == 15
    assert not re.findall(r"\[(?:\d+,)*4096,4096\]", text)


@pytest.mark.slow
def test_trinity_mini_cell_step_one_chip(topo):
    """``trinity-mini.train-8k`` as the benchmark builds it (its family, its
    job's ``plan``): every attention core a kernel (per layer a forward, dq
    and dk/dv: 15, 12 of them in the four sliding layers; 20 and 16 while
    the rematerialised forward ran again), no T x T scores, no copy of k or
    v for the eight query heads
    that share them, the held-experts layer's buffers allocated and not
    filled, and at least a quarter of the chip filled by the step's own
    plan."""
    text, planned = _cell_step(topo, "trinity-mini.train-8k")
    assert 4e9 < planned < 15.75e9
    attention = _attention_calls(text)
    assert len(attention) == 15
    assert sum("/attention_sliding/" in line for line in attention) == 12
    assert sum("/attention_global/" in line for line in attention) == 3
    assert _kernel_calls(text) == 15 + 4 * 12  # and the grouped matmuls
    assert not re.findall(r"\[(?:\d+,)*8192,8192\]", text)
    # k and v stay at 4 heads: nothing of [.., 32, ..] is made from them
    assert not re.findall(
        r"bf16\[1,32,8192,128\]\S* broadcast\(|"
        r"bf16\[1,8192,4,8,128\]", text
    )
    # 8 picks a token: buffers of 65,536 rows, allocated under dispatch and
    # combine alone, and no float32 copy of one
    assert "f32[8192,8,2048]" not in text
    buffers = [
        line for line in text.splitlines()
        if "AllocateBuffer" in line and "bf16[65536,2048]" in line
    ]
    assert buffers and all(
        "/dispatch/" in line or "/combine/" in line for line in buffers
    )
    # the scopes the new readers look for are in the compiled names
    for scope in ("/qk_norm/", "/attention_gate/", "/post_norm/"):
        assert scope in text, scope


@pytest.mark.slow
def test_gpt2_block_with_fp8_dots(topo):
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.parallel import DDP

    cfg = dataclasses.replace(GPT2Config.gpt2_125m(), n_layer=1, fp8="e4m3")
    step, state, batch = _gpt2_step(_mesh(topo, 1, dp=1), DDP(), cfg)
    _, text = _lower(step, state, batch)
    assert "f8e4m3fn" in text and "f8e5m2" in text


@pytest.mark.slow
def test_fp8_wire_grad_step_on_four_chips(topo):
    """Block-scaled fp8 gradient wire (parallel/compressed.py)."""
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.parallel import DDP, CompressedGradStep

    cfg = dataclasses.replace(GPT2Config.gpt2_125m(), n_layer=2)
    step, state, batch = _gpt2_step(
        _mesh(topo, dp=4), DDP(), cfg,
        step_cls=CompressedGradStep, wire="fp8_e4m3",
    )
    residuals = jax.eval_shape(step.init_residuals, state.params)
    state = state.replace(model_state={"grad_residual": jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p.sharding),
        residuals, state.params,
    )})
    _, text = _lower(step, state, batch)
    assert "f8e4m3fn" in text


@pytest.mark.slow
def test_ring_attention_step_on_sp4(topo):
    from pytorch_distributedtraining_tpu.models import GPT2Config
    from pytorch_distributedtraining_tpu.observe import hlo
    from pytorch_distributedtraining_tpu.ops import make_ring_attn_fn
    from pytorch_distributedtraining_tpu.parallel import DDP

    mesh = _mesh(topo, sp=4)
    cfg = dataclasses.replace(GPT2Config.gpt2_125m(), n_layer=2)
    step, state, batch = _gpt2_step(
        mesh, DDP(), cfg, attn_fn=make_ring_attn_fn(mesh)
    )
    _, text = _lower(step, state, batch)
    assert hlo.counts(text).get("collective-permute"), hlo.counts(text)


@pytest.mark.slow
def test_1f1b_pipeline_step_on_pp4(topo):
    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.models.gpt2 import Block, GPT2Config
    from pytorch_distributedtraining_tpu.observe.hlo import pipeline_audit
    from pytorch_distributedtraining_tpu.parallel import (
        PipelineStep,
        Policy,
        pipeline_state_shardings,
        stack_stage_params,
    )

    mesh = _mesh(topo, pp=4)
    cfg, n_micro, t = GPT2Config.gpt2_125m(), 8, 1024
    block = Block(cfg)
    x0 = jnp.zeros((1, 8, cfg.n_embd))

    def init_fn(rng):
        return {"h": stack_stage_params([
            block.init(jax.random.fold_in(rng, i), x0)["params"]
            for i in range(4)
        ])}, {}

    tx = optim.adamw(lr=1e-3)
    shapes, shardings = _abstract_state(init_fn, tx, mesh, Policy())
    shardings = pipeline_state_shardings(shardings, shapes, mesh, "h")
    step = PipelineStep(
        lambda p, x: Block(cfg).apply({"params": p}, x), tx, mesh, Policy(),
        n_micro=n_micro, schedule="1f1b", stages_key="h",
        embed_fn=lambda other, mb, rng: mb,
        head_fn=lambda other, y, mb, rng: jnp.mean(y.astype(jnp.float32) ** 2),
        state_shardings=shardings,
    )
    batch = _on(
        NamedSharding(mesh, P()), (n_micro, t, cfg.n_embd), jnp.bfloat16
    )
    _, text = _lower(step, _with_shardings(shapes, shardings), batch)
    assert pipeline_audit(text, step.schedule, mesh=mesh).ok


@pytest.mark.slow
def test_serve_decode_and_spec_verify_int8_kv(topo, monkeypatch):
    """The engine's decode program and its [n_slots, spec_k] verify program
    at GPT-2 125M widths over int8 block-scaled pages, donation on (the
    engine asks ``jax.default_backend()``, which says cpu here)."""
    from pytorch_distributedtraining_tpu.models import GPT2, GPT2Config
    from pytorch_distributedtraining_tpu.serve.engine import ServeEngine

    monkeypatch.setattr(ServeEngine, "_donate", lambda self: (1,))
    cfg = GPT2Config.gpt2_125m()
    dev = topo.devices[0]
    params = jax.eval_shape(
        lambda r: GPT2(cfg).init(r, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    eng = ServeEngine(
        cfg, params, n_slots=4, page_size=PAGE, max_len=256, spec_k=4,
        kv_wire="int8_block", temperature=0.0,
    )
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _on(dev, s.shape, s.dtype), tree
    )
    slots = (
        _on(dev, (4, eng.max_pages), jnp.int32), _on(dev, (4,), jnp.int32),
    )
    key = _on(dev, (2,), jnp.uint32)
    eng._decode_fn.lower(
        put(params), put(eng._pages), _on(dev, (4, 1), jnp.int32), *slots, key
    ).compile()
    eng._spec_fn.lower(
        put(params), put(eng._pages), _on(dev, (4, 4), jnp.int32), *slots
    ).compile()
    eng._prefill_fns[32].lower(
        put(params), put(eng._pages), _on(dev, (1, 32), jnp.int32),
        _on(dev, (1, eng.max_pages), jnp.int32), _on(dev, (1,), jnp.int32),
        _on(dev, (), jnp.int32), key,
    ).compile()


def _swinir_s_step(mesh, batch, *, dtype=jnp.float32, precision="fp32", **kw):
    """Full-width SwinIR-S (the constructor of drivers/stoke_ddp.py, which
    names no attention) on 64x64 inputs through TrainStep on ``mesh``."""
    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.losses import mse_loss
    from pytorch_distributedtraining_tpu.models import SwinIR
    from pytorch_distributedtraining_tpu.parallel import DDP, TrainStep
    from pytorch_distributedtraining_tpu.precision import Policy as Precision
    from pytorch_distributedtraining_tpu.runtime.mesh import batch_spec

    net = SwinIR(
        upscale=2, in_chans=3, img_size=64, window_size=8, img_range=1.0,
        depths=[6, 6, 6, 6], embed_dim=60, num_heads=[6, 6, 6, 6],
        mlp_ratio=2, upsampler="pixelshuffledirect",
        resi_connection="1conv", dtype=dtype, **kw,
    )
    tx = optim.adamw(lr=1e-3, clip_grad_norm=0.1)
    shapes, shardings = _abstract_state(
        lambda r: (net.init(r, jnp.zeros((1, 64, 64, 3)))["params"], {}),
        tx, mesh, DDP(),
    )
    step = TrainStep(
        lambda p, b, rng, ms: (
            mse_loss(net.apply({"params": p}, b[0]), b[1]), {},
        ),
        tx, mesh, DDP(), state_shardings=shardings,
        precision=Precision.from_name(precision),
    )
    data = NamedSharding(mesh, batch_spec(mesh))
    return step, _with_shardings(shapes, shardings), (
        _on(data, (batch, 64, 64, 3), jnp.float32),
        _on(data, (batch, 128, 128, 3), jnp.float32),
    )


def _window_kernels(text):
    """First result's shape of each Mosaic call: the forward's [bn, n, c],
    the backward's d qkv [bn, n, 3c]."""
    return [
        tuple(
            int(d) for d in
            re.search(r"f32\[(\d+,\d+,\d+)\]", line).group(1).split(",")
        )
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.slow
def test_swinir_s_train_step_batch18(topo):
    """SwinIR-S, batch 18 of 64x64, float32. The program is lowered for the
    described v5e, so the default path takes the fused window kernel: 24
    forward + 24 backward kernels, and fewer planned temporaries than the
    einsums, whose [1152, 6, 64, 64] scores and head-of-10 layouts are
    15.18 of the chip's 15.75 GB for the two microbatches of
    `chipbench/workloads/swinir-s-x2.fused-step.json`. The compiler's
    memory plan decides how large a batch a cell can use. bf16 compiles
    too, and plans less again.
    """
    mesh = _mesh(topo, 1, dp=1)
    plans = {}
    for label, kw in (
        ("fp32/einsum", dict(attn_impl="xla")),
        ("fp32/default", {}),
        ("bf16/default", dict(dtype=jnp.bfloat16, precision="bf16")),
    ):
        compiled, text = _lower(*_swinir_s_step(mesh, 18, **kw))
        kernels = text.count('custom_call_target="tpu_custom_call"')
        assert kernels == (0 if label == "fp32/einsum" else 48), (label, kernels)
        plans[label] = compiled.memory_analysis().temp_size_in_bytes
    print("SwinIR-S batch 18 of 64x64, planned temporaries (bytes):", plans)
    assert plans["fp32/default"] < plans["fp32/einsum"] < 15.75e9, plans
    assert plans["bf16/default"] < plans["fp32/default"], plans


@pytest.mark.slow
def test_swinir_s_default_on_four_chips_keeps_each_chips_windows(topo):
    """The stoke driver's program on a 2x2: batch 72 over dp=4. The
    partitioner cannot split a Mosaic kernel (it refuses the program), so
    the default path places it itself, over the mesh the step publishes:
    48 kernels, each over one chip's 18 x 64 windows, and no activation is
    gathered (the only collective is the gradients' all-reduce, which
    carries the kernels' d bias too)."""
    from pytorch_distributedtraining_tpu.observe import hlo

    _, text = _lower(*_swinir_s_step(_mesh(topo, dp=4), 72))
    shapes = _window_kernels(text)
    assert len(shapes) == 48, shapes
    assert set(shapes) == {(1152, 64, 60), (1152, 64, 180)}, set(shapes)
    assert not _gathered_shapes(text), _gathered_shapes(text)
    assert set(hlo.counts(text)) <= {"all-reduce"}, hlo.counts(text)
