"""Compiled-HLO collective assertions per parallel policy.

The ZeRO/TP runtime tests prove convergence and shard layouts; these pin
the *communication pattern* the compiler actually emitted — catching GSPMD
silently replicating (a grad constraint backing off to full-tensor
all-reduce plus full-size update math), which a loss curve cannot see.

Reference framing: torch DDP's C++ Reducer and fairscale's ShardedDDP
hand-place their NCCL all-reduce / reduce-scatter calls
(`/root/reference/Fairscale-DDP.py:86-89` picks the wrapper; the wrapper
picks the wire plan). Under XLA the wire plan is a compiler decision, so
it gets an assertion surface instead.

Backend note (see observe/hlo.py): XLA:CPU lacks the reduce-scatter
rewrite, so ZeRO-2's grad constraint legitimately compiles here as the
logical form — one (tuple-combined) all-reduce whose consumers
dynamic-slice down to the shard before any optimizer math. The
assertions accept literal reduce-scatter OR the logical form, and pin
the structural facts that must hold on every backend: the constraint is
in the lowered module, the update math runs at shard size, and updated
params come back via all-gather. (The on-TPU inventory is
``chip_smoke.py --chips 4`` and tests/test_chip_compile.py.)
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import (
    GPT2,
    GPT2Config,
    Net,
    cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.observe.hlo import (
    collective_inventory,
    counts,
    has_logical_reduce_scatter,
    max_all_reduce_elems,
    tokenize_hlo,
)
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    TensorParallel,
    TrainStep,
    ZeRO1,
    ZeRO2,
    ZeRO3,
    create_train_state,
    tp_zero3,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def _build_net(mesh, policy):
    model = Net(upscale_factor=2)
    tx = optim.adamw(lr=1e-3)

    def loss_fn(params, batch, rng, ms):
        lr_img, hr_img = batch
        return mse_loss(model.apply({"params": params}, lr_img), hr_img), {}

    state, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8, 8, 3)))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = TrainStep(
        loss_fn, tx, mesh, policy, state_shardings=sh, donate=False
    )
    rng = np.random.default_rng(0)
    hr = rng.random((16, 16, 16, 3)).astype(np.float32)
    lr = hr.reshape(16, 8, 2, 8, 2, 3).mean(axis=(2, 4))
    return state, step, (lr, hr)


def _build_gpt(mesh, policy):
    cfg = GPT2Config.tiny(n_embd=32, n_head=4)
    model = GPT2(cfg)
    tx = optim.adamw(lr=1e-3)

    def loss_fn(params, batch, rng, ms):
        logits = model.apply({"params": params}, batch)
        return cross_entropy_loss(logits[:, :-1], batch[:, 1:]), {}

    state, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8), jnp.int32))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = TrainStep(
        loss_fn, tx, mesh, policy, state_shardings=sh, donate=False
    )
    tok = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 16)
    ).astype(np.int32)
    return state, step, tok


def _hlo(mesh, policy, build=_build_net):
    state, step, batch = build(mesh, policy)
    return step.compiled_text(state, batch)


# Net's three shardable kernels on an 8-way ZeRO axis: shard sizes the
# update math must run at (full: 4800 / 18432 / 3456 elems, /8 each)
NET_LARGEST_GRAD = 18432          # conv (3,3,64,32) — largest leaf
NET_SHARD_ELEMS = 18432 // 8      # its 8-way shard
NET_CONV3_SHARD = 3456 // 8       # conv (3,3,32,12)'s 8-way shard


def _any_logical_rs(hlo):
    # accept the logical reduce-scatter on ANY of Net's sharded kernels:
    # which grad the CPU pipeline keeps in all-reduce + shard-slice form
    # (vs. rewriting through all-to-all) varies by kernel shape
    return any(
        has_logical_reduce_scatter(hlo, s)
        for s in (NET_SHARD_ELEMS, NET_CONV3_SHARD, 4800 // 8)
    )


@pytest.fixture()
def zmesh(devices8):
    return make_mesh(MeshSpec(fsdp=8), devices=devices8)


def test_ddp_one_grad_allreduce_no_gathers(devices8):
    mesh = make_mesh(MeshSpec(dp=8), devices=devices8)
    hlo = _hlo(mesh, DDP())
    c = counts(hlo)
    # the C++-Reducer twin: gradient sync is all-reduce, nothing else
    assert max_all_reduce_elems(hlo) >= NET_LARGEST_GRAD, c
    assert "all-gather" not in c and "reduce-scatter" not in c, c


def test_zero1_update_shards_and_gathers_params(zmesh):
    hlo = _hlo(zmesh, ZeRO1())
    c = counts(hlo)
    # grads replicated (all-reduce), updated params re-broadcast from the
    # opt shard via all-gather — one per sharded kernel
    assert max_all_reduce_elems(hlo) >= NET_LARGEST_GRAD, c
    assert c.get("all-gather", 0) >= 3, c


def test_zero2_reduce_scatters_grads(zmesh):
    hlo = _hlo(zmesh, ZeRO2())
    # literal reduce-scatter (TPU) or all-reduce + shard-sized
    # dynamic-slice (CPU pipeline) — either way the optimizer must
    # consume shard-sized gradients. The slice must provably read an
    # all-reduce result (directly or via the fusion that consumes it);
    # a coincidental shard-sized slice elsewhere no longer counts.
    assert _any_logical_rs(hlo)
    assert counts(hlo).get("all-gather", 0) >= 3


def test_zero2_constraint_in_lowered_module(zmesh):
    # the backend-independent fact: ZeRO-2 lowers MORE sharding
    # constraints than ZeRO-1 (one per sharded grad kernel). If the grad
    # constraint silently stopped being applied, both backends would
    # quietly all-reduce and this is the test that notices.
    def lowered(policy):
        state, step, batch = _build_net(zmesh, policy)
        with zmesh:
            return step._jitted.lower(
                state, batch, jnp.float32(1.0)
            ).as_text()

    marks = re.compile(r"sharding_constraint|@Sharding")
    n1 = len(marks.findall(lowered(ZeRO1())))
    n2 = len(marks.findall(lowered(ZeRO2())))
    assert n2 >= n1 + 3, (n1, n2)


def test_zero3_gathers_params_for_compute(zmesh):
    hlo2 = _hlo(zmesh, ZeRO2())
    hlo3 = _hlo(zmesh, ZeRO3())
    # ZeRO-3 adds forward/backward param all-gathers on top of ZeRO-2's
    # update-path gathers
    assert (
        counts(hlo3).get("all-gather", 0)
        > counts(hlo2).get("all-gather", 0)
    ), (counts(hlo2), counts(hlo3))
    assert _any_logical_rs(hlo3)


def test_tp_activation_allreduce_per_block(devices8):
    mesh = make_mesh(MeshSpec(dp=2, tp=4), devices=devices8)
    hlo = _hlo(mesh, TensorParallel(), build=_build_gpt)
    c = counts(hlo)
    # Megatron row-parallel projections psum activations: at least one
    # all-reduce per transformer block beyond the dp grad sync
    assert c.get("all-reduce", 0) >= GPT2Config.tiny().n_layer + 1, c


def test_hybrid_tp_zero3_gathers_and_reduces(devices8):
    mesh = make_mesh(MeshSpec(fsdp=2, tp=4), devices=devices8)
    hlo = _hlo(mesh, tp_zero3(min_shard_size=1), build=_build_gpt)
    c = counts(hlo)
    # 2D layout: fsdp param all-gathers AND tp/grad reductions coexist
    assert c.get("all-gather", 0) >= 1, c
    assert c.get("all-reduce", 0) >= 1, c
    assert collective_inventory(hlo), "no collectives at all?"


class TestInventoryParser:
    """observe.hlo text-parser edge cases (no compilation involved)."""

    HLO = "\n".join([
        "  %all-reduce.10 = (f32[64]{0}, f32[5,5,3,64]{3,2,1,0}) "
        "all-reduce(%a, %b), replica_groups=[1,8]<=[8]",
        "  %ag = bf16[3,3,8,32]{3,2,1,0} all-gather(%c), dimensions={2}",
        "  %ars = f32[100]{0} all-reduce-start(%d)",
        "  %rs = f32[2304]{0} reduce-scatter(%e)",
        # the unfused CPU reduce-scatter form: the slice reads the
        # all-reduce's result through a get-tuple-element
        "  %gte = f32[5,5,3,64]{3,2,1,0} "
        "get-tuple-element(%all-reduce.10), index=1",
        "  %ds = f32[2304]{0} dynamic-slice(%gte, %i0), "
        "dynamic_slice_sizes={2304}",
        # a COINCIDENTAL shard-sized slice of something unrelated (%f is a
        # fusion, not a reduction) — must not count as a logical
        # reduce-scatter
        "  %ds.2 = f32[1111]{0} dynamic-slice(%f, %i0), "
        "dynamic_slice_sizes={1111}",
        "  %noise = f32[9999]{0} add(%g, %h)",
    ])

    def test_kinds_and_sizes(self):
        inv = collective_inventory(self.HLO)
        kinds = [op.kind for op in inv]
        assert kinds == [
            "all-reduce", "all-gather", "all-reduce", "reduce-scatter",
        ]
        # tuple-shaped combined collective reports its largest member
        assert inv[0].max_elems == 5 * 5 * 3 * 64
        assert inv[1].max_elems == 3 * 3 * 8 * 32

    def test_counts_and_max(self):
        assert counts(self.HLO) == {
            "all-reduce": 2, "all-gather": 1, "reduce-scatter": 1,
        }
        assert max_all_reduce_elems(self.HLO) == 4800

    def test_logical_reduce_scatter_forms(self):
        # literal op present
        assert has_logical_reduce_scatter(self.HLO, 1)
        # unfused CPU form: all-reduce + shard-sized dynamic-slice that
        # reads the all-reduce's result (through the gte)
        unfused = "\n".join(
            l for l in self.HLO.splitlines() if "reduce-scatter" not in l
        )
        assert has_logical_reduce_scatter(unfused, 2304)
        assert not has_logical_reduce_scatter(unfused, 1234)
        # a shard-sized slice of something that is NOT an all-reduce
        # result (%ds.2 slices fusion %f) must not count — that module
        # shape is exactly GSPMD backing off to replication
        assert not has_logical_reduce_scatter(unfused, 1111)
        # no reduction at all
        assert not has_logical_reduce_scatter("%x = f32[4] add(%a, %b)", 4)

    def test_logical_reduce_scatter_short_name_style(self):
        # compiled.as_text() sometimes prints bare names (no %)
        short = "\n".join([
            "  ar.1 = f32[18432]{0} all-reduce(g.1), to_apply=add",
            "  ds.1 = f32[2304]{0} dynamic-slice(ar.1, idx), "
            "dynamic_slice_sizes={2304}",
        ])
        assert has_logical_reduce_scatter(short, 2304)
        coincidental = "\n".join([
            "  ar.1 = f32[18432]{0} all-reduce(g.1), to_apply=add",
            "  ds.1 = f32[2304]{0} dynamic-slice(other.7, idx), "
            "dynamic_slice_sizes={2304}",
        ])
        assert not has_logical_reduce_scatter(coincidental, 2304)

    def test_scalar_shapes(self):
        inv = collective_inventory("%r = f32[] all-reduce(%x)")
        assert inv[0].max_elems == 1


class TestTokenizer:
    """tokenize_hlo edge cases: fusion bodies, wrapped operand lists,
    computation attribution (no compilation involved)."""

    MODULE = "\n".join([
        "HloModule jit_step, entry_computation_layout="
        "{(f32[18432]{0})->f32[2304]{0}}",
        "",
        "%fused_computation (param_0.1: f32[18432], param_1.2: u32[]) "
        "-> f32[2304] {",
        "  %param_0.1 = f32[18432]{0} parameter(0)",
        "  %param_1.2 = u32[] parameter(1)",
        "  ROOT %ds.9 = f32[2304]{0} dynamic-slice(%param_0.1, "
        "%param_1.2), dynamic_slice_sizes={2304}",
        "}",
        "",
        "ENTRY %main.42 (p0: f32[18432]) -> f32[2304] {",
        "  %p0 = f32[18432]{0} parameter(0)",
        # wrapped operand list: ONE instruction across three lines
        "  %ar.5 = f32[18432]{0} all-reduce(%p0, %p0,",
        "      %p0, %p0), replica_groups={{0,1,2,3,4,5,6,7}},"
        " to_apply=%add.3",
        "  %pid.2 = u32[] partition-id()",
        "  ROOT %fus = f32[2304]{0} fusion(%ar.5, %pid.2), kind=kLoop, "
        "calls=%fused_computation",
        "}",
    ])

    def test_fusion_body_ops_attribute_to_their_computation(self):
        toks = {t.name: t for t in tokenize_hlo(self.MODULE)}
        assert toks["ds.9"].computation == "fused_computation"
        assert toks["ar.5"].computation == "main.42"
        assert toks["fus"].computation == "main.42"

    def test_multiline_operands_merge_into_one_token(self):
        ar = [t for t in tokenize_hlo(self.MODULE) if t.name == "ar.5"]
        assert len(ar) == 1
        # the wrapped tail (second operand line + attributes) joined in
        assert "to_apply=%add.3" in ar[0].text
        assert "replica_groups" in ar[0].text
        inv = [
            op for op in collective_inventory(self.MODULE)
            if op.kind == "all-reduce"
        ]
        assert len(inv) == 1 and inv[0].max_elems == 18432
        assert counts(self.MODULE) == {"all-reduce": 1}

    def test_fusion_body_slice_counts_as_logical_reduce_scatter(self):
        # the CPU fused form: all-reduce feeds a fusion whose body holds
        # the shard-sized dynamic-slice — crosses a computation boundary
        assert has_logical_reduce_scatter(self.MODULE, 2304)
        # a shard size nothing slices to must not match
        assert not has_logical_reduce_scatter(self.MODULE, 999)

    def test_headers_and_braces_produce_no_tokens(self):
        names = [t.name for t in tokenize_hlo(self.MODULE)]
        assert "fused_computation" not in names
        assert "main.42" not in names
        assert "jit_step" not in names
        # every real instruction is tokenized exactly once
        assert names == ["param_0.1", "param_1.2", "ds.9", "p0", "ar.5",
                         "pid.2", "fus"]
