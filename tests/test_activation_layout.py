"""The step states the activation layout; the model's residual stream is
held to it (``parallel.spec.batch_layout`` / ``pin_batch``).

With sharded parameters and free activations GSPMD may keep the weights
where they are and gather the batch: ZeRO-3 GPT-2 XL on a 2x2 compiled to
hidden-sharded tensor parallelism, every chip computing every sequence's
attention (PERF.md, PR 24 / PR 26). These pin what the compiler emits once
the batch dimension is constrained: parameters move, activations do not —
and that nothing at all changes where the data axes hold one device.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.models import (
    GPT2,
    GPT2Config,
    cross_entropy_loss,
)
from pytorch_distributedtraining_tpu.observe.hlo import (
    collective_inventory,
    counts,
)
from pytorch_distributedtraining_tpu.parallel import (
    DDP,
    TrainStep,
    ZeRO3,
    create_train_state,
    spec as spec_mod,
    step as step_mod,
    tp_zero3,
)
from pytorch_distributedtraining_tpu.parallel.spec import (
    batch_layout,
    pin_batch,
)
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

BATCH, SEQ, HEADS = 8, 16, 4
# an odd vocabulary, as GPT-2's 50,257 is: the embedding table then shards
# on the hidden dimension like every other kernel. (A vocabulary the axis
# divides is split by rows, and XLA partitions the lookup over them: one
# [B, T, D] all-reduce a step, outside the blocks; PERF.md section 7.)
VOCAB = 257
CONSTRAINT = re.compile(r"sharding_constraint|@Sharding")


def _cfg(**kw):
    return GPT2Config.tiny(vocab_size=VOCAB, n_embd=32, n_head=HEADS, **kw)


def _loss_fn(model):
    def loss_fn(params, batch, rng, ms):
        logits = model.apply({"params": params}, batch)
        return cross_entropy_loss(logits[:, :-1], batch[:, 1:]), {}

    return loss_fn


def _build(mesh, policy, *, loss_fn=None, **cfg_kw):
    model = GPT2(_cfg(**cfg_kw))
    tx = optim.adamw(lr=1e-3)
    state, sh = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8), jnp.int32))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy,
    )
    step = TrainStep(
        loss_fn or _loss_fn(model), tx, mesh, policy, state_shardings=sh,
        donate=False,
    )
    tok = np.random.default_rng(0).integers(
        0, VOCAB, (BATCH, SEQ)
    ).astype(np.int32)
    return state, step, tok


def _lowered(state, step, tok):
    with step.mesh:
        return step._jitted.lower(state, tok, jnp.float32(1.0)).as_text()


def _unpublished(monkeypatch):
    """A step that says nothing about activations: the parent's program."""
    monkeypatch.setattr(
        step_mod, "batch_layout", lambda mesh: contextlib.nullcontext()
    )


def _result_shapes(line):
    """``(dtype, dims)`` of every result of one HLO instruction."""
    head = line.split("=", 1)[1].split(" all-", 1)[0]
    return [
        (dt, tuple(int(d) for d in dims.split(",") if d))
        for dt, dims in re.findall(r"\b([a-z]+\d+)\[([0-9,]*)\]", head)
    ]


def _parameter_shapes(params):
    """Dimensions (order-free: a gather may follow a transpose) a gathered
    parameter can have: a leaf, one scanned layer of a stacked leaf, or the
    first SEQ rows of the position table."""
    shapes = set()
    for leaf in jax.tree.leaves(params):
        shapes.add(tuple(sorted(leaf.shape)))
        shapes.add(tuple(sorted(leaf.shape[1:])))
    shapes.add(tuple(sorted((SEQ, 32))))
    return shapes


@pytest.mark.parametrize(
    "scan_layers,remat",
    [(False, False), (True, False), (True, True)],
    ids=["loop", "scan", "scan_remat"],
)
def test_zero3_gathers_parameters_not_activations(
    devices8, scan_layers, remat
):
    kw = dict(scan_layers=scan_layers, remat=remat)
    zmesh = make_mesh(MeshSpec(fsdp=4), devices=devices8[:4])
    state, step, tok = _build(zmesh, ZeRO3(), **kw)
    hlo = step.compiled_text(state, tok)

    allowed = _parameter_shapes(state.params)
    gathers = [
        op for op in collective_inventory(hlo) if op.kind == "all-gather"
    ]
    assert gathers, counts(hlo)
    for op in gathers:
        for dtype, dims in _result_shapes(op.line):
            # integer gathers are the token ids of the embedding lookup
            if len(dims) < 2 or not dtype.startswith(("f", "bf")):
                continue
            assert dims[0] != BATCH, op.line
            squeezed = tuple(sorted(d for d in dims if d != 1))
            assert squeezed in allowed, op.line
    # the score tensors: each device's own sequences, every head, once
    lead = set(re.findall(rf"f32\[(\d+),{HEADS},{SEQ},{SEQ}\]", hlo))
    assert lead == {str(BATCH // 4)}, lead

    dmesh = make_mesh(MeshSpec(dp=4), devices=devices8[:4])
    dstate, dstep, _ = _build(dmesh, DDP(), **kw)
    with zmesh:
        state, m = step(state, tok)
    with dmesh:
        dstate, dm = dstep(dstate, tok)
    for key in ("loss", "grad_norm"):
        np.testing.assert_allclose(
            float(m[key]), float(dm[key]), rtol=1e-5, atol=1e-5
        )
    np.testing.assert_allclose(
        np.asarray(state.params["wte"]), np.asarray(dstate.params["wte"]),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize(
    "axes", [dict(dp=1), dict(tp=2)], ids=["one_device", "data_axes_of_one"]
)
def test_no_data_axis_no_change(devices8, monkeypatch, axes):
    """One chip (the benchmark's other cells), or several with none on a
    data axis: the lowered step is the one a silent step lowers to."""
    n = MeshSpec(**axes).size
    mesh = make_mesh(MeshSpec(**axes), devices=devices8[:n])
    kw = dict(scan_layers=True, remat=True)
    pinned = _lowered(*_build(mesh, DDP(), **kw))
    assert not CONSTRAINT.search(pinned)
    _unpublished(monkeypatch)
    assert _lowered(*_build(mesh, DDP(), **kw)) == pinned


@pytest.mark.parametrize("paged", [None, (5, 4)], ids=["cache", "paged"])
def test_decode_paths_publish_nothing(devices8, paged):
    """Serving has no step to publish a layout: the decode programs hold no
    constraint, and are the same text under a one-device layout."""
    cfg = _cfg()
    model = GPT2(cfg, decode=True, paged=paged)
    tok = jnp.zeros((2, 4), jnp.int32)
    extra = {}
    if paged:
        extra = dict(
            page_table=jnp.zeros((2, 2), jnp.int32),
            lengths=jnp.zeros((2,), jnp.int32),
        )
    init_tok = jnp.zeros((2, SEQ), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), init_tok, **extra)
    )
    kind = "pages" if paged else "cache"

    def fwd(v, tok, extra):
        return model.apply(v, tok, mutable=[kind], **extra)

    text = jax.jit(fwd).lower(variables, tok, extra).as_text()
    assert not CONSTRAINT.search(text)
    with batch_layout(make_mesh(MeshSpec(dp=1), devices=devices8[:1])):
        assert jax.jit(fwd).lower(variables, tok, extra).as_text() == text


def test_pin_constrains_the_batch_dimension_only(devices8):
    """On a tp x fsdp mesh the hidden and sequence dimensions stay the
    partitioner's: a bare P(axes) would force them replicated."""
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=devices8[:4])
    state, step, tok = _build(
        mesh, tp_zero3(min_shard_size=1), scan_layers=True, remat=True
    )
    text = _lowered(state, step, tok)
    pins = re.findall(
        rf"sdy\.sharding_constraint .*<@mesh, \[(.*)\]> : "
        rf"tensor<{BATCH}x{SEQ}x\d+xf32>",
        text,
    )
    # embedding, block entry and exit (forward, and again where the
    # rematerialised block is traced), logits; their cotangents
    assert len(pins) >= 4, text
    assert set(pins) == {'{"fsdp"}, {?}, {?}'}, set(pins)
    c = counts(step.compiled_text(state, tok))
    assert c.get("all-gather", 0) >= 1 and c.get("all-reduce", 0) >= 1, c


@pytest.mark.parametrize(
    "case", ["nothing_published", "data_axes_of_one", "batch_not_divisible"]
)
def test_pin_batch_is_the_identity(devices8, case):
    x = jnp.zeros((6, 4, 8))
    if case == "nothing_published":
        assert pin_batch(x) is x
        return
    axes = dict(tp=4) if case == "data_axes_of_one" else dict(fsdp=4)
    with batch_layout(make_mesh(MeshSpec(**axes), devices=devices8[:4])):
        assert pin_batch(x) is x
    assert spec_mod._BATCH_LAYOUT.get() is None


def test_pin_batch_spec(devices8):
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices=devices8)

    def f(x):
        with batch_layout(mesh):
            return pin_batch(x)

    (eqn,) = jax.make_jaxpr(f)(jnp.zeros((8, 4, 8))).eqns
    assert eqn.primitive.name == "sharding_constraint"
    sharding = eqn.params["sharding"]
    assert sharding.mesh == mesh
    assert sharding.spec == P(
        ("dp", "fsdp"), P.UNCONSTRAINED, P.UNCONSTRAINED
    )


def test_two_steps_one_loss_fn_each_its_own_mesh(devices8):
    """``jax.checkpoint`` caches its trace on the function it wraps: the
    layout is published inside the step's own wrapper, so a second step
    with the same loss function (``Policy.remat``) on other devices does
    not inherit the first one's constraints."""
    model = GPT2(_cfg())
    loss_fn = _loss_fn(model)
    za = make_mesh(MeshSpec(fsdp=4), devices=devices8[:4])
    zb = make_mesh(MeshSpec(dp=2), devices=devices8[4:6])
    losses = []
    for mesh in (za, zb):
        state, step, tok = _build(
            mesh, ZeRO3(remat=True), loss_fn=loss_fn
        )
        with mesh:
            _, m = step(state, tok)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
