"""GPT-2's attention core when the caller names none (ISSUE 30).

A training ``Block`` decides from what it can observe: the blockwise
kernels of ``ops/pallas_attn.py`` where the program is lowered for a TPU,
the shapes meet ``kernel_contract`` and the step's mesh is known; the
einsums everywhere else. On a CPU the default model is bit for bit
``attn_fn=default_attention``; the kernels are held to the einsums
interpreted, alone and placed by ``shard_map`` on four devices.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributedtraining_tpu.models import GPT2, GPT2Config
from pytorch_distributedtraining_tpu.models import gpt2 as gpt2_module
from pytorch_distributedtraining_tpu.models.gpt2 import (
    cross_entropy_loss,
    default_attention,
)
from pytorch_distributedtraining_tpu.observe import trace
from pytorch_distributedtraining_tpu.ops import pallas_attn
from pytorch_distributedtraining_tpu.parallel.spec import batch_layout
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh


def _mesh(n, axis="dp"):
    return n and make_mesh(MeshSpec(**{axis: n}), devices=jax.devices()[:n])


def _tokens(cfg, b, t, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, cfg.vocab_size, (b, t)),
        jnp.int32,
    )


def _loss(model, mesh):
    def fn(params, tok):
        with batch_layout(mesh) if mesh else contextlib.nullcontext():
            logits = model.apply({"params": params}, tok)
        return cross_entropy_loss(logits[:, :-1], tok[:, 1:])
    return fn


@contextlib.contextmanager
def _said(name="attention.path"):
    """The instants of that name left by the traces made inside."""
    tracer = trace.get_tracer()
    was = tracer.enabled
    trace.enable(crash_handler=False)
    trace.clear()
    said = []
    try:
        yield said
        said.extend(
            r["attrs"] for r in trace.records() if r["name"] == name
        )
    finally:
        trace.clear()
        tracer.enabled = was


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Steer the decided path as a TPU lowering would, on the CPU: the
    platform's branch is the kernels', and they run interpreted."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args),
    )
    monkeypatch.setattr(
        pallas_attn, "causal_attention_qkv",
        functools.partial(pallas_attn.causal_attention_qkv, interpret=True),
    )
    gpt2_module._kernel_or_einsum_attention.clear_cache()
    yield
    gpt2_module._kernel_or_einsum_attention.clear_cache()


@pytest.mark.parametrize("layout", ["none", "one_device", "dp4"])
def test_default_model_on_cpu_is_the_einsums(layout):
    """``GPT2(cfg)`` takes no argument to choose its attention. With a mesh
    published the traced program holds the kernels (for a TPU lowering) and
    the einsums, and a CPU lowers the einsums: loss and every gradient equal
    ``attn_fn=default_attention`` to the bit. With eight devices visible and
    no layout published the einsums are all there is."""
    cfg = GPT2Config.tiny(
        n_embd=128, n_head=2, n_positions=128, n_layer=2, dtype=jnp.bfloat16
    )
    tok = _tokens(cfg, 4, 128)
    auto, ref = GPT2(cfg), GPT2(cfg, attn_fn=default_attention)
    assert auto.attn_fn is None
    params = ref.init(jax.random.PRNGKey(0), tok)["params"]
    mesh = _mesh({"none": 0, "one_device": 1, "dp4": 4}[layout])

    with _said() as said:
        jaxpr = str(jax.make_jaxpr(_loss(auto, mesh))(params, tok))
    assert [(s["b"], s["t"], s["heads"], s["dh"]) for s in said] == [
        (4, 128, 2, 64)
    ] * 2
    if layout == "none":
        assert jax.device_count() > 1  # conftest's eight
        assert [s["path"] for s in said] == ["einsum"] * 2
        assert all("no step has published" in s["reason"] for s in said)
        assert "pallas_call" not in jaxpr
    else:
        assert [s["path"] for s in said] == ["by_platform"] * 2
        assert all((s["bq"], s["bk"]) == (128, 128) for s in said)
        assert said[0]["mesh"] == (4 if layout == "dp4" else 1)
        assert "platform_index" in jaxpr and "pallas_call" in jaxpr
        assert ("shard_map" in jaxpr) == (layout == "dp4")

    if layout == "dp4":
        tok = jax.device_put(tok, NamedSharding(mesh, P("dp")))
    got = jax.jit(jax.value_and_grad(_loss(auto, mesh)))(params, tok)
    want = jax.jit(jax.value_and_grad(_loss(ref, mesh)))(params, tok)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("t", [256, 512])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("heads,dh", [(4, 64), (5, 64), (3, 128)])
def test_kernels_match_the_einsums(heads, dh, bq, bk, t):
    """Forward and gradient, interpreted. Four heads of 64 pack two to a
    128-lane block and are read out of ``qkv`` where ``c_attn`` wrote them;
    five (an odd count, as GPT-2 XL's 25) are copied into thirds of their
    own behind which a head of zeros fills the block; a head of 128 is a
    block (no lane mask, and a scale that is no power of two)."""
    assert pallas_attn.packs(heads, dh) == ((heads, dh) != (5, 64))
    rng = np.random.default_rng(heads)
    qkv = jnp.asarray(rng.normal(size=(2, t, 3 * heads * dh)), jnp.float32)
    do = jnp.asarray(rng.normal(size=(2, t, heads * dh)), jnp.float32)

    def einsums(qkv):
        return default_attention(
            *gpt2_module._split_heads(qkv, heads), causal=True
        ).reshape(do.shape)

    def kernels(qkv):
        return pallas_attn.causal_attention_qkv(
            qkv, heads, interpret=True, blocks=(bq, bk)
        ).reshape(do.shape)

    out, vjp = jax.vjp(kernels, qkv)
    want, want_vjp = jax.vjp(einsums, qkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(vjp(do)[0]), np.asarray(want_vjp(do)[0]), atol=1e-4
    )


def test_blocks_are_read_from_the_sequence():
    """``causal_attention_qkv`` takes its blocks from T, and is what the
    decided path runs: bf16 operands, float32 accumulation."""
    heads, t = 2, 256
    qkv = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, t, 3 * heads * 64)),
        jnp.bfloat16,
    )
    assert pallas_attn.attention_blocks(t) is not None
    assert pallas_attn.attention_blocks(t + 8) is None
    out = pallas_attn.causal_attention_qkv(qkv, heads, interpret=True)
    want = default_attention(
        *gpt2_module._split_heads(qkv, heads), causal=True
    )
    assert out.shape == want.shape and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=3e-2
    )
    with pytest.raises(ValueError, match="multiple of the smallest block"):
        pallas_attn.causal_attention_qkv(qkv[:, :200], heads, interpret=True)


CONTRACT = {
    # name: (model kwargs, T, batch, mesh, what the reason says)
    "t_not_divisible": ({}, 96, 4, ("dp", 1), "not a multiple"),
    "head_size": ({"n_embd": 64}, 128, 4, ("dp", 1), "head size 32"),
    "decode": ({"decode": True}, 128, 4, ("dp", 1), "KV cache"),
    "paged": (
        {"decode": True, "paged": (8, 16)}, 16, 2, ("dp", 1), "page pool"
    ),
    "no_mesh_published": ({}, 128, 4, None, "no step has published"),
    "batch_not_divided": ({}, 128, 6, ("dp", 4), "6 sequences do not split"),
    "mesh_with_a_model_axis": (
        {}, 128, 4, ("tp", 2), "do not split the batch"
    ),
}


@pytest.mark.parametrize("case", sorted(CONTRACT))
def test_outside_the_contract_the_einsums_with_the_reason(case):
    """What the kernels do not take, or the block cannot place, is computed
    by the einsums, and the ``attention.path`` instant says why in words."""
    kw, t, b, mesh, reason = CONTRACT[case]
    kw = dict(kw)
    cfg = GPT2Config.tiny(
        n_embd=kw.pop("n_embd", 128), n_head=2, n_positions=128, n_layer=1
    )
    mesh = mesh and _mesh(mesh[1], mesh[0])
    model = GPT2(cfg, **kw)
    tok = _tokens(cfg, b, t)
    extra = {}
    if kw.get("paged"):
        extra = dict(
            page_table=jnp.zeros((b, 4), jnp.int32),
            lengths=jnp.zeros((b,), jnp.int32),
        )
    variables = GPT2(cfg, attn_fn=default_attention, **kw).init(
        jax.random.PRNGKey(0), tok, **extra
    )

    def fn(variables, tok):
        with batch_layout(mesh) if mesh else contextlib.nullcontext():
            return model.apply(
                variables, tok, mutable=["cache", "pages"], **extra
            )[0]

    with _said() as said:
        jaxpr = str(jax.make_jaxpr(fn)(variables, tok))
    assert [s["path"] for s in said] == ["einsum"]
    assert reason in said[0]["reason"]
    assert (said[0]["bq"], said[0]["bk"]) == (None, None)
    assert "pallas_call" not in jaxpr and "platform_index" not in jaxpr


@pytest.mark.parametrize("heads", [2, 3])
@pytest.mark.parametrize("stack", ["unrolled", "scan_remat"])
def test_kernels_placed_on_four_devices_equal_one_devices(
    on_a_tpu, stack, heads
):
    """On a mesh the kernels run under ``shard_map`` over the mesh the step
    published, each device over its own sequences: loss and gradients equal
    one device's, with the layers unrolled and as a rematerialised scan
    (whose backward reads the forward kernel's kept residuals), and stay at
    the einsums' within the kernels' rounding."""
    cfg = GPT2Config.tiny(
        n_embd=64 * heads, n_head=heads, n_positions=128, n_layer=2,
        scan_layers=stack == "scan_remat", remat=stack == "scan_remat",
    )
    tok = _tokens(cfg, 4, 128)
    auto, ref = GPT2(cfg), GPT2(cfg, attn_fn=default_attention)
    params = ref.init(jax.random.PRNGKey(0), tok)["params"]
    one, four = _mesh(1), _mesh(4)

    with _said() as said:
        jaxpr = str(jax.make_jaxpr(_loss(auto, four))(params, tok))
    assert said and all(s["path"] == "by_platform" for s in said)
    assert all(s["mesh"] == 4 for s in said)
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    assert "platform_index" not in jaxpr  # the fixture took the TPU's branch

    grad = lambda model, mesh: jax.jit(  # noqa: E731
        jax.value_and_grad(_loss(model, mesh))
    )
    on_four = grad(auto, four)(
        params, jax.device_put(tok, NamedSharding(four, P("dp")))
    )
    on_one = grad(auto, one)(params, tok)
    einsums = grad(ref, one)(params, tok)
    for a, b, c in zip(*(jax.tree.leaves(x) for x in (on_four, on_one, einsums))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(c), rtol=2e-3, atol=2e-5
        )


@pytest.mark.parametrize("heads", [2, 3])
@pytest.mark.parametrize("remat", [True, "names", "dots", False])
def test_rematerialised_scan_on_eight_devices_runs_the_forward_once(
    on_a_tpu, remat, heads, monkeypatch
):
    """``remat_block(..., in_scan=True)`` under ``nn.scan``, the core under
    the ``shard_map`` a step's mesh asks for (the way GPT-2 XL's cell runs):
    the gradient's scanned bodies hold three kernels (forward; dq and dk/dv
    in the backward's body) under every policy, as many as with no remat at
    all. A forward rule that did not name its residuals ran a fourth, the
    forward again. Packed heads (2) and an odd count padded to lanes (3)."""
    cfg = GPT2Config.tiny(
        n_embd=64 * heads, n_head=heads, n_positions=128, n_layer=2,
        scan_layers=True, remat=remat,
    )
    tok = _tokens(cfg, 8, 128)
    params = GPT2(cfg, attn_fn=default_attention).init(
        jax.random.PRNGKey(0), tok
    )["params"]

    def kernels():
        gpt2_module._kernel_or_einsum_attention.clear_cache()
        jaxpr = str(jax.make_jaxpr(jax.grad(_loss(GPT2(cfg), _mesh(8))))(
            params, tok
        ))
        assert "shard_map" in jaxpr and "scan[" in jaxpr
        return jaxpr.count("pallas_call[")

    assert kernels() == 3
    monkeypatch.setattr(pallas_attn, "_kept", lambda out, lse: (out, lse))
    assert kernels() == (3 if remat is False else 4)


def test_remat_says_what_it_keeps():
    """Each trace of a model that wraps its blocks leaves one ``remat.path``
    instant for the wrapped class, each trace of a function ``apply_remat``
    wrapped one more, with the policy and the names kept."""
    from pytorch_distributedtraining_tpu.parallel.remat import (
        CHECKPOINT_SAVED_NAMES, apply_remat,
    )

    from pytorch_distributedtraining_tpu.models.scan_utils import (
        stack_layer_params,
    )

    cfg = GPT2Config.tiny(n_positions=16, n_layer=2, remat=True)
    scanned = GPT2Config.tiny(
        n_positions=16, n_layer=2, remat=True, scan_layers=True
    )
    tok = _tokens(cfg, 2, 16)
    params = GPT2(cfg).init(jax.random.PRNGKey(0), tok)["params"]
    kernels = [pallas_attn.RESIDUALS_NAME]
    with _said("remat.path") as said:
        jax.make_jaxpr(_loss(GPT2(cfg), None))(params, tok)
        jax.make_jaxpr(_loss(GPT2(scanned), None))(
            stack_layer_params(dict(params), "h_", 2, "h"), tok
        )
        for policy in ("none", True, "dots", "names"):
            jax.make_jaxpr(apply_remat(lambda x: x * 2, policy))(1.0)
    assert [(s["policy"], s["keeps"], s["where"]) for s in said] == [
        ("full", kernels, "Block"),
        # a scan's bodies: what is kept is stacked, the statistics as rows
        ("full", kernels + [pallas_attn.DENSE_LSE_NAME], "Block"),
        ("full", kernels, "apply_remat"),
        ("dots", kernels, "apply_remat"),
        ("names", list(CHECKPOINT_SAVED_NAMES), "apply_remat"),
    ]


def test_twelve_layers_share_one_trace_of_the_core(monkeypatch):
    """The decided core is jitted on its shapes: twelve unrolled layers
    trace the kernels' body and the einsums once, not twelve times (trace
    and lowering are paid in every run's set-up)."""
    traced = {"kernels": 0, "einsums": 0}
    kernels, einsums = pallas_attn.causal_attention_qkv, default_attention

    def count(name, fn):
        def counted(*args, **kw):
            traced[name] += 1
            return fn(*args, **kw)
        return counted

    monkeypatch.setattr(
        pallas_attn, "causal_attention_qkv", count("kernels", kernels)
    )
    monkeypatch.setattr(
        gpt2_module, "default_attention", count("einsums", einsums)
    )
    gpt2_module._kernel_or_einsum_attention.clear_cache()
    cfg = GPT2Config.tiny(
        n_embd=128, n_head=2, n_positions=128, n_layer=12
    )
    tok = _tokens(cfg, 2, 128)
    model = GPT2(cfg)
    params = GPT2(cfg, attn_fn=einsums).init(jax.random.PRNGKey(0), tok)[
        "params"
    ]
    with _said() as said:
        jax.make_jaxpr(jax.grad(_loss(model, _mesh(1))))(params, tok)
    assert [s["path"] for s in said] == ["by_platform"] * 12
    assert traced == {"kernels": 1, "einsums": 1}
    gpt2_module._kernel_or_einsum_attention.clear_cache()
