"""Ablation profiler for the headline SwinIR-S bench.

Times variants of the benched train step on the real chip in ONE process
(TPU init is slow/flaky) to locate where the step time goes:

  full        the exact bench.py step (fwd+bwd+AdamW+clip)
  fwd_bwd     loss value_and_grad only, no optimizer update
  fwd         forward+loss only
  no_attnmm   WindowAttention's QK^T/softmax/AV replaced by identity on v
              (keeps qkv + proj Dense) -- isolates the head_dim=10 matmuls
  no_bias     attention without the relative-position-bias gather
  blockdiag_attn  QK^T/AV as block-diagonal-packed gemms (contraction 60
              instead of 10) -- MXU utilization vs HBM traffic trade
  bf16_softmax  attention softmax accumulated in bf16 (no f32 round-trip)
  bf16_ln     LayerNorms in bf16 instead of f32
  all_bf16    bf16 norms + bf16 softmax together
  batch72     full step at 4x batch (occupancy check)

Set GRAFT_PROFILE_TINY=1 for a CPU self-test of every arm on a tiny model
(validates the harness; timings are not TPU-meaningful, and the analytic
roofline line is suppressed since it describes the full-size model).

Prints one JSON line per variant: {"variant", "ms_per_step", "img_per_sec"}.
Also prints XLA's own flops estimate for the full step (cost_analysis) and
the implied MFU against v5e-class 197 TFLOP/s bf16 peak.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

import _bootstrap  # noqa: F401  (repo root on sys.path)
from _roofline import guard, verify_finite

from pytorch_distributedtraining_tpu import optim
from pytorch_distributedtraining_tpu.losses import mse_loss
from pytorch_distributedtraining_tpu.models import SwinIR
from pytorch_distributedtraining_tpu.models import swinir as swinir_mod
from pytorch_distributedtraining_tpu.parallel import DDP, TrainStep, create_train_state
from pytorch_distributedtraining_tpu.precision import Policy as Precision
from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

TINY = os.environ.get("GRAFT_PROFILE_TINY") == "1"  # CPU self-test mode
BATCH = 2 if TINY else 18
PATCH = 16 if TINY else 64
STEPS = 2 if TINY else 20
WARMUP = 1 if TINY else 3
PEAK_TFLOPS = 197.0  # v5e-class bf16
# model kwargs shared by the main build and every ablation arm
MODEL_KW = (
    dict(depths=[2], embed_dim=12, num_heads=[2]) if TINY else {}
)


def make_batch(batch):
    rng = np.random.default_rng(0)
    hr = rng.random((batch, 2 * PATCH, 2 * PATCH, 3)).astype(np.float32)
    lr_img = hr.reshape(batch, PATCH, 2, PATCH, 2, 3).mean(axis=(2, 4))
    d = jax.devices()[0]
    return jax.device_put(lr_img, d), jax.device_put(hr, d)


def build_step(model, batch):
    mesh = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    tx = optim.adamw(lr=5e-4, clip_grad_norm=0.1)

    def loss_fn(params, batch, rng, model_state):
        lr_img, hr_img = batch
        out = model.apply({"params": params}, lr_img)
        return mse_loss(out, hr_img), {}

    state, shardings = create_train_state(
        init_fn=lambda rng: (
            model.init(rng, jnp.zeros((1, PATCH, PATCH, 3)))["params"],
            {},
        ),
        tx=tx,
        mesh=mesh,
        policy=DDP(),
    )
    step = TrainStep(
        loss_fn, tx, mesh, DDP(),
        precision=Precision(),
        state_shardings=shardings,
        extra_metrics=False,
        donate=False,  # variants below reuse `state` after timing
    )
    return mesh, state, step, loss_fn


def time_step(mesh, state, step, batch):
    with mesh:
        for _ in range(WARMUP):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        return (time.perf_counter() - t0) / STEPS


def time_fn(fn, params, batch):
    # vary the batch per rep INSIDE one jitted program: a distinct epsilon
    # per rep keeps every call distinct work at one dispatch per rep;
    # time_step needs no such treatment because the threaded TrainState
    # differs every step.
    wrapped = jax.jit(
        lambda e, p, b: fn(p, jax.tree.map(lambda x: x + e, b))
    )
    eps = [
        jax.device_put(jnp.float32((i + 1) * 1e-6)) for i in range(STEPS)
    ]
    out = None
    for _ in range(WARMUP):
        out = wrapped(jnp.float32(0), params, batch)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(STEPS):
        out = wrapped(eps[i], params, batch)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / STEPS


def measure_peak():
    """Empirical bf16 matmul peak — the MFU denominator.

    This stage times K chained square bf16 matmuls in ONE dispatch
    (sequential data dependency, so they cannot overlap; one dispatch so
    the host's per-call cost stays amortized) and reports the best-of-3
    rate as the measured peak for this session, beside the published
    peak of ``observe.goodput.PEAK_FLOPS``.
    """
    n = 256 if TINY else 8192
    k_chain = 2 if TINY else 16
    rng = np.random.default_rng(0)
    # evolving random data, variance-preserving mixer (var(x@b) ~ var(x)):
    # ones @ const would make every chained value bit-identical
    a = jnp.asarray(
        rng.standard_normal((n, n)).astype(np.float32), jnp.bfloat16
    )
    b = jnp.asarray(
        (rng.standard_normal((n, n)) / np.sqrt(n)).astype(np.float32),
        jnp.bfloat16,
    )

    @jax.jit
    def chained(x, b):
        for _ in range(k_chain):
            x = x @ b
        return x

    # time-bound the probe: on a degraded backend (CPU self-test) one
    # 16-chain 8192^3 rep is minutes, and an unbounded rep loop
    # turns the MFU *denominator* stage into the thing that eats the
    # capture window. The budget covers the timed reps; at least one rep
    # always runs so a slow-but-alive backend still reports a number.
    budget_s = float(os.environ.get("GRAFT_PEAK_BUDGET", "120"))
    out = chained(a, b)  # compile + warm
    jax.block_until_ready(out)
    best = float("inf")
    reps_done = 0
    t_loop = time.perf_counter()
    for _ in range(3):
        t0 = time.perf_counter()
        out = chained(out, b)  # feed back: reps chain, args never repeat
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
        reps_done += 1
        if time.perf_counter() - t_loop > budget_s:
            break
    verify_finite(float(out[0, 0]), "peak-probe output")
    tflops = 2 * n * n * n * k_chain / best / 1e12
    # the denominator of every MFU line must itself be physical
    guard(
        "peak_probe", tflops, "TFLOP/s", 1500.0,
        "no v5e-class chip exceeds ~1 PFLOP/s bf16; 1.5x margin",
    )
    print(json.dumps({
        "stage": "peak_probe",
        "measured_peak_tflops": round(tflops, 1),
        "matmul_n": n,
        "chain_len": k_chain,
        "reps": reps_done,
    }), flush=True)
    return tflops * 1e12


def report(variant, sec, batch=BATCH):
    print(json.dumps({
        "variant": variant,
        "ms_per_step": round(sec * 1e3, 3),
        "img_per_sec": round(batch / sec, 1),
    }), flush=True)


def analytic_model():
    """First-principles FLOPs + HBM-bytes per image for SwinIR-S x2 @ 64x64.

    Used with the measured step time to place the step on the v5e roofline
    (compute peak ~197 TFLOP/s bf16, HBM ~819 GB/s). Activation-byte
    counts assume XLA materializes each labeled tensor once in bf16 (norms
    in f32) — an under-count of fusion wins and an over-count where XLA
    fuses better; the profiler's ablation arms calibrate it.
    """
    C, T, WS, HEADS = 60, 64 * 64, 8, 6  # channels, tokens, window, heads
    NW = T // (WS * WS)  # windows per image
    N = WS * WS  # tokens per window
    D = C // HEADS

    def mm(m, k, n):  # flops of [m,k]@[k,n]
        return 2 * m * k * n

    conv_first = mm(T, 9 * 3, C)
    per_layer = (
        mm(T, C, 3 * C)  # qkv
        + NW * HEADS * (mm(N, D, N) + mm(N, N, D))  # QK^T + AV
        + mm(T, C, C)  # proj
        + mm(T, C, 2 * C) + mm(T, 2 * C, C)  # fc1 + fc2
    )
    convs = 4 * mm(T, 9 * C, C) + mm(T, 9 * C, C)  # rstb convs + after_body
    conv_up = mm(T, 9 * C, 12)
    fwd_flops = conv_first + 24 * per_layer + convs + conv_up
    train_flops = 3 * fwd_flops  # bwd ~2x fwd

    # activation traffic per image, forward (bytes)
    bf16, f32 = 2, 4
    act = T * C
    per_layer_bytes = (
        act * f32 * 2  # norm1 out (f32 round trip)
        + act * 3 * bf16  # qkv out
        + NW * HEADS * N * N * (bf16 + f32)  # attn logits + f32 softmax
        + act * bf16 * 2  # attn out + proj out
        + act * f32 * 2  # norm2
        + act * 2 * bf16 * 2  # fc1 out + gelu
        + act * bf16 * 2  # fc2 out + residual
    )
    fwd_bytes = 24 * per_layer_bytes + 8 * act * bf16
    train_bytes = 3 * fwd_bytes  # bwd re-reads activations + writes grads

    return {
        "analytic_fwd_gflops_per_img": round(fwd_flops / 1e9, 2),
        "analytic_train_gflops_per_img": round(train_flops / 1e9, 2),
        "analytic_train_mb_per_img": round(train_bytes / 1e6, 1),
        # labeled-peak bound only — this pool's chips measure 3-4x above
        # the 197 TFLOP/s label,
        # so measured img/s can legitimately exceed this line
        "compute_bound_img_per_sec_at_labeled_197": round(
            PEAK_TFLOPS * 1e12 / train_flops, 0
        ),
        "bandwidth_bound_img_per_sec_at_819GBs": round(
            819e9 / train_bytes, 0
        ),
    }


def _rel_bias(module, n, h):
    """Shared relative-position-bias gather (mirrors WindowAttention)."""
    table = module.param(
        "relative_position_bias_table",
        nn.initializers.truncated_normal(0.02),
        ((2 * module.window_size - 1) ** 2, h),
    )
    idx = swinir_mod._relative_position_index(module.window_size)
    return table[idx.reshape(-1)].reshape(n, n, h).transpose(2, 0, 1)


def main():
    failures = []
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    if not TINY:  # the analytic model describes the full-size config only
        print(json.dumps(analytic_model()), flush=True)
    from pytorch_distributedtraining_tpu.runtime.cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    model = SwinIR(dtype=jnp.bfloat16, **MODEL_KW)
    batch = make_batch(BATCH)
    print(json.dumps({"stage": "built batch"}), flush=True)
    mesh, state, step, loss_fn = build_step(model, batch)
    print(json.dumps({"stage": "built step"}), flush=True)

    measured_peak = measure_peak()  # flops/s; the honest MFU denominator

    sec = time_step(mesh, state, step, batch)
    report("full", sec)

    # XLA's flops estimate — NOTE the AOT lower().compile() path does not
    # reuse the jit cache, so this is a second compile of the same program;
    # the persistent compilation cache (enabled in main) absorbs it
    try:
        cost = step._jitted.lower(state, batch, jnp.float32(1.0)).compile(
        ).cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        print(json.dumps({
            "xla_flops_per_step": flops,
            "flops_per_img": flops / BATCH,
            # denominator is this session's measured matmul peak; the
            # published peak is reported beside it
            "mfu_vs_measured_peak": round(flops / sec / measured_peak, 4),
            "mfu_vs_labeled_197": round(
                flops / sec / (PEAK_TFLOPS * 1e12), 4
            ),
        }), flush=True)
    except Exception as e:  # cost analysis is best-effort
        print(json.dumps({"cost_analysis_error": str(e)[:200]}), flush=True)

    # fwd+bwd only
    params = state.params

    @jax.jit
    def fwd_bwd(p, b):
        def lfn(p):
            pc = jax.tree.map(lambda x: x, p)
            l, _ = loss_fn(pc, b, None, {})
            return l
        return jax.value_and_grad(lfn)(p)

    report("fwd_bwd", time_fn(fwd_bwd, params, batch))

    @jax.jit
    def fwd(p, b):
        return loss_fn(p, b, None, {})[0]

    report("fwd", time_fn(fwd, params, batch))

    # --- model ablations (fwd+bwd, same shape of loss) -------------------
    # failure-isolated: one arm blowing up on the chip (e.g. a Mosaic
    # compile error in a Pallas variant) must not cost the later arms'
    # data — the pool windows are too rare to burn
    def ablate(model_cls_kwargs, name):
        try:
            m = SwinIR(dtype=jnp.bfloat16, **MODEL_KW, **model_cls_kwargs)
            p = m.init(
                jax.random.PRNGKey(0), jnp.zeros((1, PATCH, PATCH, 3))
            )["params"]

            @jax.jit
            def fb(p, b):
                def lfn(p):
                    out = m.apply({"params": p}, b[0])
                    return mse_loss(out, b[1])
                return jax.value_and_grad(lfn)(p)

            report(name, time_fn(fb, p, batch))
        except Exception as e:  # noqa: BLE001 — per-arm isolation
            failures.append(name)
            print(json.dumps({
                "variant": name,
                "error": f"{type(e).__name__}: {str(e)[:300]}",
            }), flush=True)

    # -- attention-variant arms: patch the module-global class (flax wraps
    # __call__ at class creation, so assigning a raw function would lose
    # the @nn.compact binding) --------------------------------------------
    def with_attention(cls, name):
        orig = swinir_mod.WindowAttention
        swinir_mod.WindowAttention = cls
        try:
            ablate({}, name)
        finally:
            swinir_mod.WindowAttention = orig

    class NoAttnMM(swinir_mod.WindowAttention):
        """qkv + proj Dense kept; QK^T/softmax/AV replaced by identity-on-v."""

        @nn.compact
        def __call__(self, x, mask=None):
            bn, n, c = x.shape
            h = self.num_heads
            head_dim = c // h
            qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype, name="qkv")(x)
            qkv = qkv.reshape(bn, n, 3, h, head_dim).transpose(2, 0, 3, 1, 4)
            v = qkv[2]
            out = v.transpose(0, 2, 1, 3).reshape(bn, n, c)
            return nn.Dense(c, dtype=self.dtype, name="proj")(out)

    with_attention(NoAttnMM, "no_attnmm")

    class NoBias(swinir_mod.WindowAttention):
        """Full attention minus the relative-position-bias gather+add."""

        @nn.compact
        def __call__(self, x, mask=None):
            bn, n, c = x.shape
            h = self.num_heads
            head_dim = c // h
            qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype, name="qkv")(x)
            qkv = qkv.reshape(bn, n, 3, h, head_dim).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]
            scale = head_dim**-0.5
            attn = (q * scale) @ k.transpose(0, 1, 3, 2)
            # keep the param so the tree matches; skip gather+add
            self.param(
                "relative_position_bias_table",
                nn.initializers.truncated_normal(0.02),
                ((2 * self.window_size - 1) ** 2, h),
            )
            if mask is not None:
                nw = mask.shape[0]
                attn = attn.reshape(bn // nw, nw, h, n, n) + mask[
                    None, :, None
                ].astype(attn.dtype)
                attn = attn.reshape(bn, h, n, n)
            attn = jax.nn.softmax(
                attn.astype(jnp.float32), axis=-1
            ).astype(self.dtype)
            out = (attn @ v).transpose(0, 2, 1, 3).reshape(bn, n, c)
            return nn.Dense(c, dtype=self.dtype, name="proj")(out)

    with_attention(NoBias, "no_bias")

    class BlockdiagAttn(swinir_mod.WindowAttention):
        """QK^T / AV as single block-diagonal-packed gemms per window:
        contraction 60 instead of 10 (6x MXU K-utilization) at the cost of
        materializing the packed operands (HBM traffic). Data decides."""

        @nn.compact
        def __call__(self, x, mask=None):
            import jax.scipy.linalg as jsp

            bn, n, c = x.shape
            h = self.num_heads
            head_dim = c // h
            qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype, name="qkv")(x)
            qkv = qkv.reshape(bn, n, 3, h, head_dim).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]  # [bn, h, n, d]
            scale = head_dim**-0.5

            kT = k.transpose(0, 1, 3, 2)  # [bn, h, d, n]
            kblk = jax.vmap(
                lambda ks: jsp.block_diag(*[ks[i] for i in range(h)])
            )(kT)  # [bn, h*d, h*n]
            q2 = q.transpose(0, 2, 1, 3).reshape(bn, n, h * head_dim)
            s = (q2 * scale) @ kblk  # [bn, n, h*n]
            attn = s.reshape(bn, n, h, n).transpose(0, 2, 1, 3)

            bias = _rel_bias(self, n, h)
            attn = attn + bias[None].astype(attn.dtype)
            if mask is not None:
                nw = mask.shape[0]
                attn = attn.reshape(bn // nw, nw, h, n, n) + mask[
                    None, :, None
                ].astype(attn.dtype)
                attn = attn.reshape(bn, h, n, n)
            attn = jax.nn.softmax(
                attn.astype(self.softmax_dtype), axis=-1
            ).astype(self.dtype)

            vblk = jax.vmap(
                lambda vs: jsp.block_diag(*[vs[i] for i in range(h)])
            )(v)  # [bn, h*n, h*d]
            p2 = attn.transpose(0, 2, 1, 3).reshape(bn, n, h * n)
            out = p2 @ vblk  # heads already concatenated
            return nn.Dense(c, dtype=self.dtype, name="proj")(out)

    with_attention(BlockdiagAttn, "blockdiag_attn")
    # production impls of the same two ideas (models/swinir.py attn_impl):
    # the arms bench.py can run as full train steps via GRAFT_BENCH_ATTN —
    # timed here too so profiler and bench numbers cross-check
    ablate({"attn_impl": "blockdiag"}, "blockdiag_impl")
    ablate({"attn_impl": "paired"}, "paired_impl")

    class PairedWindowAttn(swinir_mod.WindowAttention):
        """Two windows packed into one M=128 attention: scores become
        [2n, 2n] with an additive block-diagonal mask (off-diagonal
        -100 -> softmax ~0, same trick as the shift mask), so each
        score/AV matmul fills a full 128-row MXU tile instead of two
        half-empty 64-row passes — 2x fewer MXU passes for 2x larger
        intermediates. Data decides."""

        @nn.compact
        def __call__(self, x, mask=None):
            bn, n, c = x.shape
            h = self.num_heads
            head_dim = c // h
            p = 2  # windows per pack: p*n = 128 exactly at ws=8
            if bn % p:
                raise ValueError(f"window count {bn} not divisible by {p}")
            if mask is not None and mask.shape[0] % p:
                # shifted layers need whole pairs within one image's nW
                raise ValueError(
                    f"per-image window count {mask.shape[0]} not "
                    f"divisible by pack size {p}"
                )
            # unshifted layers may pair across image boundaries: the kill
            # mask zeroes all cross-window probs, so pairing is image-blind
            qkv = nn.Dense(3 * c, use_bias=True, dtype=self.dtype, name="qkv")(x)
            qkv = qkv.reshape(bn // p, p * n, 3, h, head_dim).transpose(
                2, 0, 3, 1, 4
            )
            q, k, v = qkv[0], qkv[1], qkv[2]  # [bn/p, h, p*n, d]
            scale = head_dim**-0.5
            attn = (q * scale) @ k.transpose(0, 1, 3, 2)  # [bn/p, h, pn, pn]

            bias = _rel_bias(self, n, h)
            # block-diag tile of the per-window bias + cross-window kill
            eye = jnp.eye(p, dtype=bias.dtype)
            bias_pair = jnp.einsum("ab,hnm->hanbm", eye, bias).reshape(
                h, p * n, p * n
            )
            kill = (1.0 - jnp.eye(p)) * -100.0
            kill = jnp.repeat(jnp.repeat(kill, n, 0), n, 1)  # [pn, pn]
            attn = attn + (bias_pair + kill[None]).astype(attn.dtype)[None]

            if mask is not None:  # [nW, n, n] per-window shift mask
                nw = mask.shape[0]
                m = jnp.asarray(mask).reshape(nw // p, p, n, n)
                m_pair = jnp.einsum(
                    "ab,wanm->wanbm", eye.astype(m.dtype), m
                ).reshape(nw // p, p * n, p * n)
                attn = attn.reshape(
                    bn // nw, nw // p, h, p * n, p * n
                ) + m_pair[None, :, None].astype(attn.dtype)
                attn = attn.reshape(bn // p, h, p * n, p * n)

            attn = jax.nn.softmax(
                attn.astype(self.softmax_dtype), axis=-1
            ).astype(self.dtype)
            out = (attn @ v).transpose(0, 2, 1, 3).reshape(bn, n, c)
            return nn.Dense(c, dtype=self.dtype, name="proj")(out)

    with_attention(PairedWindowAttn, "paired_windows")

    # the per-head einsums the default path's fused kernel
    # (ops/pallas_window_attn.py) replaces on a TPU
    ablate({"attn_impl": "xla"}, "einsum_window_attn")

    # bf16 softmax accumulation (no f32 round-trip on the [bn,h,n,n] probs)
    ablate({"softmax_dtype": jnp.bfloat16}, "bf16_softmax")

    # bf16 LayerNorms (halves LN HBM traffic; bandwidth-bound hypothesis)
    ablate({"norm_dtype": jnp.bfloat16}, "bf16_ln")
    # everything bf16: norms + softmax accumulation
    ablate(
        {"norm_dtype": jnp.bfloat16, "softmax_dtype": jnp.bfloat16},
        "all_bf16",
    )

    # occupancy: 4x batch through the full step
    if TINY:
        return 1 if failures else 0
    batch72 = make_batch(4 * BATCH)
    mesh2, state2, step2, _ = build_step(model, batch72)
    report("batch72", time_step(mesh2, state2, step2, batch72), batch=4 * BATCH)
    return 1 if failures else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
