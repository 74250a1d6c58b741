#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main paths once on a real TPU, in ONE process, through the entry
points a user calls: both drivers' ``main(argv)`` at full SwinIR-S / ESPCN
width, GPT-2 125M through ``TrainStep`` and through the serving engine, and
the two Pallas attention kernels against XLA. Weights and data are random,
made from a seed. Each phase prints one JSON line; a failed phase makes the
exit code non-zero; the last line of stdout is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only when every phase passed on a TPU. ``--chips 4`` runs the
sharded paths (and the one-device runs they are compared with) instead, on a
four-chip host. No platform is forced and no environment variable of this
repo is needed. Longer output (driver logs, shard tables) goes to
``chiprun_out/``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips, sharded paths only
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
SEED = 0

# bf16 compute against a float32 "highest" forward of the same weights: the
# initial loss is ~ln(vocab) = 10.8, and bf16 keeps 8 bits of mantissa
BF16_LOSS_TOL = 5e-2
# the same step on four devices and on one: same arithmetic, another
# reduction order (and Adam's first steps amplify it)
SHARDED_LOSS_TOL = 2e-2
# kernel vs XLA at "highest" precision, relative to the reference's
# largest magnitude; bf16 has eps 2^-8 and the backward chains three dots
KERNEL_REL_TOL = {"float32": 2e-2, "bfloat16": 4e-2}
# GPT-2's attention core on bf16 operands, as the cells run it (ISSUE 30)
CORE_REL_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at. The defaults are the real sizes; a CPU
    rehearsal builds a smaller instance — the program has no option for it."""

    # 90 batches of 18 and 10 of validation. Not fewer: the driver's
    # one-cycle schedule climbs from lr 4e-4 to 1e-2 over nine tenths of the
    # epoch, and climbs too fast for the loss to fall in an epoch of 20
    stoke_argv: tuple = (
        "--synthetic", "--synthetic-n", "1800", "--batchSize", "18",
        "--nEpochs", "1", "--threads", "2", "--fp16", "bf16",
    )
    fairscale_argv: tuple = (
        "--synthetic", "--synthetic-n", "512", "--epochs", "1",
        "--batch-size", "40", "--workers", "2",
    )
    gpt2_layers: int | None = None  # None = the published depth
    gpt2_batch: int = 8
    gpt2_seq: int = 1024
    train_steps: int = 5
    serve_prompt_lens: tuple = (5, 19, 40, 70)
    serve_new_tokens: int = 12
    serve_max_len: int = 128
    window_shape: tuple = (18 * 64, 64, 180, 6)  # qkv [B*nW, n, 3c], heads
    window_mask_nw: int = 64
    flash_shape: tuple = (8, 1024, 12, 64)  # [B, T, H, Dh]
    # the GPT-2 cells' attention cores, a chip's share: [B, T, H, Dh]
    core_shapes: tuple = ((12, 1024, 12, 64), (4, 1024, 25, 64))
    kernels_interpret: bool = False
    matmul_n: int = 8192
    elementwise_bytes: int = 2 << 30
    # --chips 4: global batch 72 = 18 x 4 local devices, on the four-device
    # mesh and on the one-device mesh alike; again 90 batches, of which the first three optimizer steps (two
    # microbatches each) are compared
    stoke4_argv: tuple = (
        "--synthetic", "--synthetic-n", "7200", "--nEpochs", "1",
        "--threads", "2", "--fp16", "bf16",
    )
    stoke4_batch: int = 18
    sharded_steps: int = 3


def emit(line: dict) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.jsonl"), "a") as f:
        f.write(text + "\n")


class CompileMeter:
    """What jax itself reports about compiling, so a phase's wall time
    splits into compile and the rest without guessing. ``compile_s`` is the
    backend's share (XLA and Mosaic, or the read of a cached executable):
    the part a warm persistent cache saves. Tracing and lowering are listed
    beside it; nested jits report theirs twice, so they are not subtracted.
    """

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _TRACING = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )
    _COUNTED = ("compile_requests_use_cache", "cache_hits", "cache_misses")

    def __init__(self):
        import jax.monitoring

        self.totals = self._zero()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _zero(self) -> dict:
        return {"compile_s": 0.0, "trace_lower_s": 0.0,
                **dict.fromkeys(self._COUNTED, 0)}

    def _duration(self, event, duration, **_):
        if event == self._BACKEND:
            self.totals["compile_s"] += duration
        elif event in self._TRACING:
            self.totals["trace_lower_s"] += duration

    def _event(self, event, **_):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in self._COUNTED:
            self.totals[key] += 1

    def take(self) -> dict:
        """Totals since the last call."""
        out, self.totals = self.totals, self._zero()
        return out


def memory(devices) -> dict:
    """The allocator's view, per device; the peaks are high-water marks
    since the process started. ``peak_bytes_in_use`` counts arrays only;
    ``peak_bytes_reserved`` also holds the running programs' temporaries
    (what ``compiled.memory_analysis()`` plans), so it is the one that
    says how full the chip got."""
    keys = (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "largest_alloc_size",
    )
    stats = [d.memory_stats() or {} for d in devices]
    return {k: [s.get(k) for s in stats] for k in keys}


def run_phase(name: str, fn, meter: CompileMeter) -> bool:
    """Run one phase, print its line, then drop what it left on the device
    (one phase's leftovers can be the next one's out-of-memory)."""
    import jax

    meter.take()
    t0 = time.perf_counter()
    try:
        info, ok = fn(), True
    except Exception:  # reported below; the run then exits non-zero
        info, ok = {"error": traceback.format_exc()[-3000:]}, False
    wall = time.perf_counter() - t0
    compiled = meter.take()
    gc.collect()
    jax.clear_caches()
    gc.collect()
    compile_s = compiled.pop("compile_s")
    emit({
        "phase": name, "ok": ok, "wall_s": round(wall, 3),
        "compile_s": round(compile_s, 3),
        "steady_s": round(wall - compile_s, 3),
        "trace_lower_s": round(compiled.pop("trace_lower_s"), 3),
        "cache": compiled, **info, "mem_after": memory(jax.devices()),
    })
    return ok


class StepLog:
    """Losses as the steps produce them, each waited for and timestamped."""

    def __init__(self):
        self.losses, self.stamps, self.t0 = [], [], time.perf_counter()

    def add(self, loss) -> None:
        import jax

        self.losses.append(float(jax.block_until_ready(loss)))
        self.stamps.append(round(time.perf_counter() - self.t0, 3))

    def check_falls(self, window: int = 1) -> dict:
        import numpy as np

        if len(self.losses) < 2 * window:
            raise AssertionError(f"only {len(self.losses)} steps ran")
        if not np.all(np.isfinite(self.losses)):
            raise AssertionError(f"non-finite loss: {self.losses}")
        first = float(np.mean(self.losses[:window]))
        last = float(np.mean(self.losses[-window:]))
        if not last < first:
            raise AssertionError(f"loss did not fall: {self.losses}")
        return {
            "steps": len(self.losses), "loss_first": first,
            "loss_last": last, "first_step_done_s": self.stamps[0],
            "last_step_done_s": self.stamps[-1],
        }


# the facade's compiled programs for a training window: the fused eager
# window, or the split path's grad program and update program
FACADE_PROGRAMS = ("_jit_eager_step", "_jit_loss_grad", "_jit_apply")


def recording_stoke(base, log: StepLog, *, mesh=None, want_hlo=False):
    """The driver's ``Stoke``, with each microbatch loss recorded and each
    training program's calls counted; optionally on a given mesh, and
    keeping the compiled text of every program that ran."""

    class RecordingStoke(base):
        instance = None
        calls = dict.fromkeys(FACADE_PROGRAMS, 0)
        hlo = {}

        def __init__(self, *args, **kwargs):
            if mesh is not None:
                kwargs["mesh"] = mesh
            super().__init__(*args, **kwargs)
            RecordingStoke.instance = self

        def _build_jits(self):
            super()._build_jits()
            for name in FACADE_PROGRAMS:
                setattr(self, name, self._counted(name, getattr(self, name)))

        @staticmethod
        def _counted(name, jitted):
            def call(*args):
                RecordingStoke.calls[name] += 1
                if want_hlo and name not in RecordingStoke.hlo:
                    RecordingStoke.hlo[name] = (
                        jitted.lower(*args).compile().as_text()
                    )
                return jitted(*args)

            return call

        def detach_and_sync_loss(self, loss):
            out = super().detach_and_sync_loss(loss)
            log.add(out)
            return out

    return RecordingStoke


@contextlib.contextmanager
def swapped(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextlib.contextmanager
def driver_run(phase: str):
    """Run a driver from a directory of its own (it writes ``checkpoint/``
    and ``metrics.jsonl`` under the working directory), its chatter sent to
    a log file so the phase lines stay readable."""
    os.makedirs(OUT_DIR, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=f"chip_smoke_{phase}_")
    cwd = os.getcwd()
    try:
        os.chdir(workdir)
        with open(os.path.join(OUT_DIR, f"{phase}.log"), "w") as log:
            with contextlib.redirect_stdout(log):
                yield workdir
    finally:
        os.chdir(cwd)
        shutil.rmtree(workdir, ignore_errors=True)


# -- phases, one chip --------------------------------------------------------


def phase_device(chips: int, cache_path: str | None) -> dict:
    import jax
    import jaxlib

    from pytorch_distributedtraining_tpu.runtime.cache import cache_entry_count
    from pytorch_distributedtraining_tpu.runtime.launch import local_tpu_chips

    devs = jax.devices()
    info = {
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "count": len(devs),
        "memory_stats_keys": sorted((devs[0].memory_stats() or {}).keys()),
        "bytes_limit": (devs[0].memory_stats() or {}).get("bytes_limit"),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "pci_tpu_chips": local_tpu_chips(),
        "cache_dir": cache_path,
        "cache_entries_before": cache_entry_count(cache_path),
    }
    if devs[0].platform != "tpu" or len(devs) != chips:
        raise RuntimeError(f"need {chips} TPU chip(s), jax found: {info}")
    return info


def phase_csrc() -> dict:
    import numpy as np

    from pytorch_distributedtraining_tpu import csrc

    if shutil.which("g++") is None:
        raise RuntimeError("no g++ on this machine: cannot build fastpipe.cpp")
    discarded = csrc.rebuild()  # raises with g++'s stderr if the build fails
    rng = np.random.default_rng(SEED)
    arrays = [rng.random((64, 64, 3), dtype=np.float32) for _ in range(16)]
    if not np.array_equal(csrc.fast_stack(arrays), np.stack(arrays)):
        raise AssertionError("native fast_stack disagrees with np.stack")
    return {
        "live_path": "native" if csrc.available() else "numpy",
        "discarded_existing_so": discarded,
        "checked": "rebuilt from fastpipe.cpp; fast_stack == np.stack",
    }


def phase_swinir_driver(sizes: Sizes) -> dict:
    from drivers import fairscale_ddp, stoke_ddp

    stoke_log = StepLog()
    cls = recording_stoke(stoke_ddp.Stoke, stoke_log)
    with driver_run("swinir_driver") as workdir:
        with swapped(stoke_ddp, "Stoke", cls):
            train_loss, val_loss = stoke_ddp.main(list(sizes.stoke_argv))
        saved = sorted(os.listdir(os.path.join(workdir, "checkpoint")))
    inst, cls.instance = cls.instance, None
    described = (
        f"{type(inst._module).__name__} policy={inst.policy.name} "
        f"precision={inst.fp16} accum={inst.grad_accum_steps} "
        f"mesh={dict(inst.mesh.shape)}"
    )
    del inst
    programs = dict(cls.calls)
    if not saved:
        raise AssertionError("the driver saved no checkpoint")
    if not (train_loss == train_loss and val_loss == val_loss):
        raise AssertionError(f"epoch losses {train_loss}, {val_loss}")
    # two microbatches make one optimizer step in this driver
    stoke = stoke_log.check_falls(window=2)

    espcn_log = StepLog()

    class RecordingTrainStep(fairscale_ddp.TrainStep):
        def __call__(self, state, batch, lr_factor=1.0):
            state, metrics = super().__call__(state, batch, lr_factor)
            espcn_log.add(metrics["loss"])
            return state, metrics

    with driver_run("espcn_driver"):
        with swapped(fairscale_ddp, "TrainStep", RecordingTrainStep):
            last = fairscale_ddp.main(list(sizes.fairscale_argv))
    espcn = espcn_log.check_falls(window=2)
    if last != espcn_log.losses[-1]:
        raise AssertionError("driver's returned loss is not its last step's")
    return {
        "stoke_ddp": {
            "model": described, "facade_program_calls": programs, **stoke,
            "epoch_train_loss": train_loss,
            "epoch_val_loss": val_loss, "checkpoint": saved,
        },
        "fairscale_ddp": {"model": "Net(ESPCN) policy=ZeRO2", **espcn},
        "checked": "main(argv) of both drivers; every loss finite; loss "
                   "falls; checkpoint written",
    }


def gpt2_config(sizes: Sizes, **changes):
    from pytorch_distributedtraining_tpu.models import GPT2Config

    cfg = GPT2Config.gpt2_125m()
    if sizes.gpt2_layers is not None:
        changes["n_layer"] = sizes.gpt2_layers
    return dataclasses.replace(cfg, **changes)


def gpt2_batch(sizes: Sizes, vocab: int):
    import numpy as np

    tok = np.random.default_rng(SEED).integers(
        0, vocab, (sizes.gpt2_batch, sizes.gpt2_seq + 1)
    ).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def gpt2_train_setup(sizes: Sizes, mesh, policy):
    """GPT-2 125M under ``policy`` on ``mesh``: (model, state, step)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu import optim
    from pytorch_distributedtraining_tpu.models import GPT2, cross_entropy_loss
    from pytorch_distributedtraining_tpu.parallel import (
        TrainStep,
        create_train_state,
    )
    from pytorch_distributedtraining_tpu.precision import Policy as Precision

    model = GPT2(gpt2_config(sizes))

    def loss_fn(params, batch, rng, model_state):
        tokens, targets = batch
        logits = model.apply({"params": params}, tokens)
        return cross_entropy_loss(logits, targets), {}

    tx = optim.adamw(lr=3e-4, clip_grad_norm=1.0)
    state, shardings = create_train_state(
        init_fn=lambda r: (
            model.init(r, jnp.zeros((1, 8), jnp.int32))["params"], {},
        ),
        tx=tx, mesh=mesh, policy=policy, rng=jax.random.PRNGKey(SEED),
    )
    step = TrainStep(
        loss_fn, tx, mesh, policy, precision=Precision.from_name("bf16"),
        state_shardings=shardings,
    )
    return model, state, step


def phase_gpt2_train(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models import GPT2, cross_entropy_loss
    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
    from pytorch_distributedtraining_tpu.parallel import DDP
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec.ddp())
    model, state, step = gpt2_train_setup(sizes, mesh, DDP())
    batch = gpt2_batch(sizes, model.cfg.vocab_size)

    # the reference, before step 0 donates the weights: a float32 forward
    # (the einsums by name: the default model's core on a TPU is the kernels)
    ref_model = GPT2(
        gpt2_config(sizes, dtype=jnp.float32), attn_fn=default_attention
    )
    kernels = require_attention_kernels(
        step.compiled_text(state, batch), model.cfg.n_layer
    )
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(
            lambda p, tok, tgt: cross_entropy_loss(
                ref_model.apply({"params": p}, tok), tgt
            )
        )(state.params, *batch))

    log = StepLog()
    with mesh:
        for _ in range(sizes.train_steps):
            state, metrics = step(state, batch)
            log.add(metrics["loss"])
    diff = abs(log.losses[0] - ref_loss)
    if not diff <= BF16_LOSS_TOL:
        raise AssertionError(
            f"step-0 loss {log.losses[0]} vs float32 reference {ref_loss}"
        )
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    return {
        "model": f"GPT2 {n_params / 1e6:.1f}M params, n_layer="
                 f"{model.cfg.n_layer}, tokens [{sizes.gpt2_batch}, "
                 f"{sizes.gpt2_seq}], bf16 policy, optim.adamw",
        **log.check_falls(), "losses": log.losses,
        "attention_kernels_in_step": kernels,
        "step0_vs_float32_reference": {
            "reference": ref_loss, "abs_diff": diff, "tol": BF16_LOSS_TOL,
        },
        "checked": "TrainStep x%d on one seeded batch: loss finite and "
                   "falling; step-0 loss == float32 'highest' forward (the "
                   "einsums) within tol; on a TPU the compiled step holds the "
                   "attention kernels, 3 a layer" % sizes.train_steps,
    }


def phase_gpt2_serve(sizes: Sizes) -> dict:
    """Greedy tokens are compared exactly, so both sides run float32 at
    'highest' matmul precision: random weights make near-ties in a 50k
    vocabulary, and bf16 rounding flips them between two correct programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributedtraining_tpu.models import GPT2
    from pytorch_distributedtraining_tpu.models.generate import generate
    from pytorch_distributedtraining_tpu.serve import build_engine
    from pytorch_distributedtraining_tpu.serve.scheduler import Request

    cfg = gpt2_config(sizes, dtype=jnp.float32)
    model = GPT2(cfg, decode=True)
    params = jax.jit(
        lambda r: GPT2(cfg).init(r, jnp.zeros((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [
        rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        for n in sizes.serve_prompt_lens
    ]
    new = sizes.serve_new_tokens
    with jax.default_matmul_precision("highest"):
        eng = build_engine(
            model, params, n_slots=4, page_size=16,
            max_len=sizes.serve_max_len, prefill_chunk=32,
            prefill_buckets=(8, 16, 32), temperature=0.0,
        )
        donated = eng._donate()
        if jax.default_backend() != "cpu" and donated != (1,):
            raise AssertionError(f"page pool not donated: {donated}")
        t0 = time.perf_counter()
        warm = eng.warmup()
        warm_s = time.perf_counter() - t0
        records = eng.run(
            [Request(i, p, new) for i, p in enumerate(prompts)],
            realtime=False,
        )
        served_s = time.perf_counter() - t0 - warm_s
        recompiles = eng.steady_recompiles()
        programs = eng.metrics()["compiled_programs"]
        mismatches = []
        for rec in sorted(records, key=lambda r: r["rid"]):
            prompt = prompts[rec["rid"]]
            ref = jax.jit(functools.partial(
                generate, model, max_new_tokens=new, temperature=0.0
            ))(params, jnp.asarray(prompt)[None, :])
            ref_new = np.asarray(ref)[0, len(prompt):].tolist()
            if rec["tokens"] != ref_new:
                mismatches.append(
                    {"rid": rec["rid"], "engine": rec["tokens"],
                     "generate": ref_new}
                )
    if len(records) != len(prompts):
        raise AssertionError(f"{len(records)} of {len(prompts)} delivered")
    if mismatches:
        raise AssertionError(f"tokens differ from generate(): {mismatches}")
    if recompiles:
        raise AssertionError(f"{recompiles} steady-state recompiles")
    return {
        "model": f"GPT2 n_layer={cfg.n_layer} float32, paged KV, 4 slots",
        "prompt_lens": list(sizes.serve_prompt_lens), "new_tokens": new,
        "warmup_s": round(warm_s, 3), "serve_s": round(served_s, 3),
        "warmup_programs": {k: round(v, 3) for k, v in warm.items()},
        "compiled_programs": programs, "steady_recompiles": recompiles,
        "donate_argnums": list(donated),
        "checked": "4 requests, greedy: tokens identical to "
                   "models.generate.generate(); 0 steady recompiles; page "
                   "pool donated",
    }


def _rel_err(got, ref) -> dict:
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)))
    return {"max_abs_err": err, "ref_max": float(jnp.max(jnp.abs(ref)))}


def _check_kernel(
    name, kernel, reference, args, dtype, interpret, tol=None
) -> dict:
    """Forward and gradient of ``kernel`` against ``reference`` (XLA at
    'highest' precision, float32) on the same device."""
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(
        jax.random.PRNGKey(SEED + 1),
        jax.eval_shape(kernel, *args).shape, jnp.float32,
    )

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    n_diff = tuple(range(len(args)))
    fwd = jax.jit(kernel).lower(*args).compile()
    bwd = jax.jit(
        jax.grad(scalar(kernel), argnums=n_diff)
    ).lower(*args).compile()
    if not interpret:
        for label, prog in (("forward", fwd), ("gradient", bwd)):
            if "tpu_custom_call" not in prog.as_text():
                raise AssertionError(f"{name} {label}: no Mosaic kernel in "
                                     "the compiled program")
    f32 = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(reference)(*f32)
        ref_grads = jax.jit(jax.grad(scalar(reference), argnums=n_diff))(*f32)
    def relative(e):
        return e["max_abs_err"] / max(e["ref_max"], 1.0)

    out = {
        "forward": _rel_err(fwd(*args), ref_out),
        # the worst of the gradients (q, k, v; for windows, qkv and the bias)
        "gradient": max(
            (_rel_err(g, r) for g, r in zip(bwd(*args), ref_grads)),
            key=relative,
        ),
    }
    tol = tol or KERNEL_REL_TOL[jnp.dtype(dtype).name]
    for label, e in out.items():
        e["tol_rel"] = tol
        if not relative(e) <= tol:
            raise AssertionError(f"{name} {label} vs XLA: {e}")
    return out


def phase_kernels(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention
    from pytorch_distributedtraining_tpu.ops.pallas_attn import (
        causal_attention_qkv,
        flash_attention,
    )
    from pytorch_distributedtraining_tpu.ops.pallas_window_attn import (
        window_attention_qkv,
    )

    interpret = sizes.kernels_interpret
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    bn, n, c3, h = sizes.window_shape
    qkv = jax.random.normal(keys[0], (bn, n, c3), jnp.float32)
    bias = 0.5 * jax.random.normal(keys[3], (h, n, n), jnp.float32)
    nw = sizes.window_mask_nw
    mask = jnp.where(
        jax.random.bernoulli(keys[4], 0.2, (nw, n, n)), -100.0, 0.0
    )

    # The only independent check of the window kernel on the chip (the
    # benchmark's reference for the SwinIR cells is the module's own
    # forward), so the reference is written here and shares no code with
    # the package: per-head einsums over [bn, heads, n, d].
    def window_ref(mask):
        d = c3 // 3 // h

        def ref(qkv, bias):
            q, k, v = qkv.reshape(bn, n, 3, h, d).transpose(2, 0, 3, 1, 4)
            s = jnp.einsum("whnd,whmd->whnm", q * d**-0.5, k) + bias[None]
            if mask is not None:
                s = (
                    s.reshape(bn // nw, nw, h, n, n) + mask[None, :, None]
                ).reshape(bn, h, n, n)
            out = jnp.einsum("whnm,whmd->whnd", jax.nn.softmax(s, -1), v)
            return out.transpose(0, 2, 1, 3).reshape(bn, n, h * d)
        return ref

    out = {}
    for label, m in (("window_attention", None),
                     ("window_attention_shift_mask", mask)):
        out[label] = _check_kernel(
            label,
            lambda qkv, bias, m=m: window_attention_qkv(
                qkv, bias, m, interpret
            ),
            window_ref(m), (qkv, bias), jnp.float32, interpret,
        )
        out[label]["shape"] = list(sizes.window_shape)

    for dtype in (jnp.bfloat16, jnp.float32):
        qkv = tuple(
            jax.random.normal(keys[5 + i], sizes.flash_shape, jnp.float32)
            .astype(dtype) for i in range(3)
        )
        label = f"flash_attention_{jnp.dtype(dtype).name}"
        out[label] = _check_kernel(
            label,
            lambda q, k, v: flash_attention(
                q, k, v, True, 128, 128, interpret
            ),
            lambda q, k, v: default_attention(q, k, v, causal=True),
            qkv, dtype, interpret,
        )
        out[label]["shape"] = list(sizes.flash_shape)

    # What the default GPT-2 runs on a TPU, at the two cells' shapes, over
    # ``qkv`` as ``c_attn`` writes it (12 heads pack two to a lane block, 25
    # do not). The reference is written here: einsums over [B, H, T, Dh].
    def core_ref(heads):
        def ref(qkv):
            b, t, d3 = qkv.shape
            q, k, v = qkv.reshape(b, t, 3, heads, d3 // 3 // heads).transpose(
                2, 0, 3, 1, 4
            )
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
            out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            return out.transpose(0, 2, 1, 3)
        return ref

    for b, t, heads, dh in sizes.core_shapes:
        label = f"gpt2_core_{heads}_heads"
        out[label] = _check_kernel(
            label,
            lambda qkv, heads=heads: causal_attention_qkv(
                qkv, heads, interpret
            ),
            core_ref(heads),
            (jax.random.normal(
                keys[0], (b, t, 3 * heads * dh), jnp.float32
            ).astype(jnp.bfloat16),),
            jnp.bfloat16, interpret, tol=CORE_REL_TOL,
        )
        out[label]["shape"] = [b, t, heads, dh]
    out["checked"] = (
        "forward and gradient of each kernel vs XLA ('highest', float32) on "
        "the same device; compiled program holds a tpu_custom_call"
        if not interpret else "interpreted kernels vs XLA (rehearsal)"
    )
    return out


def phase_reference_points(sizes: Sizes) -> dict:
    """Two printed facts that gate nothing: one large bf16 matmul and one
    elementwise pass, both timed to ``block_until_ready``."""
    import jax
    import jax.numpy as jnp

    n, reps = sizes.matmul_n, 10
    a = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    jax.block_until_ready(mm(a, a))
    t0 = time.perf_counter()
    for _ in range(reps):
        c = mm(a, a)
    jax.block_until_ready(c)
    mm_s = (time.perf_counter() - t0) / reps
    del a, c

    x = jnp.ones((sizes.elementwise_bytes // 4,), jnp.float32)
    ew = jax.jit(lambda x: x * 2.0 + 1.0)
    jax.block_until_ready(ew(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        y = ew(x)
    jax.block_until_ready(y)
    ew_s = (time.perf_counter() - t0) / reps
    return {
        "gates": "nothing",
        "matmul_bf16": {
            "n": n, "seconds": mm_s,
            "achieved_tflops": 2 * n**3 / mm_s / 1e12,
        },
        "elementwise_f32": {
            "array_bytes": x.nbytes, "seconds": ew_s,
            # one read and one write of the array
            "achieved_gbytes_per_s": 2 * x.nbytes / ew_s / 1e9,
        },
    }


# -- phases, four chips ------------------------------------------------------


def shard_table(tree, mesh, label: str) -> dict:
    """Where the bytes of ``tree`` live. Fails if a leaf whose sharding
    names a mesh axis is nevertheless held whole on one device."""
    import jax
    from jax.sharding import PartitionSpec

    ids = [d.id for d in mesh.devices.ravel()]
    per_device = {i: 0 for i in ids}
    rows, whole = [], []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        spec = getattr(leaf.sharding, "spec", PartitionSpec())
        sharded = any(s is not None for s in spec)
        shard_bytes = {
            s.device.id: s.data.nbytes for s in leaf.addressable_shards
        }
        for dev, nbytes in shard_bytes.items():
            per_device[dev] += nbytes
        name = jax.tree_util.keystr(path)
        rows.append({
            "leaf": name, "shape": list(leaf.shape), "spec": str(spec),
            "bytes": leaf.nbytes, "bytes_per_device": shard_bytes,
        })
        if sharded and max(shard_bytes.values()) >= leaf.nbytes:
            whole.append(name)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"shards_{label}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    if whole:
        raise AssertionError(f"{label}: sharded by spec, whole on a device: "
                             f"{whole[:5]}")
    return {
        "leaves": len(rows),
        "leaves_sharded": sum(r["spec"] != "PartitionSpec()" for r in rows),
        "total_bytes": sum(r["bytes"] for r in rows),
        "bytes_per_device": per_device,
    }


def require_collectives(counts: dict, *, gathers: bool) -> None:
    """The sharded step must really talk: gradients reduced across devices
    (XLA:TPU may spell a reduce-scatter as all-reduce or all-to-all), and
    under ZeRO-3 parameters gathered before use."""
    reduces = sum(
        counts.get(k, 0) for k in ("all-reduce", "reduce-scatter", "all-to-all")
    )
    if not reduces or (gathers and not counts.get("all-gather")):
        raise AssertionError(f"collectives missing from the step: {counts}")


def require_attention_kernels(text: str, n_layer: int) -> int:
    """A GPT-2 step compiled for a TPU runs its attention core as Mosaic
    kernels: forward, dq and dk/dv a layer (unrolled). A CPU rehearsal holds
    the einsums and none."""
    import jax

    found = text.count('custom_call_target="tpu_custom_call"')
    want = 3 * n_layer if jax.devices()[0].platform == "tpu" else 0
    if found != want:
        raise AssertionError(
            f"{found} Mosaic kernels in the compiled step, expected {want}"
        )
    return found


def compare_losses(a: list, b: list, tol: float) -> dict:
    diffs = [abs(x - y) for x, y in zip(a, b)]
    if len(a) != len(b) or not a or max(diffs) > tol:
        raise AssertionError(f"losses differ beyond {tol}: {a} vs {b}")
    return {"four_devices": a, "one_device": b, "max_abs_diff": max(diffs),
            "tol": tol}


def phase_fsdp4_gpt2(sizes: Sizes) -> dict:
    import jax

    from pytorch_distributedtraining_tpu.observe import hlo
    from pytorch_distributedtraining_tpu.parallel import FSDP
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    def run(mesh):
        model, state, step = gpt2_train_setup(sizes, mesh, FSDP())
        batch = gpt2_batch(sizes, model.cfg.vocab_size)
        info = {}
        if mesh.size > 1:
            info["params"] = shard_table(state.params, mesh, "fsdp4_params")
            info["opt_state"] = shard_table(
                state.opt_state, mesh, "fsdp4_opt_state"
            )
            text = step.compiled_text(state, batch)
            info["collectives"] = hlo.counts(text)
            require_collectives(info["collectives"], gathers=True)
            # each device runs the kernels over its own sequences
            info["attention_kernels_in_step"] = require_attention_kernels(
                text, model.cfg.n_layer
            )
            for part in ("params", "opt_state"):
                if not info[part]["leaves_sharded"]:
                    raise AssertionError(f"ZeRO-3 sharded no {part} leaf")
        log = StepLog()
        with mesh:
            for _ in range(sizes.sharded_steps):
                state, metrics = step(state, batch)
                log.add(metrics["loss"])
        log.check_falls()
        return log.losses, info

    devs = jax.devices()
    losses4, info = run(make_mesh(MeshSpec(fsdp=len(devs))))
    gc.collect()
    losses1, _ = run(make_mesh(MeshSpec(fsdp=1), devices=devs[:1]))
    return {
        "policy": "ZeRO-3 (parallel.FSDP) on make_mesh(MeshSpec(fsdp=4))",
        **info, "losses": compare_losses(losses4, losses1, SHARDED_LOSS_TOL),
        "checked": "3 steps on 4 devices == same step, seed and global batch "
                   "on 1 device within tol; every sharded leaf spread over "
                   "the devices; gathers, reductions and (on a TPU) the "
                   "attention kernels in the HLO",
    }


def phase_stoke4(sizes: Sizes) -> dict:
    import jax

    from drivers import stoke_ddp
    from pytorch_distributedtraining_tpu.observe import hlo
    from pytorch_distributedtraining_tpu.runtime.mesh import MeshSpec, make_mesh

    devs = jax.devices()

    def run(label, per_device_batch, mesh):
        log = StepLog()
        cls = recording_stoke(
            stoke_ddp.Stoke, log, mesh=mesh, want_hlo=mesh is None
        )  # the compiled text of the four-device run only
        argv = [*sizes.stoke4_argv, "--batchSize", str(per_device_batch)]
        with driver_run(label), swapped(stoke_ddp, "Stoke", cls):
            stoke_ddp.main(argv)
        inst, cls.instance = cls.instance, None
        info = {}
        if mesh is None:
            info["policy"] = inst.policy.name
            info["mesh"] = dict(inst.mesh.shape)
            info["params"] = shard_table(
                inst.state.params, inst.mesh, "stoke4_params"
            )
            info["opt_state"] = shard_table(
                inst.state.opt_state, inst.mesh, "stoke4_opt_state"
            )
            if not info["opt_state"]["leaves_sharded"]:
                raise AssertionError("OSS/ShardedDDP sharded no opt leaf")
            info["facade_program_calls"] = dict(cls.calls)
            info["collectives"] = {
                name: hlo.counts(text) for name, text in cls.hlo.items()
            }
            total = {}
            for counts in info["collectives"].values():
                for kind, n in counts.items():
                    total[kind] = total.get(kind, 0) + n
            # replicated params, optimizer state sharded: gradients are
            # reduced, and the sharded update is gathered back
            require_collectives(total, gathers=True)
            # the driver names no attention: on a mesh the default path
            # places the window kernel itself, each device over its own
            # windows (the partitioner cannot split a Mosaic call)
            info["window_kernels"] = {
                name: text.count('custom_call_target="tpu_custom_call"')
                for name, text in cls.hlo.items()
            }
            on_tpu = jax.default_backend() == "tpu"  # a CPU lowers einsums
            if on_tpu and not any(info["window_kernels"].values()):
                raise AssertionError(
                    "no window kernel in the four-device programs: "
                    f"{info['window_kernels']}"
                )
        log.check_falls(window=2)
        return log.losses, info

    # the same --batchSize both times: the facade's loader multiplies it by
    # jax.local_device_count() whatever the mesh, so both runs see 72
    losses4, info = run("stoke4", sizes.stoke4_batch, None)
    gc.collect()
    losses1, _ = run(
        "stoke4_one_device", sizes.stoke4_batch,
        make_mesh(MeshSpec.zero(1), devices=devs[:1]),
    )
    first = 2 * sizes.sharded_steps  # two microbatches an optimizer step
    return {
        **info,
        "losses": compare_losses(
            losses4[:first], losses1[:first], SHARDED_LOSS_TOL
        ),
        "checked": "drivers/stoke_ddp.py main() on 4 devices == on 1 device "
                   "at the same global batch within tol over the first 3 "
                   "optimizer steps; shard table; collectives in the HLO of "
                   "the facade programs that ran",
    }


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opt = parser.parse_args(argv)
    sys.path.insert(0, ROOT)

    # as the drivers start: arm libtpu's flags before the backend exists
    from pytorch_distributedtraining_tpu import runtime
    from pytorch_distributedtraining_tpu.runtime.cache import cache_entry_count

    runtime.initialize()
    cache_path = runtime.enable_compile_cache()
    import jax

    meter = CompileMeter()
    sizes = Sizes()
    t0 = time.perf_counter()
    if not run_phase(
        "device", lambda: phase_device(opt.chips, cache_path), meter
    ):
        return 2
    if opt.chips == 1:
        phases = [
            ("csrc", phase_csrc),
            ("swinir_driver", lambda: phase_swinir_driver(sizes)),
            ("gpt2_train", lambda: phase_gpt2_train(sizes)),
            ("gpt2_serve", lambda: phase_gpt2_serve(sizes)),
            ("kernels", lambda: phase_kernels(sizes)),
            ("reference_points", lambda: phase_reference_points(sizes)),
        ]
    else:
        phases = [
            ("fsdp4_gpt2", lambda: phase_fsdp4_gpt2(sizes)),
            ("stoke4", lambda: phase_stoke4(sizes)),
        ]
    failed = [name for name, fn in phases if not run_phase(name, fn, meter)]
    dev = jax.devices()[0]
    emit({
        "phase": "summary", "ok": not failed, "failed": failed,
        "wall_s": round(time.perf_counter() - t0, 3),
        "cache_dir": cache_path,
        "cache_entries_after": cache_entry_count(cache_path),
    })
    if failed:
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
