"""The Stoke facade: one object owning model, optimizer, loss, precision,
distribution, grad-accum/clip, data loading, checkpointing and rank I/O.

Twin of stoke's ``Stoke`` class exactly as the reference drives it
(`/root/reference/Stoke-DDP.py:240-254` construction; runtime surface
`.model :73`, `.loss :74`, `.backward :79`, `.step :82`, `.model_access
:68,104`, `.optimizer :300-301`, `.DataLoader :286-298`, `.save :142-145`,
`.world_size/.rank :274-275`, `.print_on_devices :67,130`, `.print_ema_loss
:76`, `.detach_and_sync_loss :86`).

TPU-native architecture (hard part (d) of SURVEY §7): the eager-feeling
``.model → .loss → .backward → .step`` sequence is backed by three compiled
programs — forward, loss+grad, apply — so user code keeps the reference's
loop shape while every FLOP runs under jit with the policy's shardings. The
fused path (:meth:`fused_step`) collapses all three into the single
TrainStep program for peak throughput; both paths share state bit-for-bit.

Grad accumulation follows Stoke semantics: ``.backward`` scales by
``1/grad_accum_steps`` and accumulates; ``.step`` fires the optimizer every
``grad_accum_steps``-th call (`Stoke-DDP.py:251` with the update at `:82`).
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import threading
import time
import weakref
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from .. import checkpoint as ckpt
from .. import optim as optim_mod
from ..data import DataLoader as _DataLoader
from ..observe import trace as _telemetry
from ..observe.profiling import remember_program
from ..ops import sync_scalar_device
from ..parallel import (
    CompressedGradStep,
    HierGradStep,
    TrainStep,
    create_train_state,
    policy_from_flags,
    wire_format,
)
from ..parallel.remat import apply_remat, resolve_remat
from ..parallel.spec import (
    batch_layout,
    constrain,
    shard_axis,
    stream_to_device,
)
from ..precision import DynamicLossScaler, Policy as PrecisionPolicy
from ..runtime import dist as _dist
from ..runtime.mesh import (
    MeshSpec,
    batch_spec,
    make_hybrid_mesh,
    make_mesh,
    slice_axis,
)
from .config import (
    AMPConfig,
    ClipGradConfig,
    ClipGradNormConfig,
    DDPConfig,
    DeepspeedConfig,
    DistributedOptions,
    FairscaleFSDPConfig,
    FairscaleOSSConfig,
    FP16Options,
    TPUConfig,
)
from .optimizer import StokeOptimizer


def _remat_from_env(configured):
    """Resolve the effective remat policy: explicit TPUConfig wins, else the
    ``GRAFT_REMAT`` env supplies one ("none"/"full"/"dots"/"names"/
    "offload"), else off. Validated here so a typo fails at construction."""
    if configured:  # explicit config (True or a named policy) wins
        return configured
    env = os.environ.get("GRAFT_REMAT")
    if env is None:
        return configured
    return resolve_remat(env)


def _pp_from_env(cfg):
    """Resolve the pipeline knobs: ``$GRAFT_PP`` / ``$GRAFT_PP_SCHEDULE`` /
    ``$GRAFT_PP_MICRO`` override the TPUConfig fields (deploy-time twins,
    same pattern as GRAFT_REMAT). Returns ``(pp, schedule, n_micro)``;
    schedule spelling is validated at PipelineStep construction."""
    pp = int(os.environ.get("GRAFT_PP", cfg.pp or 1))
    schedule = os.environ.get("GRAFT_PP_SCHEDULE", cfg.pp_schedule or "1f1b")
    n_micro = int(os.environ.get("GRAFT_PP_MICRO", cfg.pp_micro or 0))
    return pp, schedule, n_micro


def _apply_scan_layers_env(model):
    """``GRAFT_SCAN_LAYERS=1|0`` flips a model's ``scan_layers`` flag.

    Deploy-time twin of the model constructor arg. Covers both flag
    placements: a direct module field (SwinIR) and a ``cfg`` dataclass
    field (GPT2/ViT). Models without the flag (or a non-flax wrapper)
    pass through untouched, so the env is safe to export globally.
    """
    env = os.environ.get("GRAFT_SCAN_LAYERS")
    if env is None or not hasattr(model, "clone"):
        return model
    want = env.strip().lower() in ("1", "true", "on", "yes")
    if hasattr(model, "scan_layers"):
        if bool(model.scan_layers) == want:
            return model
        return model.clone(scan_layers=want)
    cfg = getattr(model, "cfg", None)
    if cfg is not None and hasattr(cfg, "scan_layers"):
        if bool(cfg.scan_layers) == want:
            return model
        return model.clone(cfg=dataclasses.replace(cfg, scan_layers=want))
    return model


def _wire_from_env(cfg):
    """Resolve the quantized gradient wire: ``$GRAFT_WIRE`` overrides
    ``TPUConfig.wire`` (deploy-time twin, same pattern as GRAFT_REMAT).
    Returns a ``WireFormat`` or None; a typoed spelling fails here, at
    construction, not mid-training."""
    spec = os.environ.get("GRAFT_WIRE", cfg.wire)
    return wire_format(spec)


def _hier_from_env(cfg):
    """Resolve the two-level gradient sync: ``$GRAFT_HIER`` overrides
    ``TPUConfig.hier`` (same env-twin pattern as GRAFT_WIRE)."""
    env = os.environ.get("GRAFT_HIER")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off", "no")
    return bool(cfg.hier)


def _apply_fp8_env(model, cfg):
    """``$GRAFT_FP8``/``TPUConfig.fp8`` clone an fp8 matmul mode onto
    models whose ``cfg`` dataclass carries an ``fp8`` field (GPT-2/ViT —
    see ``precision.fp8_dot_general_cls``). Returns ``(model, mode)``;
    models without the field pass through with a warning when the knob
    is set — their matmuls have no fp8 tagging, and pretending otherwise
    would mislabel every number downstream."""
    spec = os.environ.get("GRAFT_FP8", cfg.fp8)
    if spec is None or str(spec).strip().lower() in (
        "", "off", "none", "0", "false",
    ):
        return model, None
    from ..precision import FP8_DTYPES

    mode = str(spec).strip().lower()
    if mode not in FP8_DTYPES:
        raise ValueError(
            f"fp8 mode {spec!r} unknown; have {sorted(FP8_DTYPES)}"
        )
    mcfg = getattr(model, "cfg", None)
    if (
        hasattr(model, "clone")
        and mcfg is not None
        and hasattr(mcfg, "fp8")
    ):
        if mcfg.fp8 == mode:
            return model, mode
        return model.clone(cfg=dataclasses.replace(mcfg, fp8=mode)), mode
    import warnings

    warnings.warn(
        f"fp8={mode!r} requested but {type(model).__name__} has no fp8 "
        "config field — matmuls stay at the model dtype (the fp8 path "
        "covers the GPT-2/ViT trunks)",
        stacklevel=3,
    )
    return model, None


def _numerics_from_env(cfg):
    """Resolve the numerics plane: ``$GRAFT_NUMERICS`` overrides
    ``TPUConfig.numerics`` (same env-twin pattern as GRAFT_WIRE), and
    ``$GRAFT_NUMERICS_ACTION`` overrides ``TPUConfig.numerics_action``.
    Returns ``(enabled, action)``; a bad action spelling fails here, at
    construction, not at the first watchdog trip."""
    env = os.environ.get("GRAFT_NUMERICS")
    if env is not None:
        on = env.strip().lower() not in ("", "0", "false", "off", "no")
    else:
        on = bool(cfg.numerics)
    action = (
        os.environ.get("GRAFT_NUMERICS_ACTION", cfg.numerics_action)
        .strip().lower()
        or "halt"
    )
    from ..observe.numerics import ACTIONS

    if action not in ACTIONS:
        raise ValueError(
            f"numerics action {action!r}: expected one of {ACTIONS} "
            "(GRAFT_NUMERICS_ACTION / TPUConfig.numerics_action)"
        )
    return on, action


def _opcost_from_env(cfg):
    """Resolve the op-cost plane: ``$GRAFT_OPCOST`` overrides
    ``TPUConfig.opcost`` (same env-twin pattern as GRAFT_NUMERICS)."""
    env = os.environ.get("GRAFT_OPCOST")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off", "no")
    return bool(cfg.opcost)


def _capture_from_env(cfg):
    """Resolve the anomaly-triggered capture: ``$GRAFT_CAPTURE``
    overrides ``TPUConfig.capture``; a value that is neither a boolean
    spelling nor empty is the capture directory (on + dir), overriding
    ``TPUConfig.capture_dir``. Returns ``(enabled, capture_dir)``."""
    cap_dir = cfg.capture_dir
    env = os.environ.get("GRAFT_CAPTURE")
    if env is None:
        return bool(cfg.capture), cap_dir
    v = env.strip()
    if v.lower() in ("", "0", "false", "off", "no"):
        return False, cap_dir
    if v.lower() not in ("1", "true", "on", "yes"):
        cap_dir = v
    return True, cap_dir


def _telemetry_from_env(cfg):
    """Resolve the telemetry switch: ``$GRAFT_TELEMETRY`` overrides
    ``TPUConfig.telemetry`` (deploy-time twin, same pattern as GRAFT_WIRE);
    a non-empty ``$GRAFT_TRACE`` — the Chrome-trace export destination —
    also turns the tracer on, overriding ``TPUConfig.trace_dir``. Returns
    ``(enabled, trace_dir)``; an explicit falsy $GRAFT_TELEMETRY wins over
    everything, so an operator can silence an instrumented config."""
    trace_dir = os.environ.get("GRAFT_TRACE", cfg.trace_dir) or None
    env = os.environ.get("GRAFT_TELEMETRY")
    if env is not None:
        on = env.strip().lower() not in ("", "0", "false", "off", "no")
        return on, trace_dir
    return bool(cfg.telemetry or trace_dir), trace_dir


def _serve_fastpath_overrides(cfg, overrides: dict) -> dict:
    """Fill the serve decode-fast-path knobs from TPUConfig twins.

    Precedence matches GRAFT_WIRE: explicit keyword ``overrides`` win,
    then the env knobs ($GRAFT_SERVE_SPEC_K / $GRAFT_SERVE_KV_WIRE,
    resolved downstream by ``serve_knobs_from_env``), then
    ``TPUConfig.serve_spec_k`` / ``TPUConfig.serve_kv_wire`` — so this
    helper only injects a config value when neither the caller nor the
    environment spoke.
    """
    out = dict(overrides)
    if (
        "spec_k" not in out
        and not (os.environ.get("GRAFT_SERVE_SPEC_K") or "").strip()
        and cfg.serve_spec_k
    ):
        out["spec_k"] = int(cfg.serve_spec_k)
    if (
        "kv_wire" not in out
        and not (os.environ.get("GRAFT_SERVE_KV_WIRE") or "").strip()
        and cfg.serve_kv_wire
    ):
        out["kv_wire"] = cfg.serve_kv_wire
    return out


@jax.jit
def _ema_update(ema, val):
    """0.98-decay loss monitor folded on device (`Stoke-DDP.py:76` EMA);
    keeping it as a compiled scalar op lets the facade track the loss
    without a per-step host sync."""
    with jax.named_scope("metrics"):
        return 0.98 * ema + 0.02 * jnp.asarray(val, jnp.float32)


class _AsyncScalarFetcher:
    """Last-value-wins background device→host fetch for display scalars.

    A blocking ``device_get`` inside the hot loop stalls the dispatch
    queue once per call. A display EMA doesn't need synchronous values:
    one daemon thread drains the newest submitted scalar while the main
    thread keeps dispatching; readers see the freshest *arrived* value.
    Exact reads stay on the blocking paths (``detach_and_sync_loss``,
    ``_last_loss``).
    """

    _IDLE_EXIT_S = 5.0  # a workless thread dies; submit() restarts it

    def __init__(self):
        self._cond = threading.Condition()
        self._pending = None
        self._busy = False
        self._thread = None
        self.value: float | None = None

    def submit(self, arr) -> None:
        """Queue ``arr`` for fetch, replacing any not-yet-started fetch."""
        with self._cond:
            self._pending = arr
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._drain, name="graft-scalar-fetch", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()

    def _drain(self) -> None:
        while True:
            with self._cond:
                deadline = time.monotonic() + self._IDLE_EXIT_S
                while self._pending is None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        # idle: exit rather than park forever; nulling the
                        # handle under the lock means a racing submit()
                        # starts a fresh worker instead of notifying this
                        # exiting one
                        self._thread = None
                        return
                    self._cond.wait(remaining)
                arr, self._pending = self._pending, None
                self._busy = True
            val = None
            try:
                # np.asarray blocks in C++ (GIL released) — not routed
                # through jax.device_get so sync-counting tests/monitors
                # see the hot loop as what it now is: sync-free
                val = float(np.asarray(arr))
            except Exception:
                val = None  # deleted/donated buffer: keep last value
            finally:
                # clears _busy even on BaseException (thread teardown):
                # a flush() waiter must never deadlock on a dead worker
                with self._cond:
                    if val is not None:
                        self.value = val
                    self._busy = False
                    self._cond.notify_all()

    def flush(self, timeout: float = 30.0) -> float | None:
        """Block until submitted fetches landed (or worker death/timeout);
        return the freshest value."""
        deadline = time.monotonic() + timeout
        with _telemetry.span("facade.loss_fetch.flush", "step"), self._cond:
            while self._pending is not None or self._busy:
                alive = self._thread is not None and self._thread.is_alive()
                remaining = deadline - time.monotonic()
                if not alive or remaining <= 0:
                    break
                self._cond.wait(min(0.5, remaining))
            return self.value


class _ModelAccess:
    """``stoke_model.model_access`` twin: `.train()`/`.eval()` mode switch
    (`Stoke-DDP.py:68,104`) plus passthrough to the underlying module."""

    def __init__(self, facade: "Stoke"):
        object.__setattr__(self, "_facade", facade)

    def train(self):
        self._facade._training = True
        return self

    def eval(self):
        self._facade._training = False
        return self

    def __getattr__(self, name):
        return getattr(self._facade._module, name)


def _forward_op(name):
    def op(self, *args):
        return getattr(self.materialize(), name)(*args)

    op.__name__ = name
    return op


class _LazyBase:
    """Shared machinery for deferred values: any use outside the fused
    ``loss → backward`` flow transparently materializes through the compiled
    programs, so the handles behave like the jax arrays they stand for
    (arithmetic, comparisons, indexing, numpy conversion, iteration)."""

    __slots__ = ("_facade", "_value", "__weakref__")

    def materialize(self):  # overridden
        raise NotImplementedError

    def __jax_array__(self):
        return self.materialize()

    def __array__(self, dtype=None):
        arr = np.asarray(jax.device_get(self.materialize()))
        return arr.astype(dtype) if dtype is not None else arr

    def __getitem__(self, idx):
        return self.materialize()[idx]

    def __len__(self):
        return len(self.materialize())

    def __iter__(self):
        return iter(self.materialize())

    def __float__(self):
        return float(jax.device_get(self.materialize()))

    def __bool__(self):
        return bool(self.materialize())

    def __format__(self, spec):
        return format(float(self), spec) if spec else repr(self)

    def __getattr__(self, name):
        return getattr(self.materialize(), name)

    def __repr__(self):
        state = "pending" if self._value is None else "materialized"
        return f"{type(self).__name__}<{state}>"


for _name in (
    "__add__", "__radd__", "__sub__", "__rsub__", "__mul__", "__rmul__",
    "__truediv__", "__rtruediv__", "__pow__", "__neg__", "__abs__",
    "__eq__", "__ne__", "__lt__", "__le__", "__gt__", "__ge__",
    "__matmul__", "__rmatmul__", "__mod__",
):
    setattr(_LazyBase, _name, _forward_op(_name))
_LazyBase.__hash__ = object.__hash__  # __eq__ above would otherwise drop it


class _LazyOutput(_LazyBase):
    """Deferred forward result from ``.model()`` on the training path.

    The reference loop is ``out = s.model(x); l = s.loss(out, y);
    s.backward(l); s.step()`` (`Stoke-DDP.py:73-82`). Running the forward
    inside ``.model()`` *and* again under grad inside ``.backward()`` pays
    2x forward; deferring it means the common loop executes exactly one
    compiled fwd+bwd program. The handle captures the params/model-state/rng
    in effect at the ``.model()`` call, so late materialization reproduces
    exactly what an eager forward would have computed — even after
    ``.step()`` has updated (and donated) the live params; ``.step()``
    force-materializes still-pending handles before donation invalidates
    their buffers. ``.shape``/``.dtype``/``.ndim`` come from ``eval_shape``
    without running the forward.
    """

    __slots__ = ("_inputs", "_params", "_model_state", "_rng_parts")

    def __init__(self, facade, inputs, params, model_state, rng_parts):
        self._facade = facade
        self._inputs = inputs
        self._params = params
        self._model_state = model_state
        # (base_rng, step): the fold_in happens lazily at materialization —
        # an eager fold per .model() call costs ~1 ms of host dispatch on
        # the hot loop for a handle that usually resolves from the fused
        # program instead
        self._rng_parts = rng_parts
        self._value = None

    def _rng(self):
        base, step = self._rng_parts
        return jax.random.fold_in(base, step)

    def materialize(self):
        if self._value is None:
            facade = self._facade
            with facade._span("facade.forward"):
                self._value, _ = facade._run(
                    "_jit_fwd", self._params, self._model_state,
                    self._inputs, self._rng(), train=True,
                )
        return self._value

    @property
    def _aval(self):
        base, step = self._rng_parts
        # the fold happens abstractly inside eval_shape: shape queries
        # must not pay the real fold_in dispatch
        out, _ = jax.eval_shape(
            lambda p, m, x, b, s: self._facade._jit_fwd(
                p, m, x, jax.random.fold_in(b, s), train=True
            ),
            self._params, self._model_state, self._inputs, base, step,
        )
        return out

    def __getattr__(self, name):
        if self._value is None and name in ("shape", "dtype", "ndim", "size"):
            return getattr(self._aval, name)
        return getattr(self.materialize(), name)


class _LazyLoss(_LazyBase):
    """Deferred loss from ``.loss()``; resolved for free by ``.backward()``
    (which computes the true loss inside the fused grad program) or on
    demand via the compiled forward + loss programs."""

    __slots__ = ("_output", "_targets")

    def __init__(self, facade, output, targets):
        self._facade = facade
        self._output = output
        self._targets = targets
        self._value = None

    def materialize(self):
        if self._value is None:
            self._value = self._facade._materialize_lazy_loss(self)
        return self._value


class Stoke:
    def __init__(
        self,
        model,
        optimizer: StokeOptimizer | dict,
        loss: Callable,
        batch_size_per_device: int = 1,
        verbose: bool = False,
        gpu: bool = False,  # parity no-op (device comes from the runtime)
        fp16: str | None = None,
        distributed: str | None = None,
        fairscale_oss: bool = False,
        fairscale_sddp: bool = False,
        fairscale_fsdp: bool = False,
        grad_accum_steps: int = 1,
        configs: list | None = None,
        grad_clip: ClipGradNormConfig | ClipGradConfig | None = None,
        *,
        sample_input=None,
        pretrained=None,
        mesh=None,
        rng_seed: int = 0,
        fuse_eager_step: bool = True,
        fused_optimizer: bool | None = None,
    ):
        _dist.initialize()
        t_construct = time.perf_counter()
        self._module = _apply_scan_layers_env(model)
        self._loss_callable = loss
        self.batch_size_per_device = int(batch_size_per_device)
        self.verbose = bool(verbose)
        # fuse_eager_step: run the reference-shaped backward()+step() pair
        # as ONE compiled program per accum window (backward defers, step
        # dispatches). Measured on chip: the split loss_grad+apply pair is
        # dispatch-bound at 0.59x of TrainStep; fusing restores the single-
        # dispatch economics of the fast path while keeping eager API
        # semantics (lazies resolve from the program's own outputs).
        self.fuse_eager_step = bool(fuse_eager_step)
        self.grad_accum_steps = max(1, int(grad_accum_steps))
        self.grad_clip = grad_clip
        self._training = True

        # -- configs (list surface, Stoke-DDP.py:252) ----------------------
        self._configs = list(configs or [])
        self.amp_config = self._find_config(AMPConfig) or AMPConfig()
        self.ddp_config = self._find_config(DDPConfig) or DDPConfig()
        self.oss_config = self._find_config(FairscaleOSSConfig) or FairscaleOSSConfig()
        self.tpu_config = self._find_config(TPUConfig) or TPUConfig()
        ds_config = self._find_config(DeepspeedConfig)
        # GRAFT_PLAN (env > TPUConfig.plan): adopt the auto-planner's
        # top-ranked configuration as the *weakest* voice — any explicit
        # TPUConfig field or set env twin wins, with the disagreement
        # logged so neither side is silently ignored (docs/PLANNER.md)
        self._plan = None
        self._plan_conflicts: list = []
        plan_spec = os.environ.get("GRAFT_PLAN") or self.tpu_config.plan
        if plan_spec:
            from ..analyze import plan as _plan_mod

            self._plan = _plan_mod.load_plan(plan_spec)
            self.tpu_config, self._plan_conflicts = (
                _plan_mod.apply_plan_to_config(self._plan, self.tpu_config)
            )
            if self._plan_conflicts:
                import warnings

                for c in self._plan_conflicts:
                    warnings.warn(
                        f"GRAFT_PLAN conflict on {c['knob']!r}: explicit "
                        f"{c['explicit']!r} wins over the plan's "
                        f"{c['plan']!r}",
                        stacklevel=2,
                    )
        # low-precision knobs (env > TPUConfig): quantized gradient wire
        # and the fp8 matmul mode for models that implement it
        self.wire = _wire_from_env(self.tpu_config)
        # two-level grad sync (env > TPUConfig): slice-aware mesh + a
        # tiered fused step; composes with the wire (only the DCN hop
        # is quantized on a hybrid mesh)
        self.hier = _hier_from_env(self.tpu_config)
        self._module, self.fp8 = _apply_fp8_env(
            self._module, self.tpu_config
        )
        # unified telemetry (env > TPUConfig): step spans + goodput ledger
        # + crash flight recorder; export_trace() writes the Chrome trace
        self.telemetry, self.trace_dir = _telemetry_from_env(self.tpu_config)
        if self.telemetry:
            _telemetry.enable()
        # numerics observability plane (env > TPUConfig): fused on-device
        # probes on the step + the host-side divergence watchdog; the
        # probe aux rides metrics["numerics"] out of fused_step, decoded
        # at the GRAFT_NUMERICS_EVERY cadence (a decode costs one
        # device→host fetch — default every step)
        numerics_on, numerics_action = _numerics_from_env(self.tpu_config)
        self.numerics_probe = None
        self.numerics_watchdog = None
        if numerics_on:
            from ..observe import numerics as _numerics

            fp8_max = None
            if self.fp8 is not None:
                from ..precision import FP8_DTYPES, _fp8_max

                fp8_max = _fp8_max(FP8_DTYPES[self.fp8])
            self.numerics_probe = _numerics.NumericsProbe(
                **({"fp8_max": fp8_max} if fp8_max else {})
            )
            self.numerics_watchdog = _numerics.NumericsWatchdog(
                action=numerics_action
            )
        self._numerics_every = max(
            1, int(os.environ.get("GRAFT_NUMERICS_EVERY", "1") or 1)
        )
        self._numerics_count = 0
        # op-cost attribution + anomaly-triggered capture (env >
        # TPUConfig): an armed OnDemandProfiler polls the anomaly
        # sources once per fused step (dict reads); when a capture fires
        # and the opcost plane is on, the post-fire hook parses it into
        # the per-axis bandwidth gauges the fleet endpoint publishes
        self.opcost = _opcost_from_env(self.tpu_config)
        capture_on, capture_dir = _capture_from_env(self.tpu_config)
        self.capture = None
        if capture_on:
            from ..observe.capture import OnDemandProfiler

            on_capture = None
            if self.opcost:
                from ..observe import opcost as _opcost_mod

                def on_capture(cap_dir, source):
                    _opcost_mod.ingest_trace(
                        cap_dir,
                        hlo_text=self._compiled_hlo_text(),
                        mesh_axes=dict(self.mesh.shape),
                    )

            self.capture = OnDemandProfiler(
                trace_dir=capture_dir, on_capture=on_capture
            ).arm()
        self._last_batch = None  # host refs for the post-capture HLO join

        # -- distribution policy ------------------------------------------
        distributed = (
            distributed.value
            if isinstance(distributed, DistributedOptions)
            else distributed
        )
        if ds_config is not None and ds_config.zero_optimization is not None:
            stage = ds_config.zero_optimization.stage
            fairscale_oss = fairscale_oss or stage >= 1
            fairscale_sddp = fairscale_sddp or stage >= 2
            fairscale_fsdp = fairscale_fsdp or stage >= 3
        if self._plan is not None:
            # plan policy rides the ctor engine flags; same precedence as
            # the config fields — explicit flags (ctor or ds stage) win
            want = self._plan.policy_flags()
            have = (fairscale_oss, fairscale_sddp, fairscale_fsdp)
            if not any(have):
                fairscale_oss = want.get("fairscale_oss", False)
                fairscale_sddp = want.get("fairscale_sddp", False)
                fairscale_fsdp = want.get("fairscale_fsdp", False)
            elif have != (
                want.get("fairscale_oss", False),
                want.get("fairscale_sddp", False),
                want.get("fairscale_fsdp", False),
            ):
                import warnings

                conflict = {
                    "knob": "policy",
                    "explicit": f"oss={have[0]},sddp={have[1]},fsdp={have[2]}",
                    "plan": self._plan.policy,
                }
                self._plan_conflicts.append(conflict)
                warnings.warn(
                    f"GRAFT_PLAN conflict on 'policy': explicit engine "
                    f"flags ({conflict['explicit']}) win over the plan's "
                    f"{self._plan.policy!r}",
                    stacklevel=2,
                )
        # DeepSpeed/Fairscale offload knobs -> optimizer state in host memory
        fsdp_config = self._find_config(FairscaleFSDPConfig)
        offload_opt = bool(fsdp_config is not None and fsdp_config.cpu_offload)
        if ds_config is not None and ds_config.offload_optimizer is not None:
            offload_opt = offload_opt or (
                ds_config.offload_optimizer.device == "cpu"
            )
        offload_par = bool(
            ds_config is not None
            and ds_config.offload_param is not None
            and ds_config.offload_param.device == "cpu"
        )
        if ds_config is not None:
            # surface-parity knobs with no TPU effect must say so out loud
            #
            import warnings

            if ds_config.aio is not None:
                warnings.warn(
                    "DeepspeedAIOConfig is inert on TPU (no NVMe tier); "
                    "use offload_optimizer/offload_param(device='cpu') for "
                    "the host-memory twin",
                    stacklevel=2,
                )
            for label, oc in (
                ("offload_optimizer", ds_config.offload_optimizer),
                ("offload_param", ds_config.offload_param),
            ):
                if oc is not None and oc.device not in ("cpu", "none"):
                    warnings.warn(
                        f"Deepspeed {label} device={oc.device!r} has no TPU "
                        "equivalent (only 'cpu' = pinned host memory maps); "
                        "ignoring",
                        stacklevel=2,
                    )
        self.policy = policy_from_flags(
            distributed=distributed,
            fairscale_oss=fairscale_oss,
            fairscale_sddp=fairscale_sddp,
            fairscale_fsdp=fairscale_fsdp,
            remat=_remat_from_env(self.tpu_config.remat),
            offload_opt_state=offload_opt,
            offload_params=offload_par,
        )
        zero = fairscale_oss or fairscale_sddp or fairscale_fsdp
        self.pp, self.pp_schedule, self.pp_micro = _pp_from_env(self.tpu_config)
        if mesh is not None:
            self.mesh = mesh
            self.pp = self.mesh.shape.get("pp", 1)
            if self.hier and slice_axis(self.mesh) is None:
                import warnings

                warnings.warn(
                    "hier requested but the provided mesh has no slice "
                    "axis (build it with make_hybrid_mesh) — falling "
                    "back to the flat gradient sync",
                    stacklevel=2,
                )
                self.hier = False
        elif (
            self.tpu_config.dp
            or self.tpu_config.fsdp > 1
            or self.tpu_config.tp > 1
            or self.pp > 1
        ):
            dp = self.tpu_config.dp
            if dp is None and self.pp > 1:
                # $GRAFT_PP alone: remaining devices go to the data axis
                used = (
                    self.tpu_config.fsdp * self.tpu_config.tp
                    * self.tpu_config.sp * self.pp
                )
                dp = max(1, jax.device_count() // used)
            spec = MeshSpec(
                dp=dp or 1,
                fsdp=self.tpu_config.fsdp,
                tp=self.tpu_config.tp,
                sp=self.tpu_config.sp,
                pp=self.pp,
            )
            if self.hier and (dp or 1) >= 2:
                # the dp axis is the DCN hop: slice-aware layout so the
                # fused step can tier its sync over slice_axis(mesh)
                self.mesh = make_hybrid_mesh(
                    dataclasses.replace(spec, dp=1), dcn_dp=dp
                )
            else:
                if self.hier:
                    import warnings

                    warnings.warn(
                        "hier requested but dp < 2 (no slice boundary "
                        "to tier over) — falling back to the flat "
                        "gradient sync",
                        stacklevel=2,
                    )
                    self.hier = False
                self.mesh = make_mesh(spec)
        else:
            if self.hier:
                import warnings

                warnings.warn(
                    "hier requested but no mesh axes were configured "
                    "(set TPUConfig.dp>=2 and fsdp>=2, or pass a "
                    "make_hybrid_mesh mesh) — falling back to the flat "
                    "gradient sync",
                    stacklevel=2,
                )
                self.hier = False
            self.mesh = make_mesh(MeshSpec.zero() if zero else MeshSpec.ddp())
        if self._plan is not None:
            # publish the applied plan into analyze.plan.runtime_stats and
            # re-check its own prunes against THIS host — the
            # plan-infeasible runtime rule fires from what lands here
            from ..analyze import plan as _plan_mod
            from ..observe.memory import device_hbm_budget

            reason = _plan_mod.record_applied(
                self._plan,
                device_count=jax.device_count(),
                budget_bytes=device_hbm_budget(),
                conflicts=self._plan_conflicts,
            )
            if reason:
                import warnings

                warnings.warn(
                    f"GRAFT_PLAN is infeasible on this topology: {reason}",
                    stacklevel=2,
                )

        # -- precision -----------------------------------------------------
        fp16 = fp16.value if isinstance(fp16, FP16Options) else fp16
        if fp16 is None and ds_config is not None:
            # DeepSpeed's own precision switches (json-config parity):
            # honored only when the ctor's fp16 arg doesn't already decide
            if ds_config.bf16_enabled:
                fp16 = "bf16"
            elif ds_config.fp16_enabled:
                fp16 = "amp"
        self.fp16 = fp16
        if fp16 in ("amp", "apex_O1", "apex_O2", "deepspeed"):
            # AMPConfig.enabled=False is torch GradScaler(enabled=False):
            # fp16 compute stays, the scaler becomes a pass-through
            self.precision = PrecisionPolicy.from_name("fp16")
            self.loss_scaler = (
                DynamicLossScaler(
                    init_scale=self.amp_config.init_scale,
                    growth_factor=self.amp_config.growth_factor,
                    backoff_factor=self.amp_config.backoff_factor,
                    growth_interval=self.amp_config.growth_interval,
                )
                if self.amp_config.enabled
                else None
            )
        elif fp16 == "bf16":
            self.precision = PrecisionPolicy.from_name("bf16")
            self.loss_scaler = None
        elif fp16 is None:
            self.precision = PrecisionPolicy()
            self.loss_scaler = None
        else:
            raise ValueError(f"unknown fp16 option {fp16!r}")

        # -- optimizer -----------------------------------------------------
        factory, kwargs = StokeOptimizer.resolve(optimizer)
        self._base_lr = float(kwargs.pop("lr", 1e-3))
        if grad_clip is not None:
            # both stoke clip twins: ClipGradNormConfig (global norm) and
            # ClipGradConfig (elementwise value)
            if isinstance(grad_clip, ClipGradNormConfig):
                kwargs.setdefault("clip_grad_norm", grad_clip.max_norm)
            elif isinstance(grad_clip, ClipGradConfig):
                kwargs.setdefault("clip_grad_value", grad_clip.clip)
            else:
                raise TypeError(
                    f"grad_clip must be ClipGradNormConfig or "
                    f"ClipGradConfig, got {type(grad_clip).__name__}"
                )
        elif ds_config is not None and ds_config.gradient_clipping:
            # DeepSpeed json-config clip (global norm), when no explicit
            # grad_clip argument takes precedence
            kwargs.setdefault("clip_grad_norm", ds_config.gradient_clipping)
        # lr=1.0: the real lr rides the OptimizerHandle and is applied as a
        # runtime scalar, so torch-style schedulers never retrace anything.
        # fused_optimizer=None (auto): replicated (DDP) and ZeRO-1/OSS
        # AdamW layouts take the flat fused update — the measured 2.6x
        # step-time winner on chip; under ZeRO-1
        # the flat moments shard over dp (DeepSpeed flat partitioning as
        # shardings). Numerics are pinned to the per-leaf chain by
        # tests/test_fused_optim.py. ZeRO-2/3 shard grads/params per
        # leaf and keep the optax chain. Pass fused_optimizer=False to
        # keep the chain layout — e.g. to .load() a checkpoint whose
        # opt_state was saved pre-fused (the pytrees are not
        # interchangeable).
        fused_eligible = (
            factory is optim_mod.adamw
            and optim_mod.fused_adamw_eligible(self.policy)
        )
        if fused_optimizer is True and not fused_eligible:
            raise ValueError(
                "fused_optimizer=True needs AdamW on a replicated (DDP) "
                "or ZeRO-1/OSS layout; ZeRO-2/3 shard grads/params per "
                "leaf and keep the per-leaf chain"
            )
        if fused_optimizer is True and self.wire is not None:
            raise ValueError(
                "fused_optimizer=True and a quantized gradient wire are "
                "mutually exclusive: the wire quantizes per leaf, the "
                "fused update ravels grads flat — drop one of the two"
            )
        if fused_optimizer is True and self.hier:
            raise ValueError(
                "fused_optimizer=True and hier are mutually exclusive: "
                "HierGradStep drives an optax-style per-leaf update; "
                "the fused update ravels grads flat — drop one of the two"
            )
        # auto mode defers to a requested wire or the two-level sync:
        # CompressedGradStep/HierGradStep are per-leaf paths, so the
        # flat fused update cannot carry them
        if (
            fused_eligible
            and fused_optimizer is not False
            and self.wire is None
            and not self.hier
        ):
            self._tx = optim_mod.FusedAdamW(lr=1.0, **kwargs)
        else:
            self._tx = factory(lr=1.0, **kwargs)
        self._opt_handle = optim_mod.OptimizerHandle(self._base_lr)

        # -- lazy-built state ---------------------------------------------
        self._state = None
        self._shardings = None
        self._fused = None
        self._pending_pretrained = pretrained
        self._rng_seed = rng_seed
        self._ema_dev = None  # EMA loss as a device scalar (no host sync)
        self._ema_async = _AsyncScalarFetcher()  # non-blocking display reads
        self._last_inputs = None
        self._last_targets = None
        self._last_loss_dev = None
        self._lazy_output = None
        self._lazy_loss = None
        self._pending_lazies = []  # weakref.ref of unresolved handles
        self._backward_count = 0
        self._grad_acc = None
        # deferred-backward records for the fused eager path:
        # (inputs, targets, lazy_loss | None, lazy_output | None) per micro
        self._pending_micro = []
        self._accepts_train = self._model_accepts("train")
        # always on, plain adds the facade owns: dispatches of each compiled
        # program (by the attribute that holds it; ``_run`` keeps a
        # program's signature at its first), and the optimizer steps taken
        # — a Python integer kept on the host, the ``step`` argument of
        # every facade span (never ``state.step``: that is a device value)
        self.programs = {}
        self._opt_steps = 0
        _telemetry.add_span(
            "facade.construct", "startup", t_construct,
            time.perf_counter() - t_construct, {"policy": self.policy.name},
        )

        if sample_input is not None:
            self.init(sample_input)

    # -- init / state ------------------------------------------------------

    def _find_config(self, cls):
        for c in self._configs:
            if isinstance(c, cls):
                return c
        return None

    def _model_accepts(self, kwarg: str) -> bool:
        try:
            sig = inspect.signature(type(self._module).__call__)
            return kwarg in sig.parameters
        except (TypeError, ValueError):
            return False

    def init(self, sample_input) -> "Stoke":
        """Initialize (sharded) params from a sample input. Called
        automatically by the first ``.model(inputs)``."""
        if self._state is not None:
            return self
        with _telemetry.span("facade.init_state", "startup"):
            self._init_state(sample_input)
        if self.verbose:
            n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self._state.params))
            self.print_on_devices(
                f"Stoke[tpu]: {type(self._module).__name__} {n/1e6:.2f}M params, "
                f"policy={self.policy.name}, mesh={dict(self.mesh.shape)}, "
                f"precision={self.fp16 or 'fp32'}, accum={self.grad_accum_steps}"
            )
        return self

    def _init_state(self, sample_input) -> None:
        sample = jax.tree.map(
            lambda x: jnp.asarray(x)[:1] if hasattr(x, "shape") else x, sample_input
        )
        init_kwargs = {"train": False} if self._accepts_train else {}
        if isinstance(self._tx, optim_mod.FusedAdamW):
            # the OSS broadcast_fp16 wire needs the mesh (ctor doesn't
            # have it): resolve onto the tx before anything traces
            self._tx.update_wire_dtype = self._update_wire_dtype()
        self._state, self._shardings = create_train_state(
            model=self._module,
            sample_input=sample,
            tx=self._tx,
            mesh=self.mesh,
            policy=self.policy,
            rng=jax.random.PRNGKey(self._rng_seed),
            scaler_state=self.loss_scaler.init() if self.loss_scaler else None,
            init_kwargs=init_kwargs,
        )
        self._build_jits()
        if self._pending_pretrained is not None:
            self.load_model_state(self._pending_pretrained)
            self._pending_pretrained = None

    @property
    def state(self):
        """The facade's TrainState (shared by eager/fused/pipelined paths).

        Assignable so an external engine (``pipeline_step``) can hand an
        updated state back: ``stoke.state, m = pstep(stoke.state, batch)``.
        """
        return self._state

    @state.setter
    def state(self, new_state):
        self._state = new_state

    def _update_wire_dtype(self):
        """Fairscale OSS ``broadcast_fp16`` twin (`Stoke-DDP.py:197-199`):
        under a ZeRO policy the sharded-state update fans out through an
        implicit all-gather; the flag narrows that wire to bf16 (the
        TPU-native 2-byte dtype, same deliberate lossiness as the
        reference's fp16 param broadcast). No-op for plain DDP or a
        single-device mesh — there is no fan-out to compress."""
        if (
            self.oss_config.broadcast_fp16
            and self.policy.shard_opt_state
            and shard_axis(self.mesh) is not None
        ):
            return jnp.bfloat16
        return None

    def _apply_model(self, params, model_state, x, train: bool, rng):
        variables = {"params": params, **model_state}
        kwargs = {}
        if self._accepts_train:
            kwargs["train"] = train
        mutable = [k for k in model_state] if (train and model_state) else False
        rngs = {"dropout": rng} if rng is not None else None
        # the facade owns the mesh and places every batch on its data axes
        # (_shard_batch), so it says so while the model is traced
        with batch_layout(self.mesh):
            if mutable:
                out, new_state = self._module.apply(
                    variables, x, rngs=rngs, mutable=mutable, **kwargs
                )
                return out, dict(new_state)
            out = self._module.apply(variables, x, rngs=rngs, **kwargs)
            return out, model_state

    def _build_jits(self):
        precision = self.precision
        loss_callable = self._loss_callable
        param_shardings = self._shardings.params
        opt_shardings = self._shardings.opt_state

        def fwd(params, model_state, x, rng, train: bool):
            params = stream_to_device(params, param_shardings)
            pc = precision.cast_to_compute(params)
            out, new_state = self._apply_model(pc, model_state, x, train, rng)
            return precision.cast_to_output(out), new_state

        self._jit_fwd = jax.jit(fwd, static_argnames=("train",))

        def loss_only(o, t):
            with jax.named_scope("loss"):
                return loss_callable(o, t)

        self._jit_loss = jax.jit(loss_only)
        self._jit_ema = _ema_update

        def fwd_loss(p, model_state, x, y, rng):
            out, new_state = self._apply_model(
                precision.cast_to_compute(p), model_state, x, True, rng
            )
            with jax.named_scope("loss"):
                loss = loss_callable(out, y)
            return loss, precision.cast_to_output(out), new_state

        # the eager .backward() path honors Policy.remat too (the fused
        # TrainStep wires it separately), resolved through the same named
        # registry: "full" recomputes the forward (keeping an attention
        # kernel's residuals), "dots"/"names"/"offload" save the policy's
        # subset (parallel/remat.py)
        fwd_loss = apply_remat(fwd_loss, self.policy.remat)

        def loss_grad(params, model_state, x, y, rng, scaler_state):
            # stream BEFORE value_and_grad: differentiating through the
            # host->device copy would transpose the grads back to host
            params = stream_to_device(params, param_shardings)

            def lfn(p):
                loss, out, new_state = fwd_loss(p, model_state, x, y, rng)
                scaled = (
                    loss * scaler_state.scale.astype(loss.dtype)
                    if scaler_state is not None
                    else loss
                )
                return scaled, (loss, out, new_state)

            (_, (loss, out, new_state)), grads = jax.value_and_grad(
                lfn, has_aux=True
            )(params)
            return loss, out, new_state, grads

        self._jit_loss_grad = jax.jit(loss_grad)

        accum = self.grad_accum_steps

        def acc(buf, grads):
            with jax.named_scope("grad_accum"):
                g32 = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / accum, grads
                )
                return g32 if buf is None else jax.tree.map(jnp.add, buf, g32)

        self._jit_acc_first = jax.jit(lambda g: acc(None, g))
        self._jit_acc = jax.jit(acc)

        tx = self._tx
        policy = self.policy
        mesh = self.mesh
        scaler = self.loss_scaler

        wire_dtype = self._update_wire_dtype()

        fused_tx = tx if isinstance(tx, optim_mod.FusedAdamW) else None

        def apply_updates(params, opt_state, scaler_state, grads, lr):
            # the update program is the "optimizer" of a profile, as in
            # TrainStep (metadata only); optim.py nests "clip" and "adamw"
            with jax.named_scope("optimizer"):
                return update(params, opt_state, scaler_state, grads, lr)

        def update(params, opt_state, scaler_state, grads, lr):
            params = stream_to_device(params, param_shardings)
            opt_state = stream_to_device(opt_state, opt_shardings)
            if fused_tx is not None:
                # flat fused path: one ravel, full-width unscale/gate/
                # update — shared with TrainStep via FusedAdamW.apply_tree
                new_params, new_opt, new_scaler, _ = fused_tx.apply_tree(
                    grads,
                    opt_state,
                    params,
                    lr,
                    scaler=scaler,
                    scaler_state=scaler_state,
                )
                return new_params, new_opt, new_scaler
            finite = jnp.bool_(True)
            new_scaler = scaler_state
            if scaler is not None and scaler_state is not None:
                grads = scaler.unscale_grads(grads, scaler_state)
                finite = DynamicLossScaler.grads_finite(grads)
                new_scaler = scaler.update(scaler_state, finite)
            gspecs = policy.grads_specs(params, mesh)
            if gspecs is not None:
                with jax.named_scope("grad_sync"):
                    grads = constrain(grads, gspecs, mesh)
            updates, new_opt = tx.update(grads, opt_state, params)
            updates = jax.tree.map(lambda u: u * lr, updates)
            if wire_dtype is not None:
                # OSS broadcast_fp16 twin: narrow the update fan-out wire
                updates = jax.tree.map(
                    lambda u: u.astype(wire_dtype), updates
                )
            new_params = jax.tree.map(lambda p, u: p + u, params, updates)
            # params-EMA correction: lr rides THIS post-chain multiply, so
            # the chain element's own EMA tracked lr=1.0-magnitude steps
            new_opt = optim_mod.refresh_params_ema(
                opt_state, new_opt, new_params
            )
            if scaler is not None:
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_params, params
                )
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_opt, opt_state
                )
            return new_params, new_opt, new_scaler

        self._jit_apply = jax.jit(
            apply_updates,
            in_shardings=(
                self._shardings.params,
                self._shardings.opt_state,
                self._shardings.scaler,
                None,
                None,
            ),
            out_shardings=(
                self._shardings.params,
                self._shardings.opt_state,
                self._shardings.scaler,
            ),
            donate_argnums=(0, 1),
        )

        # fused eager path: the whole accum window (every micro's fwd+bwd,
        # the mean, and the update) as ONE program — the same two closures
        # the split path jits (loss_grad / apply_updates), traced together
        # so numerics are identical and the hot loop costs one dispatch.
        # model_state threads micro-to-micro (sequential BN semantics,
        # matching torch and the split eager path — TrainStep's scan
        # broadcasts the pre-step state instead).
        def eager_step(params, opt_state, scaler_state, model_state,
                       micros, rng_base, step_no, lr, ema, has_ema):
            # fold in-program: an eager host-side fold_in costs ~1 ms of
            # dispatch per step on the hot loop
            rng = jax.random.fold_in(rng_base, step_no)
            gacc = None
            losses, outs = [], []
            ms = model_state
            l32 = None
            for x, y in micros:
                loss, out, ms, grads = loss_grad(
                    params, ms, x, y, rng, scaler_state
                )
                gacc = acc(gacc, grads)  # the split path's own fold
                # loss monitor folded in-program (the split path
                # dispatches _ema_update per backward): same 0.98-decay
                # single source of truth; has_ema distinguishes "no EMA
                # yet" from a genuinely-NaN EMA, which must propagate
                l32 = jnp.mean(jnp.asarray(loss, jnp.float32))
                ema = jnp.where(has_ema, _ema_update(ema, l32), l32)
                has_ema = jnp.bool_(True)
                losses.append(loss)
                outs.append(out)
            new_params, new_opt, new_scaler = apply_updates(
                params, opt_state, scaler_state, gacc, lr
            )
            return (
                losses, outs, ms, new_params, new_opt, new_scaler, ema, l32
            )

        self._jit_eager_step = jax.jit(
            eager_step,
            in_shardings=(
                self._shardings.params,
                self._shardings.opt_state,
                self._shardings.scaler,
                self._shardings.model_state,
                None,
                None,
                None,
                None,
                None,
                None,
            ),
            out_shardings=(
                None,
                None,
                self._shardings.model_state,
                self._shardings.params,
                self._shardings.opt_state,
                self._shardings.scaler,
                None,
                None,
            ),
            donate_argnums=(0, 1),
        )
        # the programs themselves, by the attribute that holds each: a
        # subclass may wrap the attributes (a counter), the signatures
        # kept for a trace reader (``_run``) are the programs'
        self._jits = {
            name: getattr(self, name) for name in (
                "_jit_fwd", "_jit_loss", "_jit_ema", "_jit_loss_grad",
                "_jit_acc_first", "_jit_acc", "_jit_apply",
                "_jit_eager_step",
            )
        }

    def _span(self, name: str):
        """A span of the facade (``observe.trace``): a trace annotation
        always, a ring record with telemetry on; its ``step`` argument is
        the number of optimizer steps taken so far. The first optimizer
        step traces and compiles the programs, so its spans are billed to
        the ledger's ``compile`` bucket, as a step's first dispatch is."""
        n = self._opt_steps
        return _telemetry.span(name, "step" if n else "compile", step=n)

    def _run(self, name: str, *args, **kwargs):
        """Call the compiled program held by attribute ``name``, counted;
        at its first call its abstract signature is kept
        (``observe.profiling.remember_program``)."""
        n = self.programs.get(name, 0)
        self.programs[name] = n + 1
        if n:
            return getattr(self, name)(*args, **kwargs)
        remember_program(self._jits[name], args, kwargs)
        with _telemetry.span(
            "facade.program.compile+dispatch", "compile", program=name,
            step=self._opt_steps,
        ):
            return getattr(self, name)(*args, **kwargs)

    # -- eager-parity runtime surface --------------------------------------

    def model(self, inputs):
        """Forward pass (`Stoke-DDP.py:73,116`). Lazily initializes params
        from the first batch's shapes.

        In training mode the forward is *deferred*: the returned handle
        materializes on any direct use, but when it only flows into
        ``.loss → .backward`` the whole iteration runs as one compiled
        fwd+bwd program (no double forward)."""
        if self._state is None:
            self.init(inputs)
        with self._span("facade.model"):
            inputs = self._shard_batch(inputs)
            self._last_inputs = inputs
            if self._training:
                lazy = _LazyOutput(
                    self, inputs, self._state.params,
                    self._state.model_state,
                    (self._state.rng, self._state.step),
                )
                self._lazy_output = lazy
                self._pending_lazies.append(weakref.ref(lazy))
                return lazy
            return self._run_forward(inputs, train=False)

    def _run_forward(self, inputs, train: bool):
        with self._span("facade.forward"):
            rng = jax.random.fold_in(self._state.rng, self._state.step)
            out, _ = self._run(
                "_jit_fwd", self._state.params, self._state.model_state,
                inputs, rng, train=train,
            )
        return out

    def _compute_loss(self, outputs, targets):
        with self._span("facade.loss.compute"):
            loss = self._run("_jit_loss", outputs, targets)
        self._note_loss(loss)
        return loss

    def _materialize_loss(self, output, targets):
        """Fallback for direct use of a deferred loss before backward()."""
        return self._compute_loss(output.materialize(), targets)

    def _materialize_lazy_loss(self, lazy):
        """Early use of a deferred loss.

        If the handle belongs to a pending (deferred-backward) micro, the
        grads for its window are needed anyway — flush the window through
        the split path, which computes and records this loss as a
        byproduct (no throwaway forward; `step()` then takes the legacy
        apply). Otherwise (pre-backward use) run the standalone
        forward+loss programs."""
        if any(rec[2] is lazy for rec in self._pending_micro):
            self._flush_pending_micros()
            return lazy._value
        return self._materialize_loss(lazy._output, lazy._targets)

    def loss(self, outputs, targets):
        """Loss computation (`Stoke-DDP.py:74,118`). Deferred when the
        outputs are themselves deferred — ``.backward()`` then resolves it
        from the fused grad program at zero extra cost."""
        with self._span("facade.loss"):
            targets = self._shard_batch(targets)
            self._last_targets = targets
            if isinstance(outputs, _LazyOutput) and outputs._value is None:
                lazy = _LazyLoss(self, outputs, targets)
                self._lazy_loss = lazy
                return lazy
            if isinstance(outputs, _LazyOutput):
                outputs = outputs.materialize()
            return self._compute_loss(outputs, targets)

    def backward(self, loss=None):
        """Backward (`Stoke-DDP.py:79`).

        With ``fuse_eager_step`` (default) this *defers*: the micro's
        (inputs, targets) are recorded and the whole accum window runs as
        one compiled fwd+bwd+update program inside ``.step()`` — the
        reference loop then costs a single dispatch per window, same as
        the fused fast path. The deferred loss/output handles resolve
        from that program's outputs; used before ``.step()`` they
        self-materialize, so deferral never changes observable values.

        The split path (``fuse_eager_step=False`` or odd call patterns)
        recomputes fwd+loss under grad on the recorded pair right here
        and accumulates ``grads/accum``. The ``loss`` argument is
        accepted for API parity; gradients come from the compiled
        programs either way."""
        if self._last_inputs is None or self._last_targets is None:
            raise RuntimeError(
                "backward() needs a preceding model(inputs) and loss(outputs, targets)"
            )
        with self._span("facade.backward"):
            lazy_loss = (
                loss if isinstance(loss, _LazyLoss) else self._lazy_loss
            )
            lazy_out = self._lazy_output
            self._lazy_loss = None
            self._lazy_output = None
            if self.fuse_eager_step:
                self._pending_micro.append((
                    self._last_inputs, self._last_targets, lazy_loss,
                    lazy_out,
                ))
                self._backward_count += 1
                # split-path parity: a caller that brought its own concrete
                # loss gets it back, not None
                return lazy_loss if lazy_loss is not None else loss
            val = self._backward_now(
                self._last_inputs, self._last_targets, lazy_loss, lazy_out
            )
            self._backward_count += 1
            return val

    def _backward_now(self, x, y, lazy_loss=None, lazy_out=None):
        """Split-path backward on one micro (does NOT bump the counter)."""
        # the grad program and the accumulate: dispatch, blocked or not
        with self._span("facade.backward.grad"):
            rng = jax.random.fold_in(self._state.rng, self._state.step)
            loss_val, out, new_model_state, grads = self._run(
                "_jit_loss_grad",
                self._state.params,
                self._state.model_state,
                x,
                y,
                rng,
                self._state.scaler,
            )
            self._state = self._state.replace(model_state=new_model_state)
            if self.grad_accum_steps == 1 and self._grad_acc is None:
                self._grad_acc = grads  # scale 1/1 and f32 cast are no-ops
            elif self._grad_acc is None:
                self._grad_acc = self._run("_jit_acc_first", grads)
            else:
                self._grad_acc = self._run("_jit_acc", self._grad_acc, grads)
        self._note_loss(loss_val)
        # resolve the deferred loss/output handles from the fused program's
        # own results, so `detach_and_sync_loss(loss)` and any later use of
        # the `.model()` output cost nothing extra; `is None` guards keep
        # any already-observed value stable (differently-fused programs
        # can round differently)
        if lazy_loss is not None and lazy_loss._value is None:
            lazy_loss._value = loss_val
        if lazy_out is not None and lazy_out._value is None:
            lazy_out._value = out
        self._prune_pending_lazies()
        return loss_val

    def _prune_pending_lazies(self):
        self._pending_lazies = [
            r for r in self._pending_lazies
            if r() is not None and r()._value is None
        ]

    def _flush_pending_micros(self):
        """Run deferred micros through the split path (odd call patterns:
        mixed accumulation state, early prints — correctness over speed)."""
        window, self._pending_micro = self._pending_micro, []
        for x, y, lazy_loss, lazy_out in window:
            self._backward_now(x, y, lazy_loss, lazy_out)

    def step(self):
        """Optimizer step (`Stoke-DDP.py:82`): fires every
        ``grad_accum_steps``-th call (Stoke accumulation semantics)."""
        with self._span("facade.step"):
            if self._backward_count == 0:
                return
            if self._backward_count % self.grad_accum_steps != 0:
                return
            if (
                self._pending_micro
                and self._grad_acc is None
                and len(self._pending_micro) == self.grad_accum_steps
            ):
                return self._step_fused()
            self._step_split()

    def _step_split(self):
        """The update as its own program, after the grad programs. Each
        part that can leave the device waiting is a span of its own, and
        every dispatch is inside exactly one innermost span."""
        with self._span("facade.step.flush_micros"):
            self._flush_pending_micros()
        # any still-deferred handles hold references to the CURRENT params,
        # whose buffers _jit_apply is about to donate — materialize them now
        # so late use reproduces the pre-step forward instead of crashing
        with self._span("facade.step.materialize_lazies"):
            for ref in self._pending_lazies:
                lazy = ref()
                if lazy is not None:
                    lazy.materialize()
            self._pending_lazies = []
        with self._span("facade.step.lr"):  # a host float onto the device
            lr = jnp.float32(self._opt_handle.lr)
        with self._span("facade.step.apply"):
            new_params, new_opt, new_scaler = self._run(
                "_jit_apply",
                self._state.params,
                self._state.opt_state,
                self._state.scaler,
                self._grad_acc,
                lr,
            )
            self._state = self._state.replace(
                params=new_params,
                opt_state=new_opt,
                scaler=new_scaler,
                step=self._state.step + 1,
            )
        self._grad_acc = None
        self._backward_count = 0
        self._opt_steps += 1

    def _step_fused(self):
        """The deferred accum window as one compiled program."""
        window, self._pending_micro = self._pending_micro, []
        # handles from OUTSIDE this window still reference the pre-step
        # params whose buffers the program donates — materialize them now;
        # the window's own handles resolve from the program outputs below
        window_ids = {
            id(h) for rec in window for h in rec[2:] if h is not None
        }
        for ref in self._pending_lazies:
            lazy = ref()
            if (
                lazy is not None
                and lazy._value is None
                and id(lazy) not in window_ids
            ):
                lazy.materialize()
        self._pending_lazies = []
        micros = tuple((x, y) for x, y, _, _ in window)
        has_ema = self._ema_dev is not None
        with self._span("facade.step.fused"):  # the window's one dispatch
            ema_in = self._ema_dev if has_ema else jnp.float32(0.0)
            (
                losses, outs, new_ms, new_params, new_opt, new_scaler,
                new_ema, last_l32,
            ) = self._run(
                "_jit_eager_step",
                self._state.params,
                self._state.opt_state,
                self._state.scaler,
                self._state.model_state,
                micros,
                self._state.rng,
                self._state.step,
                jnp.float32(self._opt_handle.lr),
                ema_in,
                jnp.bool_(has_ema),
            )
            next_step = self._state.step + 1
        # EMA/last-loss bookkeeping came back from the program itself —
        # no per-micro _note_loss dispatches on the fused path (last_l32
        # is the final micro's scalar mean, matching _note_loss's
        # non-scalar-loss reduction)
        self._ema_dev = new_ema
        self._last_loss_dev = last_l32
        if self.verbose:
            # same freshness contract as _note_loss: the display fetch
            # starts when the EMA updates, not when it's printed
            self._ema_async.submit(new_ema)
        for (_, _, lazy_loss, lazy_out), loss_val, out in zip(
            window, losses, outs
        ):
            # `is None` guards: a handle the user force-materialized
            # mid-window keeps its observed value (the fused program's
            # differently-fused result could round differently)
            if lazy_loss is not None and lazy_loss._value is None:
                lazy_loss._value = loss_val
            if lazy_out is not None and lazy_out._value is None:
                lazy_out._value = out
        self._state = self._state.replace(
            params=new_params,
            opt_state=new_opt,
            scaler=new_scaler,
            model_state=new_ms,
            step=next_step,
        )
        self._grad_acc = None
        self._backward_count = 0
        self._opt_steps += 1

    def zero_grad(self):
        """Drop accumulated grads (raw-loop parity, `Fairscale-DDP.py:97`).

        Deferred micros are dropped too; their handles self-materialize
        (captured params) if still referenced."""
        self._grad_acc = None
        self._backward_count = 0
        self._pending_micro = []

    def detach_and_sync_loss(self, loss):
        """Cross-device mean of a loss for reporting (`Stoke-DDP.py:86`).

        Under SPMD the compiled loss is already the global mean. Returns a
        0-d device array — the faithful twin of the reference's detached
        *tensor* — so `sum_loss += ...` accumulation stays on device and
        the hot loop never blocks the host; ``float()`` it at log points.
        """
        with self._span("facade.detach_and_sync_loss"):
            if isinstance(loss, (_LazyLoss, _LazyOutput)):
                loss = loss.materialize()
            return sync_scalar_device(loss)

    # -- fused fast path ---------------------------------------------------

    def _maybe_static_analyze(self, step, batch):
        """``GRAFT_ANALYZE=warn|error``: run graftcheck once, at first
        compile of the fused step (the AOT artifacts are free then — the
        jit cache already holds the lowering). ``warn`` prints the
        report; ``error`` additionally raises on error-severity findings
        so a misconfigured pod run dies before burning its first step.
        Off by default; same env-knob family as GRAFT_REMAT/GRAFT_PP.
        """
        from ..analyze import analyze_mode, analyze_step

        mode = analyze_mode()
        if mode == "off":
            return
        report = analyze_step(
            step, self._state, batch, lr_factor=self._opt_handle.lr
        )
        print(report.render())
        if mode == "error" and not report.ok:
            raise RuntimeError(
                f"GRAFT_ANALYZE=error: graftcheck found "
                f"{len(report.errors)} error-severity finding(s) in the "
                "fused step; see report above (suppress individual rules "
                "via GRAFT_ANALYZE_IGNORE)"
            )

    def _build_fused(self):
        """Construct the fused TrainStep once, without executing a step.
        Shared by ``fused_step`` and ``static_analyze`` so graftcheck can
        inspect the exact program the fast path would run."""
        if self._fused is not None:
            return self._fused
        module_apply = self._apply_model
        loss_callable = self._loss_callable

        def loss_fn(params, batch, rng, model_state):
            x, y = batch
            out, new_state = module_apply(params, model_state, x, True, rng)
            loss = loss_callable(out, y)
            aux = {"model_state": new_state} if new_state else {}
            return loss, aux

        if self.wire is not None:
            # quantized gradient wire: CompressedGradStep composes with
            # DDP/ZeRO-1/ZeRO-2 on data-only meshes and owns its whole
            # reduce path, so features TrainStep layers on top of psum
            # (accum windows, the fp16 loss scaler, precision casts) fall
            # back to the f32 wire rather than silently dropping
            reason = None
            if self.grad_accum_steps > 1:
                reason = "grad_accum_steps > 1"
            elif self.loss_scaler is not None:
                reason = "the dynamic fp16 loss scaler"
            elif self.fp16 is not None:
                reason = f"the {self.fp16!r} precision policy"
            elif self.pp > 1:
                reason = "pipeline parallelism"
            if reason is None:
                try:
                    self._fused = CompressedGradStep(
                        loss_fn,
                        self._tx,
                        self.mesh,
                        self.policy,
                        donate=self.tpu_config.donate_state,
                        wire=self.wire,
                        numerics=self.numerics_probe,
                    )
                    return self._fused
                except ValueError as e:  # ZeRO-3 / non-data mesh axes
                    reason = str(e)
            import warnings

            warnings.warn(
                f"wire={self.wire.name!r} requested but the fused step "
                f"does not compose with {reason}; falling back to "
                "TrainStep's f32 gradient wire",
                stacklevel=2,
            )

        if self.hier and self.wire is None:
            # two-level f32 sync: HierGradStep owns the whole reduce
            # path (reduce-scatter on ICI -> all-reduce across slices on
            # DCN -> all-gather), so the same TrainStep extras the wire
            # path refuses (accum windows, loss scaler, precision casts,
            # pipelining) fall back to the flat sync out loud. The
            # wire+hier composition took the CompressedGradStep branch
            # above — on a hybrid mesh it is already the two-level
            # quantized form.
            reason = None
            if self.grad_accum_steps > 1:
                reason = "grad_accum_steps > 1"
            elif self.loss_scaler is not None:
                reason = "the dynamic fp16 loss scaler"
            elif self.fp16 is not None:
                reason = f"the {self.fp16!r} precision policy"
            elif self.pp > 1:
                reason = "pipeline parallelism"
            if reason is None:
                try:
                    self._fused = HierGradStep(
                        loss_fn,
                        self._tx,
                        self.mesh,
                        self.policy,
                        donate=self.tpu_config.donate_state,
                        numerics=self.numerics_probe,
                    )
                    return self._fused
                except ValueError as e:  # ZeRO-3 / non-data mesh axes
                    reason = str(e)
            import warnings

            warnings.warn(
                f"hier requested but the fused step does not compose "
                f"with {reason}; falling back to TrainStep's flat "
                "gradient sync",
                stacklevel=2,
            )

        self._fused = TrainStep(
            loss_fn,
            self._tx,
            self.mesh,
            self.policy,
            grad_accum_steps=self.grad_accum_steps,
            precision=self.precision,
            loss_scaler=self.loss_scaler,
            state_shardings=self._shardings,
            donate=self.tpu_config.donate_state,
            # a FusedAdamW carries its own flat wire dtype (set at
            # init()); the per-leaf knob is the tree path's
            update_wire_dtype=(
                None
                if isinstance(self._tx, optim_mod.FusedAdamW)
                else self._update_wire_dtype()
            ),
            numerics=self.numerics_probe,
        )
        return self._fused

    def static_analyze(self, inputs, targets):
        """Run graftcheck against the fused step and return the Report,
        without taking a device step. For drivers on the eager
        loss/backward/step surface this is the way to analyze the program
        they *would* run fused — the constructed TrainStep is cached, so a
        later ``fused_step`` pays no second trace. The caller decides what
        to do with the report (print / abort); no env knob is consulted.
        """
        from ..analyze import analyze_step

        if self._state is None:
            self.init(inputs)
        step = self._build_fused()
        return analyze_step(
            step,
            self._state,
            (self._shard_batch(inputs), self._shard_batch(targets)),
            lr_factor=self._opt_handle.lr,
        )

    def fused_step(self, inputs, targets):
        """One compiled program for fwd+bwd+accum+clip+update — the TPU fast
        path. Returns the metrics dict. State is shared with the eager
        surface, so the two paths can be mixed."""
        if self._state is None:
            self.init(inputs)
        if self._fused is None:
            self._maybe_static_analyze(
                self._build_fused(),
                (self._shard_batch(inputs), self._shard_batch(targets)),
            )
        with self._span("facade.fused_step"):
            batch = (self._shard_batch(inputs), self._shard_batch(targets))
            # the step's own dispatch span is the innermost one here
            self._state, metrics = self._fused(
                self._state, batch, lr_factor=self._opt_handle.lr
            )
            self._opt_steps += 1
            self._note_loss(metrics["loss"])
            self._observe_numerics(metrics)
            if self.capture is not None:
                self._last_batch = (inputs, targets)
                self.capture.note_step()
            return metrics

    def _compiled_hlo_text(self) -> str | None:
        """Compiled HLO of the fused step (a cache hit after the first
        step) — the wire-inventory join source for the opcost ingest
        hook. None before the first fused step or when lowering fails;
        the hook then publishes op tables without the bandwidth join."""
        if (
            self._fused is None
            or self._state is None
            or self._last_batch is None
        ):
            return None
        try:
            inputs, targets = self._last_batch
            return self._fused.compiled_text(
                self._state,
                (self._shard_batch(inputs), self._shard_batch(targets)),
                lr_factor=self._opt_handle.lr,
            )
        except Exception:  # noqa: BLE001 — accounting must not kill a step
            return None

    def _observe_numerics(self, metrics) -> None:
        """Decode the step's numerics aux at the configured cadence and
        feed the watchdog. A ``halt`` trip raises NumericsDivergence out
        of the step; ``rollback``/``degrade`` trips record the verdict
        (``Stoke.numerics_watchdog.tripped``) for the training loop /
        launcher to act on — the facade has no checkpoint manager of its
        own to roll back through."""
        if self.numerics_probe is None or "numerics" not in metrics:
            return
        self._numerics_count += 1
        if self._numerics_count % self._numerics_every:
            return
        summary = self.numerics_probe.observe(
            metrics["numerics"],
            step=self._numerics_count,
            loss=metrics.get("loss"),
            watchdog=self.numerics_watchdog,
        )
        verdict = summary.get("verdict")
        if verdict is not None and verdict.get("action") == "halt":
            self.numerics_watchdog.apply_action(verdict)

    def pipeline_step(
        self,
        block_fn,
        head_fn,
        *,
        embed_fn=None,
        stages_key: str = "h",
        n_micro: int | None = None,
        schedule: str | None = None,
        v: int = 1,
    ):
        """Build a :class:`~..parallel.pipeline.PipelineStep` on the
        facade's mesh/optimizer/policy (the ``$GRAFT_PP`` family sizes the
        mesh and supplies schedule/n_micro defaults).

        The pipelined loss is DECOMPOSED — ``embed_fn``/``block_fn``/
        ``head_fn`` as documented on PipelineStep — because the engine
        places it around the pipe; the facade's monolithic ``loss``
        callable cannot be split automatically. Re-homes the facade
        state's stacked ``stages_key`` leaves onto the pp axis (state is
        shared with the eager surface). Call after ``init(...)``.
        """
        if self._state is None:
            raise RuntimeError(
                "pipeline_step needs initialized state — call "
                "stoke.init(sample_input) (or run a forward) first"
            )
        from ..parallel.pipeline import PipelineStep, pipeline_state_shardings

        self._shardings = pipeline_state_shardings(
            self._shardings, self._state, self.mesh, stages_key
        )
        self._state = jax.device_put(self._state, self._shardings)
        n_micro = n_micro or self.pp_micro or max(
            self.grad_accum_steps, 2 * max(self.pp, 1)
        )
        return PipelineStep(
            block_fn,
            self._tx,
            self.mesh,
            self.policy,
            n_micro=n_micro,
            schedule=schedule or self.pp_schedule,
            v=v,
            stages_key=stages_key,
            embed_fn=embed_fn,
            head_fn=head_fn,
            state_shardings=self._shardings,
            donate=self.tpu_config.donate_state,
        )

    # -- data --------------------------------------------------------------

    def DataLoader(
        self,
        dataset,
        batch_size: int | None = None,
        sampler=None,
        num_workers: int = 0,
        drop_last: bool = True,
        device_prefetch: int | None = None,
        **kwargs,
    ):
        """Loader bound to the facade's batch size and mesh
        (`Stoke-DDP.py:286-298`). Per-process batch =
        ``batch_size_per_device × local device count``; ``drop_last``
        defaults True (static shapes — XLA recompiles on ragged tails).

        ``device_prefetch`` (default from ``$GRAFT_DEVICE_PREFETCH``, 2)
        stages that many sharded batches onto the mesh ahead of the hot
        loop so H2D transfers overlap the running step; 0 reverts to
        synchronous per-batch placement.
        """
        if batch_size is None:
            batch_size = self.batch_size_per_device * jax.local_device_count()
        if device_prefetch is None:
            device_prefetch = int(
                os.environ.get("GRAFT_DEVICE_PREFETCH", "2") or 0
            )
        # multiprocessing_context passes through: a spawn/fork context is a
        # real process pool in the loader (GIL escape hatch), not a no-op
        return _DataLoader(
            dataset,
            batch_size=batch_size,
            sampler=sampler,
            num_workers=num_workers,
            drop_last=drop_last,
            mesh=self.mesh,
            spec=batch_spec(self.mesh),
            device_prefetch=device_prefetch,
            **kwargs,
        )

    def _shard_batch(self, x):
        if hasattr(x, "sharding") and not isinstance(x, np.ndarray):
            return x  # already placed (came from our DataLoader)
        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, batch_spec(self.mesh))
        with self._span("facade.shard_batch"):  # host data onto the mesh
            return jax.tree.map(
                lambda a: jax.make_array_from_process_local_data(
                    sharding, np.asarray(a)
                ),
                x,
            )

    # -- checkpoint --------------------------------------------------------

    def save(self, path: str = "./", name: str = "checkpoint", extras: dict | None = None):
        """Consolidated save → ``(full_path, tag)`` (`Stoke-DDP.py:142-145`).
        Unlike the reference, optimizer/scaler/step/RNG state is included."""
        self._require_state()
        named = {
            "params": self._state.params,
            "model_state": self._state.model_state,
        }
        positional = {"opt_state": self._state.opt_state}
        meta = {
            "step": int(self._state.step),
            "lr": self._opt_handle.lr,
            "backward_count": self._backward_count,
            "rng": np.asarray(jax.random.key_data(self._state.rng)).tolist(),
            "scaler": None
            if self._state.scaler is None
            else {
                "scale": float(self._state.scaler.scale),
                "growth_count": int(self._state.scaler.growth_count),
            },
            **(extras or {}),
        }
        return ckpt.save_checkpoint(path, name, named, positional, meta)

    def load(self, path: str):
        """Full-state restore (the resume path the reference lacks)."""
        self._require_state()
        flat, meta = ckpt.load_checkpoint(path)
        params = ckpt.load_params_dict(
            ckpt.extract_tree(flat, "params"), jax.device_get(self._state.params)
        )
        opt_state = ckpt.restore_positional(flat, "opt_state", self._state.opt_state)
        model_state = ckpt.extract_tree(flat, "model_state")
        scaler = self._state.scaler
        if meta.get("scaler") and scaler is not None:
            scaler = scaler.replace(
                scale=jnp.float32(meta["scaler"]["scale"]),
                growth_count=jnp.int32(meta["scaler"]["growth_count"]),
            )
        rng = self._state.rng
        if "rng" in meta:
            rng = jax.random.wrap_key_data(
                jnp.asarray(meta["rng"], dtype=jnp.uint32)
            )
        new = self._state.replace(
            params=params,
            opt_state=opt_state,
            model_state=model_state or self._state.model_state,
            step=jnp.int32(meta.get("step", 0)),
            rng=rng,
            scaler=scaler,
        )
        # re-place on the policy's shardings
        self._state = jax.device_put(new, self._shardings)
        self._opt_handle.lr = float(meta.get("lr", self._opt_handle.lr))
        if self.verbose:
            self.print_on_devices(f"restored checkpoint @ step {int(self._state.step)}")

    def load_model_state(
        self, source, strict: bool = True, param_key: str = "params",
        key_map=None,
    ):
        """Pretrained-weights load with optional ``'params'`` nesting and
        strict matching (`Stoke-DDP.py:209-213`). Accepts framework ``.npz``
        checkpoints or torch ``.pth``/``.pt`` files (the reference's
        pretrained format): torch tensors get layout conversion (OIHW→HWIO,
        [out,in]→[in,out]) and weight→kernel/scale renames automatically;
        pass ``key_map`` (dict or ``[(regex, repl)]``) when the module paths
        themselves differ (see interop.load_torch_into_template)."""
        self._require_state()
        if key_map is None:
            from ..models.swinir import SwinIR as _SwinIR

            if isinstance(self._module, _SwinIR):
                # the reference's own checkpoint family loads unmodified
                # (`Stoke-DDP.py:209-213` -> torch-SwinIR state_dict naming);
                # the classical 'pixelshuffle' tail names its upsample
                # modules differently, so the map follows the model config
                from ..models.swinir import (
                    TORCH_KEY_MAP,
                    TORCH_KEY_MAP_CLASSICAL,
                )

                key_map = (
                    TORCH_KEY_MAP_CLASSICAL
                    if self._module.upsampler in ("pixelshuffle",
                                                  "nearest+conv")
                    else TORCH_KEY_MAP
                )
        if isinstance(source, str):
            if source.endswith((".pth", ".pt")):
                from ..interop import (
                    load_torch_checkpoint,
                    load_torch_into_template,
                )

                params = load_torch_into_template(
                    load_torch_checkpoint(source),
                    jax.device_get(self._state.params),
                    key_map=key_map, strict=strict, param_key=param_key,
                )
                params = jax.device_put(params, self._shardings.params)
                self._state = self._state.replace(params=params)
                return
            flat, _ = ckpt.load_checkpoint(source)
            source = ckpt.flat_dict_to_tree(flat)
        params = ckpt.load_params_dict(
            source, jax.device_get(self._state.params), strict=strict,
            param_key=param_key,
        )
        params = jax.device_put(params, self._shardings.params)
        self._state = self._state.replace(params=params)

    def save_sharded(self, path: str) -> str:
        """Per-shard (orbax) save of the FULL train state — the TPU-scale
        path: every process writes its own shards, no consolidation OOM."""
        self._require_state()
        from ..checkpoint_sharded import save_sharded as _save

        return _save(path, self._state, force=True)

    def load_sharded(self, path: str) -> None:
        """Restore a :meth:`save_sharded` checkpoint into the live state,
        preserving the policy's shardings."""
        self._require_state()
        from ..checkpoint_sharded import restore_sharded as _restore

        self._state = _restore(path, self._state)
        if self.verbose:
            self.print_on_devices(
                f"restored sharded checkpoint @ step {int(self._state.step)}"
            )

    def save_portable(self, path: str, *, step: int | None = None) -> str:
        """Topology-independent save of the FULL train state: the portable
        format (manifest + per-rank shards + commit marker) that
        :meth:`load_resharded` can restore onto a DIFFERENT mesh shape."""
        self._require_state()
        from ..checkpoint_sharded import save_portable as _save

        return _save(
            path, self._state,
            step=int(self._state.step) if step is None else step,
        )

    def load_resharded(self, path: str) -> None:
        """Restore a :meth:`save_portable` checkpoint into the live state,
        re-homing every leaf (params AND optimizer moments) onto THIS
        run's mesh/shardings — the N→M elastic-resume path."""
        self._require_state()
        from ..checkpoint_sharded import restore_portable as _restore

        self._state = _restore(path, self._state)
        if self.verbose:
            self.print_on_devices(
                f"resharded portable checkpoint @ step "
                f"{int(self._state.step)}"
            )

    def serve(self, **overrides):
        """Build a serving engine over the live params (``serve/``).

        GPT-2 gets the continuous-batching :class:`~..serve.engine.
        ServeEngine` (paged KV cache, chunked prefill, fixed compiled
        shapes); SwinIR gets the tiled
        :class:`~..serve.tiles.SwinIRTileServer`. Defaults come from the
        ``GRAFT_SERVE_*`` env family (slots, page size, prefill buckets,
        tile size — see ``serve/__init__.py``); keyword ``overrides``
        win over env. The engine snapshots the current params — later
        training steps do not leak into in-flight generations.
        """
        self._require_state()
        from ..serve import build_engine

        overrides = _serve_fastpath_overrides(self.tpu_config, overrides)
        return build_engine(self._module, self._state.params, **overrides)

    def serve_fleet(
        self,
        replicas: int | None = None,
        standby: int = 0,
        *,
        started: bool = True,
        route_knobs: dict | None = None,
        **overrides,
    ):
        """Build a fault-tolerant serve fleet over the live params
        (``serve/fleet.py``): N engines behind a membership-backed
        :class:`~..serve.router.FleetRouter` with failover, graceful
        drain/migration, and SLO-driven elastic scaling.

        ``replicas`` defaults to ``GRAFT_SERVE_REPLICAS`` (2); each
        replica gets its OWN engine built exactly like :meth:`serve`
        (same ``GRAFT_SERVE_*`` knobs and ``overrides``, same snapshotted
        params — so replay and KV migration land bitwise-identical
        greedy tokens on any replica). ``standby`` engines register as
        scale-out capacity the controller can admit when the SLO burn
        rate runs hot. Router behavior comes from the ``GRAFT_ROUTE_*``
        family (deadline, retries, backoff, TTL, breaker — see
        ``docs/SERVING.md``), overridable via ``route_knobs``. Returns
        the started :class:`~..serve.fleet.ServeFleet` (a context
        manager; ``stop()`` or ``with`` tears it down).
        """
        self._require_state()
        from ..serve import build_engine
        from ..serve.fleet import ServeFleet

        overrides = _serve_fastpath_overrides(self.tpu_config, overrides)
        n = replicas if replicas is not None else int(
            os.environ.get("GRAFT_SERVE_REPLICAS", "2") or 2
        )
        if n < 1:
            raise ValueError(f"serve_fleet needs >=1 replica, got {n}")
        engines = {
            f"replica-{i}": build_engine(
                self._module, self._state.params, **overrides
            )
            for i in range(n)
        }
        standbys = {
            f"standby-{i}": build_engine(
                self._module, self._state.params, **overrides
            )
            for i in range(max(0, int(standby)))
        }
        fleet = ServeFleet(
            engines, standby=standbys or None, route_knobs=route_knobs,
        )
        return fleet.start() if started else fleet

    def export_trace(self, path: str | None = None) -> str | None:
        """Write recorded telemetry spans as Chrome trace-event JSON.

        Destination precedence: explicit ``path`` > ``trace_dir`` resolved
        at construction ($GRAFT_TRACE / TPUConfig.trace_dir) > the shared
        run dir. Returns the written path, or None when telemetry was
        never enabled (nothing to export ≠ an error)."""
        if not _telemetry.enabled() and not _telemetry.records():
            return None
        if path is None:
            base = self.trace_dir or _telemetry.run_dir()
            if base.endswith(".json"):
                path = base
            else:
                path = os.path.join(
                    base, f"telemetry-{os.getpid()}.trace.json"
                )
        return _telemetry.export_chrome_trace(path)

    # -- introspection / rank I/O ------------------------------------------

    @property
    def world_size(self) -> int:
        return _dist.world_size()

    @property
    def rank(self) -> int:
        return _dist.rank()

    @property
    def optimizer(self) -> optim_mod.OptimizerHandle:
        return self._opt_handle

    @property
    def model_access(self) -> _ModelAccess:
        return _ModelAccess(self)

    @property
    def state(self):
        self._require_state()
        return self._state

    @property
    def step_count(self) -> int:
        return 0 if self._state is None else int(self._state.step)

    @property
    def ema_params(self):
        """Eval-ready params-EMA tree, or None when no EMA is tracked.

        Enable via ``optimizer_kwargs={'ema_decay': 0.999}`` (works on
        both the auto-selected fused path and the per-leaf chain); the
        EMA updates inside the compiled step and shards/checkpoints with
        the optimizer state. Evaluate with
        ``model.apply({'params': stoke_model.ema_params}, x)``.
        """
        if self._state is None:
            return None
        return optim_mod.ema_params(
            self._state.opt_state, self._state.params
        )

    def print_on_devices(self, msg: str = ""):
        """Rank-stamped print (`Stoke-DDP.py:67,130`)."""
        print(f"[rank {self.rank}/{self.world_size}] {msg}", flush=True)

    def print_ema_loss(self, prepend_msg: str = "EMA Loss"):
        """Smoothed-loss print (`Stoke-DDP.py:76`).

        On the fused training path the loss value only exists once
        ``.backward()`` runs, so when called between ``.loss()`` and
        ``.backward()`` (the reference's order) the printed EMA includes
        every loss up to the *previous* iteration — a one-call display lag
        on a 0.98-decay monitor, accepted to keep the hot loop at exactly
        one compiled fwd+bwd program.

        The fetch itself is asynchronous (``_AsyncScalarFetcher``): the
        printed value is the freshest EMA that has *arrived* on the host,
        so a per-step verbose loop never blocks on the device. The first
        call blocks once so the very first line already shows a real
        number. Exact synchronous reads remain available via
        ``detach_and_sync_loss`` / ``_last_loss``."""
        if self._ema_dev is not None and self.verbose:
            self._ema_async.submit(self._ema_dev)
            val = self._ema_async.value
            if val is None:  # first call: one blocking fetch
                val = self._ema_async.flush()
            if val is None:  # async fetch failed (e.g. deleted buffer):
                try:  # fall back to one exact blocking read
                    val = float(np.asarray(self._ema_dev))
                except Exception:
                    return
            print(f"{prepend_msg}: {val:.6f}", flush=True)

    def barrier(self):
        from ..ops import barrier

        barrier()

    def eval_step(
        self, metric_fns: dict | None = None, use_ema: bool = False
    ) -> Callable:
        """Policy-aware compiled validation step.

        Returns ``step(inputs, targets) -> dict`` of device scalars:
        ``{"loss": ..., **metric_fns}`` computed in one compiled program
        under the same sharded layout training uses (:class:`EvalStep` —
        params keep their policy placement, the batch rides the mesh's
        data axes). Results stay on device so the caller can accumulate
        across batches and pay one host sync per epoch, unlike the
        reference's per-batch ``float()`` loop (`Stoke-DDP.py:114-121`).

        ``use_ema=True`` evaluates the tracked params EMA (see
        :attr:`ema_params`) instead of the raw weights — the standard SR
        eval protocol when ``ema_decay`` is on.
        """
        self._require_state()
        metric_fns = dict(metric_fns or {})
        if use_ema and not optim_mod.has_ema(self._state.opt_state):
            # whether an EMA is tracked is fixed at optimizer
            # construction — fail at build, not on the first batch
            # (presence probe only: extraction is paid per epoch below)
            raise ValueError(
                "use_ema=True but no EMA is tracked — pass "
                "optimizer_kwargs={'ema_decay': ...}"
            )
        # keyed by fn identity AND the current shardings object: a re-init
        # (new mesh/policy) must not replay a step jitted against stale
        # in_shardings. Bounded: fresh lambdas per epoch would otherwise
        # grow the cache (and retained closures) without limit.
        key = (
            tuple(sorted((name, id(fn)) for name, fn in metric_fns.items())),
            id(self._shardings),
            bool(use_ema),
        )
        cached = getattr(self, "_eval_steps", None)
        if cached is None:
            cached = self._eval_steps = {}
        if key in cached:
            return cached[key]
        if len(cached) >= 8:
            cached.pop(next(iter(cached)))  # evict oldest

        # one compiled program serves both the raw and EMA wrappers
        # (use_ema only changes which params tree is fed)
        inners = getattr(self, "_eval_inners", None)
        if inners is None:
            inners = self._eval_inners = {}
        ikey = key[:2]
        inner = inners.get(ikey)
        if inner is None:
            from ..parallel.step import EvalStep

            precision = self.precision
            loss_callable = self._loss_callable

            def eval_fn(params, batch, model_state):
                x, y = batch
                pc = precision.cast_to_compute(params)
                out, _ = self._apply_model(
                    pc, model_state, x, train=False, rng=None
                )
                out = precision.cast_to_output(out)
                result = {"loss": loss_callable(out, y)}
                for name, fn in metric_fns.items():
                    result[name] = fn(out, y)
                return result

            inner = EvalStep(
                eval_fn, self.mesh, state_shardings=self._shardings
            )
            if len(inners) >= 8:
                inners.pop(next(iter(inners)))
            inners[ikey] = inner

        # EMA extraction is opt_state-fixed for a whole validation epoch:
        # memoize per state object (held by reference — an `id` key could
        # be recycled after GC and silently serve a stale tree), and place
        # the tree on the DECLARED param shardings so the jitted step never
        # reshards per batch (host-offloaded layouts keep their memory kind)
        ema_cache: dict = {"state": None, "tree": None}

        def step(inputs, targets):
            st = self._state
            if use_ema:
                if ema_cache["state"] is not st.opt_state:
                    ep = self.ema_params
                    ep = jax.tree.map(
                        lambda e, s: jax.device_put(e, s),
                        ep, self._shardings.params,
                    )
                    ema_cache["state"], ema_cache["tree"] = st.opt_state, ep
                st = st.replace(params=ema_cache["tree"])
            batch = (self._shard_batch(inputs), self._shard_batch(targets))
            return inner(st, batch)

        cached[key] = step
        return step

    @property
    def _ema_loss(self):
        """Host view of the EMA loss (None before any step)."""
        if self._ema_dev is None:
            return None
        return float(jax.device_get(self._ema_dev))

    @property
    def _last_loss(self):
        """Host view of the most recent loss (None before any step)."""
        if self._last_loss_dev is None:
            return None
        return float(jax.device_get(self._last_loss_dev))

    def _note_loss(self, loss):
        """Record a step's loss WITHOUT synchronizing the host.

        The round-2 version called ``float(jax.device_get(loss))`` here,
        blocking the host on every iteration of the reference-shaped loop
        (`Stoke-DDP.py:73-86`) so the device could never be dispatched
        ahead. Now the EMA is folded on-device by a compiled scalar op and
        fetched only by ``print_ema_loss`` / the ``_last_loss`` property.
        """
        if isinstance(loss, jax.core.Tracer):
            return
        try:
            loss = jnp.asarray(loss)
        except (TypeError, ValueError):
            return
        if loss.ndim != 0:  # per-sample/per-shard losses: monitor the mean
            loss = jnp.mean(loss)
        self._last_loss_dev = loss
        with self._span("facade.note_loss"):  # the monitor's own dispatch
            self._ema_dev = (
                jnp.asarray(loss, jnp.float32)
                if self._ema_dev is None
                else self._run("_jit_ema", self._ema_dev, loss)
            )
        if self.verbose:
            # keep the async display value ~one link-RTT fresh even when
            # print_ema_loss is called rarely (staleness otherwise spans
            # the whole print interval); last-value-wins, off hot path
            self._ema_async.submit(self._ema_dev)

    def _require_state(self):
        if self._state is None:
            raise RuntimeError(
                "Stoke is not initialized — call .init(sample_input) or run a "
                "first .model(inputs)"
            )
