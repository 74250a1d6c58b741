"""The compiled train step: fwd → bwd → clip → update, one XLA program.

This is where the reference's eager hot loop (`/root/reference/
Stoke-DDP.py:70-86`: forward, loss, ``backward`` with grad-accum division,
hook-fired collectives, ``step`` with unscale→clip→sharded update→param
broadcast — three separate device/network crossings) becomes a single SPMD
function. XLA fuses the collectives into the compute schedule; grad
accumulation is a `lax.scan` over microbatches inside the step (no host
round-trips, hard part (b) of SURVEY §7); the fp16 scale/unscale/skip dance
is branchless arithmetic in the same program.

Contract for ``loss_fn``::

    loss_fn(params, batch, rng, model_state) -> (loss, aux_dict)

``aux_dict`` may carry a ``"model_state"`` entry (updated mutable
collections, e.g. sync-BN stats) which replaces ``state.model_state``;
other entries are reported as metrics (averaged over microbatches).

Siblings with the same optimizer/donation/metrics contract: ``MultiStep``
(k steps per dispatch), ``CompressedGradStep`` (grad wire compression),
and ``parallel.pipeline.PipelineStep`` — the schedule-driven pipeline
engine for meshes with a "pp" axis (this class does NOT pipeline; it
warns if handed one).
"""

from __future__ import annotations

from typing import Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..observe import trace as telemetry
from ..observe.numerics import NumericsProbe
from ..observe.profiling import remember_program
from ..optim import FusedAdamW, clip_stats, refresh_params_ema
from ..precision import DynamicLossScaler, Policy as PrecisionPolicy
from ..runtime.mesh import batch_spec, stacked_batch_spec
from .policy import Policy
from .remat import apply_remat
from .spec import batch_layout, constrain, stream_to_device
from .state import TrainState


@runtime_checkable
class CostSurface(Protocol):
    """The analytic cost contract every plannable step class exposes.

    ``comm_cost(params)`` returns at least ``{"collective",
    "fp32_bytes", "wire_bytes", "wire_format", "axis", "axis_size"}``
    with the shared hop convention (reduce-scatter moves n bytes per
    shard, all-reduce 2n); ``wire_bytes`` is what actually crosses the
    wire after any grad compression (== ``fp32_bytes`` on the f32
    wire). `TrainStep`, `CompressedGradStep`, and `PipelineStep` all
    satisfy it, so `analyze.planner` can rank any of them off one
    surface.
    """

    def comm_cost(self, params) -> dict: ...


def _split_microbatches(batch, n: int):
    """[B, ...] -> [n, B/n, ...] on every leaf."""

    def split(x):
        b = x.shape[0]
        if b % n:
            raise ValueError(f"batch {b} not divisible by grad_accum_steps {n}")
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(split, batch)


class TrainStep:
    """Assembles and jits the policy-sharded train step.

    The eager-feeling facade (`stoke/facade.py`) replays this one compiled
    function; drivers may also call it directly (the fast path).
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        policy: Policy | None = None,
        *,
        grad_accum_steps: int = 1,
        precision: PrecisionPolicy | None = None,
        loss_scaler: DynamicLossScaler | None = None,
        state_shardings: TrainState | None = None,
        extra_metrics: bool = True,
        donate: bool = True,
        detect_anomaly: bool = False,
        update_wire_dtype=None,
        numerics: NumericsProbe | bool | None = None,
    ):
        self.tx = tx
        self.mesh = mesh
        self.policy = policy or Policy()
        # Activation rematerialization (FSDP/DeepSpeed activation-
        # checkpointing twin at the step level), resolved through the named
        # registry (parallel/remat.py): "full" recomputes the forward but
        # for a blockwise attention kernel's, whose output and row
        # statistics it keeps (~1/3 extra FLOPs for near-minimum HBM),
        # "dots" saves matmul outputs too, "names"/"offload" the
        # checkpoint_name-tagged activations (attention outputs in the
        # model zoo). Finer-grained per-block remat lives in the models'
        # own `remat` flags (gpt2/vit/swinir); both compose (inner
        # checkpoints nest).
        # The step owns the mesh, so the step says where activations live:
        # while the loss is traced the batch's layout is published and the
        # model's residual stream is pinned to it (spec.pin_batch), else
        # GSPMD may gather the batch instead of the sharded parameters.
        # Inside the checkpointed function: jax.checkpoint caches its
        # trace on the function it wraps, and this one is the step's own.
        def laid_out(params, batch, rng, model_state):
            with batch_layout(mesh):
                return loss_fn(params, batch, rng, model_state)

        self.loss_fn = apply_remat(laid_out, self.policy.remat)
        self.grad_accum_steps = int(grad_accum_steps)
        self.precision = precision or PrecisionPolicy()
        self.loss_scaler = loss_scaler
        self.extra_metrics = extra_metrics
        # torch.autograd.set_detect_anomaly twin: raise with the offending
        # param paths the step a non-finite gradient appears (debug mode —
        # the host callback costs a device sync per step). Forces
        # donate=False so the pre-step state survives for inspection when
        # the (possibly async) callback error surfaces.
        self.detect_anomaly = detect_anomaly
        # Numerics observability plane (observe/numerics.py): one fused
        # aux computation appended to the step — first-offender blame,
        # grad/param norms, update ratios, fp8/wire health — landing
        # under metrics["numerics"] for the host probe/watchdog. Unlike
        # detect_anomaly this costs NO device sync; the host decodes at
        # its own cadence.
        self.numerics = (
            NumericsProbe() if numerics is True
            else (numerics or None)
        )
        # Fairscale OSS broadcast_fp16 twin (`Stoke-DDP.py:197-199`): under
        # ZeRO the optimizer update is computed on sharded state and fans
        # out through an implicit all-gather; casting the update to a
        # narrow wire dtype before the add halves that fan-out traffic —
        # the same deliberate lossiness as the reference's fp16 param
        # broadcast (bf16 here: TPU-native, same 2-byte wire).
        self.update_wire_dtype = update_wire_dtype
        # Flat fused update path (see optim.FusedAdamW). Composes with
        # ZeRO-1 (the flat [N] moments shard over the data axis through
        # the ordinary opt_specs path; GSPMD all-gathers the flat update
        # once). Per-leaf grad/param sharding (ZeRO-2/3) has no flat
        # story, and the per-leaf wire cast belongs to the tree path —
        # FusedAdamW carries its own update_wire_dtype.
        self.fused = tx if isinstance(tx, FusedAdamW) else None
        if self.fused is not None and (
            self.policy.shard_grads
            or self.policy.shard_params
            or update_wire_dtype is not None
        ):
            raise ValueError(
                "FusedAdamW composes with replicated (DDP) and ZeRO-1 "
                "layouts only: ZeRO-2/3 shard grads/params per leaf, and "
                "update_wire_dtype is the tree path's knob (pass "
                "FusedAdamW(update_wire_dtype=...) instead) — use "
                "optim.adamw for those"
            )
        if detect_anomaly:
            donate = False
        self.donate = donate  # MultiStep mirrors this choice

        self._state_shardings = state_shardings
        if (
            self.fused is not None
            and self.policy.shard_opt_state
            and state_shardings is not None
            and all(
                getattr(s, "spec", None) == PartitionSpec()
                for s in jax.tree.leaves(state_shardings.opt_state)
                if hasattr(s, "spec")
            )
        ):
            # the ZeRO-1 memory saving the user asked for silently never
            # materializes when the axis doesn't divide the padded flat
            # length (FusedAdamW._PAD) — say so instead of training on
            import warnings

            warnings.warn(
                "FusedAdamW under a sharded-opt-state policy, but the "
                "flat moments resolved to fully replicated (mesh axis "
                "does not divide the padded length?) — the ZeRO-1 memory "
                "saving is not in effect",
                RuntimeWarning,
                stacklevel=2,
            )
        if mesh.shape.get("pp", 1) > 1:
            # TrainStep has no stage placement: on a pp mesh the whole
            # model replicates across pp ranks and the axis computes the
            # same step N times — almost certainly not what was meant
            import warnings

            warnings.warn(
                "TrainStep on a mesh with a pp axis of size "
                f"{mesh.shape['pp']}: the step does not pipeline — the pp "
                "ranks run replicated, identical work. Use "
                "parallel.PipelineStep (schedule-driven 1F1B engine) for "
                "pipeline parallelism",
                RuntimeWarning,
                stacklevel=2,
            )
        data_sharding = NamedSharding(mesh, batch_spec(mesh))
        # pytree-prefix semantics: one sharding covers every batch leaf
        self._jitted = jax.jit(
            self._step,
            in_shardings=(state_shardings, data_sharding, None),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )

    # -- the traced function ------------------------------------------------

    def _grads_one(self, params, model_state, batch, rng, scaler_state):
        """Value-and-grad on one microbatch (precision + loss scaling)."""

        def lfn(p):
            pc = self.precision.cast_to_compute(p)
            loss, aux = self.loss_fn(pc, batch, rng, model_state)
            scaled = (
                loss * scaler_state.scale.astype(loss.dtype)
                if scaler_state is not None
                else loss
            )
            return scaled, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(lfn, has_aux=True)(params)
        return loss, aux, grads

    def _step(self, state: TrainState, batch, lr_factor):
        if self._state_shardings is not None and (
            self.policy.offload_params or self.policy.offload_opt_state
        ):
            # offloaded leaves live in pinned host memory between steps;
            # stream them in (async DMA), compute on device, and let
            # out_shardings (which keep the host kind) write results back
            state = state.replace(
                params=stream_to_device(
                    state.params, self._state_shardings.params
                ),
                opt_state=stream_to_device(
                    state.opt_state, self._state_shardings.opt_state
                ),
            )
        rng = jax.random.fold_in(state.rng, state.step)

        if self.grad_accum_steps > 1:
            micro = _split_microbatches(batch, self.grad_accum_steps)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )

            def body(acc, mb_i):
                mb, i = mb_i
                loss, aux, grads = self._grads_one(
                    state.params, state.model_state, mb,
                    jax.random.fold_in(rng, i), state.scaler
                )
                with jax.named_scope("grad_accum"):
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), acc, grads
                    )
                return acc, (loss, aux)

            gsum, (losses, auxs) = jax.lax.scan(
                body, zero, (micro, jnp.arange(self.grad_accum_steps))
            )
            # mean over microbatches (the ref divides in backward, :79,251)
            with jax.named_scope("grad_accum"):
                grads = jax.tree.map(
                    lambda g: g / self.grad_accum_steps, gsum
                )
            loss = jnp.mean(losses)
            aux = {
                k: (
                    jax.tree.map(lambda x: x[-1], v)  # state: keep last
                    if k == "model_state"
                    else jax.tree.map(lambda x: jnp.mean(x, axis=0), v)
                )
                for k, v in auxs.items()
            }
        else:
            loss, aux, grads = self._grads_one(
                state.params, state.model_state, batch, rng, state.scaler
            )

        if self.numerics is not None:
            # deterministic NaN drill (GRAFT_NUMERICS_INJECT): branchless
            # on the traced step counter, a no-op without a spec
            grads = self.numerics.inject(grads, state.step)

        new_scaler = None
        finite = jnp.bool_(True)
        gnorm_fused = None
        updates = None  # tree path sets it; the probe's update-ratio feed
        # everything from the finished gradients to the new state is the
        # "optimizer" of a profile (metadata only: no instruction changes);
        # optim.py nests "clip" and "adamw" inside it
        with jax.named_scope("optimizer"):
            if self.fused is not None:
                # flat path: ravel once, scaler/clip/Adam as full-width vector
                # ops, unravel once (see optim.FusedAdamW.apply_tree)
                if self.detect_anomaly:
                    # NaN survives the (power-of-two) scale, so the tree-path
                    # check below reads identically on still-scaled grads
                    self._check_finite(
                        grads, loss, nan_only=self.loss_scaler is not None
                    )
                scaler_state = (
                    state.scaler if self.loss_scaler is not None else None
                )
                new_params, new_opt, new_scaler, gnorm_fused = (
                    self.fused.apply_tree(
                        grads,
                        state.opt_state,
                        state.params,
                        lr_factor,
                        scaler=self.loss_scaler,
                        scaler_state=scaler_state,
                    )
                )
            else:
                # fp16: unscale to f32 before clip/update (torch unscale_
                # parity)
                if self.loss_scaler is not None and state.scaler is not None:
                    grads = self.loss_scaler.unscale_grads(grads, state.scaler)
                    finite = DynamicLossScaler.grads_finite(grads)
                    new_scaler = self.loss_scaler.update(state.scaler, finite)
                else:
                    grads = jax.tree.map(
                        lambda g: g.astype(jnp.float32), grads
                    )

                if self.detect_anomaly:
                    # after unscale; with a loss scaler active only NaN is
                    # anomalous (inf overflows are the scaler's own
                    # backoff-and-skip path — torch's set_detect_anomaly
                    # likewise flags NaN only)
                    self._check_finite(
                        grads, loss, nan_only=self.loss_scaler is not None
                    )

                # ZeRO-2/3: force reduce-scatter layout on grads (named, so
                # a profile can tell this traffic from parameter gathers)
                gspecs = self.policy.grads_specs(state.params, self.mesh)
                if gspecs is not None:
                    with jax.named_scope("grad_sync"):
                        grads = constrain(grads, gspecs, self.mesh)

                updates, new_opt = self.tx.update(
                    grads, state.opt_state, state.params
                )
                # the plateau scheduler's factor
                updates = jax.tree.map(lambda u: u * lr_factor, updates)
                if self.update_wire_dtype is not None:
                    # narrow the fan-out wire (see ctor comment); the add below
                    # upcasts back to the param dtype
                    updates = jax.tree.map(
                        lambda u: u.astype(self.update_wire_dtype), updates
                    )
                new_params = optax.apply_updates(state.params, updates)
                # params-EMA correction: the chain element saw pre-lr_factor
                # updates; recompute from the TRUE new params
                # (optim.params_ema)
                new_opt = refresh_params_ema(
                    state.opt_state, new_opt, new_params
                )

                if self.loss_scaler is not None:
                    # skip the whole update on overflow (GradScaler semantics)
                    new_params = jax.tree.map(
                        lambda n, o: jnp.where(finite, n, o),
                        new_params,
                        state.params,
                    )
                    new_opt = jax.tree.map(
                        lambda n, o: jnp.where(finite, n, o),
                        new_opt,
                        state.opt_state,
                    )

        new_model_state = aux.get("model_state", state.model_state)
        metrics = {"loss": loss.astype(jnp.float32)}
        # the recorded-clip chain element (optim.clip_by_global_norm_
        # recorded) already computed the pre-clip global norm; read it
        # from the fresh opt state instead of computing the norm twice
        recorded_clip = clip_stats(new_opt)
        gnorm_known = (
            gnorm_fused
            if gnorm_fused is not None
            else (recorded_clip.gnorm if recorded_clip is not None else None)
        )
        if self.extra_metrics:
            if gnorm_known is None:
                with jax.named_scope("metrics"):  # a norm only to return it
                    gnorm_known = optax.global_norm(grads)
            metrics["grad_norm"] = gnorm_known
            if recorded_clip is not None:
                metrics["grad_clipped"] = recorded_clip.clipped
            if new_scaler is not None:
                metrics["loss_scale"] = new_scaler.scale
        if self.numerics is not None:
            with jax.named_scope("metrics"):
                metrics["numerics"] = self.numerics.aux(
                    grads,
                    params=state.params,
                    updates=updates,
                    model_state=new_model_state,
                    grad_norm=gnorm_known,
                )
        for k, v in aux.items():
            if k != "model_state":
                metrics[k] = v

        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            model_state=new_model_state,
            scaler=new_scaler if new_scaler is not None else state.scaler,
        )
        return new_state, metrics

    def _check_finite(self, grads, loss, nan_only: bool = False):
        """In-jit anomaly check: host callback raises naming bad leaves.

        The raise travels through ``jax.debug.callback``, so on async
        backends it surfaces at the next sync point (possibly wrapped in an
        XlaRuntimeError) — debug-mode semantics; donation is disabled so
        the caller's pre-step state stays inspectable.
        """
        ok = (
            (lambda v: jnp.logical_not(jnp.any(jnp.isnan(v))))
            if nan_only
            else (lambda v: jnp.all(jnp.isfinite(v)))
        )
        paths = [
            jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(grads)[0]
        ]
        flags = jnp.asarray([ok(v) for v in jax.tree.leaves(grads)])
        loss_ok = ok(loss)

        def raise_on_bad(flags_host, loss_ok_host):
            bad = [p for p, ok in zip(paths, flags_host) if not ok]
            if not loss_ok_host:
                bad = ["<loss>"] + bad
            if bad:
                raise FloatingPointError(
                    "detect_anomaly: non-finite values in "
                    + ", ".join(bad[:8])
                    + (" ..." if len(bad) > 8 else "")
                )

        jax.debug.callback(raise_on_bad, flags, loss_ok)

    def precompile(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compile the step without executing it.

        With the persistent compilation cache enabled the artifact lands
        on disk, so the first real call is a fast deserialize. Use before
        ``runtime.dist.coordination_barrier`` in multi-process runs: it
        takes per-rank compile skew out of the first collective's window
        (Gloo's context bootstrap has a fixed ~30 s timeout that compile
        skew on oversubscribed hosts can exceed).
        """
        if not jax.config.jax_compilation_cache_dir:
            import warnings

            warnings.warn(
                "TrainStep.precompile without jax_compilation_cache_dir: "
                "the AOT artifact is discarded and the first real step "
                "recompiles — enable the persistent compilation cache for "
                "precompile to pay off",
                RuntimeWarning,
                stacklevel=2,
            )
        with self.mesh:
            self._jitted.lower(state, batch, jnp.float32(lr_factor)).compile()

    def compiled_text(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiled HLO of this step, for `observe.hlo` collective audits
        (prove the compiler emitted the policy's promised wire plan)."""
        with self.mesh:
            return (
                self._jitted.lower(state, batch, jnp.float32(lr_factor))
                .compile()
                .as_text()
            )

    def comm_cost(self, params) -> dict:
        """Analytic bytes-on-wire for the grad hop of one step — the f32
        twin of ``CompressedGradStep.wire_cost`` (same hop convention: a
        reduce-scatter moves n bytes per shard, an all-reduce 2n for the
        reduce + gather hops). Leaves below the policy's
        ``min_shard_size`` floor stay replicated and pay the all-reduce
        rate even under ``shard_grads``. Feeds the opcost plane's "wire"
        calibration model (analytic bytes vs HLO-measured bytes).
        """
        from .spec import leaf_spec, shard_axis

        ax = shard_axis(self.mesh)
        size = int(self.mesh.shape.get(ax, 1)) if ax else 1
        if ax is None or size <= 1:
            return {
                "collective": None,
                "fp32_bytes": 0,
                "wire_bytes": 0,
                "wire_format": None,
                "axis": None,
                "axis_size": 1,
            }
        rs = bool(self.policy.shard_grads)
        total = 0
        for p in jax.tree.leaves(params):
            n = 1
            for s in p.shape:
                n *= int(s)
            scattered = rs and leaf_spec(
                p.shape, ax, size, self.policy.min_shard_size
            ) != PartitionSpec()
            hops = 1 if scattered else 2
            total += hops * n * 4
        return {
            "collective": "reduce-scatter" if rs else "all-reduce",
            "fp32_bytes": int(total),
            # f32 wire: on-wire bytes == fp32 bytes, no quantized format
            "wire_bytes": int(total),
            "wire_format": None,
            "axis": ax,
            "axis_size": size,
        }

    def memory_analysis(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiler memory accounting for this step (`observe.memory`).

        Returns a :class:`~..observe.memory.MemoryStats` (peak / argument /
        temp bytes per device) or ``None`` when the backend's compiler
        doesn't report memory. Costs an AOT compile — with the persistent
        compilation cache enabled the XLA work is a disk deserialize.
        """
        from ..observe.memory import compiled_memory_stats

        with self.mesh:
            compiled = self._jitted.lower(
                state, batch, jnp.float32(lr_factor)
            ).compile()
        return compiled_memory_stats(compiled)

    def __call__(self, state: TrainState, batch, lr_factor: float = 1.0):
        # async dispatch: the span covers trace/compile + enqueue, not
        # device execution (which overlaps the host's next iteration —
        # the final block_until_ready's sync span absorbs the remainder)
        lr_factor = jnp.float32(lr_factor)
        if not hasattr(self, "_telemetry_dispatches"):  # the first call
            remember_program(self._jitted, (state, batch, lr_factor))
        with telemetry.dispatch_span(self, "TrainStep"):
            return self._jitted(state, batch, lr_factor)


class MultiStep:
    """K train steps as ONE compiled program (`lax.scan` over stacked
    batches).

    Amortizes per-dispatch host cost by K. When the host is the
    bottleneck (a small step is dispatch-bound, not FLOP-bound), wrap the
    step and stack K batches
    (:func:`~..data.stack_windows` handles host and device batches)::

        multi = MultiStep(step, k=8)
        for stacked in stack_windows(loader, 8):    # leaves [8, B, ...]
            state, metrics = multi(state, stacked)  # one dispatch

    Semantics vs. K ``step()`` calls: identical math, including the
    per-step rng fold (``state.step`` advances inside the scan). Metrics
    come back stacked ``[K]`` per entry (take ``[-1]`` or a mean).
    ``lr_factor`` is constant across the window — per-step schedules that
    must change within K steps (OneCycle per-batch) should either keep
    K small relative to the schedule's rate of change or stay on the
    single-step path.
    """

    def __init__(self, step: TrainStep, k: int):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.step = step
        self.k = int(k)
        mesh = step.mesh
        # stacked batches add a leading scan axis: shard everything after it
        # exactly like the single-step batch
        stacked_sharding = NamedSharding(mesh, stacked_batch_spec(mesh))
        sh = step._state_shardings

        def multi(state, batches, lr_factor):
            def body(s, mb):
                s2, m = step._step(s, mb, lr_factor)
                return s2, m

            return jax.lax.scan(body, state, batches)

        self._jitted = jax.jit(
            multi,
            in_shardings=(sh, stacked_sharding, None),
            out_shardings=(sh, None),
            # mirror the wrapped step's choice: donate=False callers (incl.
            # detect_anomaly's inspectable-pre-step-state contract) keep
            # their input state valid here too
            donate_argnums=(0,) if step.donate else (),
        )

    def __call__(self, state: TrainState, batches, lr_factor: float = 1.0):
        """``batches`` leaves are ``[K, B, ...]`` stacks."""
        k = jax.tree.leaves(batches)[0].shape[0]
        if k != self.k:
            raise ValueError(
                f"stacked batch has window {k}, MultiStep compiled for "
                f"{self.k}"
            )
        with self.step.mesh, telemetry.dispatch_span(self, "MultiStep"):
            return self._jitted(state, batches, jnp.float32(lr_factor))

    def feed(self, loader, depth: int | None = None):
        """Stacked windows from a loader, staged ahead via device prefetch.

        ``DataLoader.device_iter`` keeps up to ``depth`` batches in flight
        on the mesh while the previous window computes; ``stack_windows``
        then assembles ``[k, B, ...]`` stacks (already-on-device leaves
        stack for free). Default depth is ``k`` — one whole window staged
        ahead of the running one.
        """
        from ..data.loader import stack_windows

        mesh = self.step.mesh
        it = loader.device_iter(
            mesh, batch_spec(mesh), depth=self.k if depth is None else depth
        )
        return stack_windows(it, self.k)


def tune_multi_step_k(
    step: TrainStep,
    state: TrainState,
    batch,
    ks=(1, 2, 5, 10),
    steps_per_arm: int = 20,
    lr_factor: float = 1.0,
):
    """Measure K-steps-per-dispatch empirically and pick the winner.

    Whether :class:`MultiStep` pays depends on the host, not the model:
    on a dispatch-bound host it should win by ~k. Don't guess — measure
    each candidate k on the live backend and keep the best:

        best_k, rates, state = tune_multi_step_k(step, state, batch)
        multi = MultiStep(step, best_k) if best_k > 1 else step

    Costs one compile per candidate k plus ``steps_per_arm`` real
    optimizer steps per arm (the returned ``state`` has advanced; thread
    it back into training — with ``donate=True`` steps the input state
    is consumed either way). Pass the loop's current ``lr_factor`` so
    the tuning steps train at the schedule's real rate, not full LR.
    Timing is wall-clock per completed window with a final host fetch,
    so an arm cannot look fast by returning before its work is done.

    Returns ``(best_k, {k: steps_per_sec}, state)``. On a non-finite
    loss the raised ``RuntimeError`` carries ``err.state``: a snapshot
    of the state from *before* the failing arm — true last-good, never
    advanced through the NaN-poisoned steps (with donated steps the
    input state is already consumed; this keeps the run resumable
    without a checkpoint).
    """
    import time as _time

    rates: dict[int, float] = {}
    with step.mesh:
        for k in ks:
            k = int(k)
            n_calls = max(1, steps_per_arm // k)
            # snapshot BEFORE the arm touches the state: if this arm
            # diverges, every step inside it is suspect — handing back the
            # advanced (NaN-poisoned) state would poison the resumed run.
            # jnp.copy keeps each leaf's sharding; the arm's donated steps
            # consume `state`, never the snapshot.
            snapshot = jax.tree.map(jnp.copy, state)
            if k == 1:
                runner, fed = step, batch
            else:
                runner = MultiStep(step, k)
                fed = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (k,) + x.shape),
                    batch,
                )
            state, metrics = runner(state, fed, lr_factor)  # compile+warm
            jax.block_until_ready(metrics["loss"])
            t0 = _time.perf_counter()
            for _ in range(n_calls):
                state, metrics = runner(state, fed, lr_factor)
            # host fetch: transitively waits on every step of the arm
            last = jnp.ravel(metrics["loss"])[-1]
            if not bool(jnp.isfinite(last)):
                err = RuntimeError(f"non-finite loss while tuning k={k}")
                err.state = snapshot  # pre-arm state: last-good by construction
                raise err
            del snapshot
            rates[k] = k * n_calls / (_time.perf_counter() - t0)
    best_k = max(rates, key=rates.get)
    return best_k, rates, state


class EvalStep:
    """Compiled forward+metrics step (validation loop,
    `Stoke-DDP.py:101-128`).

    ``eval_fn(params, batch, model_state) -> dict`` of metrics.

    Honors the policy's state layout the same way TrainStep does: params /
    model_state keep their sharded placement (no implicit all-gather onto
    one device) and the batch is constrained to the mesh's data axes — so
    validation on a real mesh runs under the same SPMD layout as training
   .
    """

    def __init__(
        self,
        eval_fn: Callable,
        mesh: Mesh,
        *,
        state_shardings: TrainState | None = None,
    ):
        self.eval_fn = eval_fn
        self.mesh = mesh
        data_sharding = NamedSharding(mesh, batch_spec(mesh))
        if state_shardings is not None:
            in_shardings = (
                state_shardings.params,
                data_sharding,
                state_shardings.model_state,
            )
            param_shardings = state_shardings.params
        else:
            in_shardings = (None, data_sharding, None)
            param_shardings = None

        def run(params, batch, model_state):
            # offloaded params stream in exactly like the train step
            return eval_fn(
                stream_to_device(params, param_shardings), batch, model_state
            )

        self._jitted = jax.jit(run, in_shardings=in_shardings)

    def __call__(self, state: TrainState, batch):
        with self.mesh, telemetry.dispatch_span(self, "EvalStep"):
            return self._jitted(state.params, batch, state.model_state)
