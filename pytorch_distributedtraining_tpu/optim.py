"""Optimizers and LR schedules: AdamW, OneCycleLR, ReduceLROnPlateau.

Twin of the reference's optimizer surface — ``AdamW(lr=1e-4, betas=(0.9,
0.999), eps=1e-8, weight_decay=1e-5)`` built from a ``StokeOptimizer`` dict
(`/root/reference/Stoke-DDP.py:226-235`) or passed to OSS
(`Fairscale-DDP.py:78-86`) — plus the two schedulers the Stoke driver steps
(`Stoke-DDP.py:300-306`: ``OneCycleLR`` per-batch, ``ReduceLROnPlateau`` on
val loss; impls `torch/optim/lr_scheduler.py:1584,2285`).

TPU-native design: schedules are **pure functions of the step counter**
evaluated *inside* the compiled step (no host round-trip per batch — the
reference pays a Python call per ``scheduler.step()``). The one genuinely
data-dependent schedule, ReduceLROnPlateau, runs on host between epochs and
feeds a scalar ``lr_factor`` into the step — one small transfer per epoch,
not per batch.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.flatten_util import ravel_pytree


# -- optimizers --------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class ParamsEMAState:
    """EMA tree + its decay (decay is static aux data, not a leaf)."""

    def __init__(self, ema, decay: float):
        self.ema = ema
        self.decay = float(decay)

    def tree_flatten(self):
        return (self.ema,), self.decay

    @classmethod
    def tree_unflatten(cls, decay, children):
        return cls(children[0], decay)

    def __repr__(self):  # pragma: no cover - debugging nicety
        return f"ParamsEMAState(decay={self.decay})"


def params_ema(decay: float = 0.999) -> optax.GradientTransformation:
    """Exponential moving average of the PARAMETERS, as a chain element.

    The official SwinIR training (and most SR/diffusion recipes) evaluates
    an EMA of the weights, not the raw weights. TPU-first this is one more
    fused vector op per leaf inside the compiled step — not a separate
    host-side shadow copy like the common torch ``ModelEma`` wrappers —
    and because the EMA tree lives in the OPTIMIZER state it inherits the
    policy's sharding (ZeRO-1+ shards it like the moments) and rides every
    checkpoint for free.

    The chain element's own value tracks ``params + update`` as seen
    inside the chain — which is WRONG whenever the caller post-scales
    updates (``TrainStep``'s ``lr_factor``; the Stoke facade feeds the
    entire lr that way). Those consumers therefore overwrite it via
    :func:`refresh_params_ema` with the EMA of the TRUE new params; the
    chain value only stands for plain ``optax.apply_updates`` users,
    where it is exact. Extract with :func:`ema_params`.
    """

    def init(params):
        return ParamsEMAState(
            ema=jax.tree.map(lambda p: p.astype(jnp.float32), params),
            decay=decay,
        )

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("params_ema requires update(..., params=...)")
        new_ema = jax.tree.map(
            lambda e, p, u: decay * e + (1.0 - decay) * (
                p.astype(jnp.float32) + u.astype(jnp.float32)
            ),
            state.ema, params, updates,
        )
        return updates, ParamsEMAState(ema=new_ema, decay=decay)

    return optax.GradientTransformation(init, update)


def _is_ema_state(x) -> bool:
    return isinstance(x, ParamsEMAState)


def refresh_params_ema(prev_opt_state, new_opt_state, new_params):
    """Recompute every :class:`ParamsEMAState` from the TRUE new params.

    ``decay * prev_ema + (1-decay) * new_params`` — the correction applied
    by TrainStep and the facade after their post-chain ``lr_factor``
    scaling (see :func:`params_ema`). No-op when no EMA element exists.
    """

    def fix(new, old):
        if isinstance(new, ParamsEMAState):
            d = new.decay
            ema = jax.tree.map(
                lambda e, p: d * e + (1.0 - d) * p.astype(jnp.float32),
                old.ema, new_params,
            )
            return ParamsEMAState(ema=ema, decay=d)
        return new

    return jax.tree.map(
        fix, new_opt_state, prev_opt_state, is_leaf=_is_ema_state
    )


def has_ema(opt_state) -> bool:
    """Cheap presence probe: is an EMA being tracked in this state?
    (No extraction — :func:`ema_params` materializes the tree.)"""
    is_state = lambda x: isinstance(  # noqa: E731
        x, (ParamsEMAState, FusedAdamWState)
    )
    return any(
        isinstance(s, ParamsEMAState)
        or (isinstance(s, FusedAdamWState) and s.ema is not None)
        for s in jax.tree.leaves(opt_state, is_leaf=is_state)
        if is_state(s)
    )


def ema_params(opt_state, params=None):
    """Dig the EMA tree out of an optimizer state (tree OR fused path).

    Returns the EMA pytree cast to each param leaf's dtype when ``params``
    is given (eval-ready), else the raw f32 tree. None when no EMA is
    being tracked. The fused path's flat EMA requires ``params`` to
    unravel — passing none raises rather than silently returning None.
    """
    is_state = lambda x: isinstance(  # noqa: E731
        x, (ParamsEMAState, FusedAdamWState)
    )
    found = [
        s for s in jax.tree.leaves(opt_state, is_leaf=is_state)
        if is_state(s)
    ]
    for s in found:
        if isinstance(s, ParamsEMAState):
            ema = s.ema
            if params is not None:
                ema = jax.tree.map(
                    lambda e, p: e.astype(p.dtype), ema, params
                )
            return ema
        if s.ema is not None:  # FusedAdamWState with EMA enabled
            if params is None:
                raise ValueError(
                    "fused EMA is a flat buffer; pass params to unravel"
                )
            pflat, unravel = ravel_pytree(params)
            return unravel(s.ema[: pflat.size].astype(pflat.dtype))
    return None


class RecordedClipState(NamedTuple):
    """Pre-clip global norm + whether this step actually clipped.

    ``optax.clip_by_global_norm`` computes the global norm and throws it
    away (EmptyState); recording it here means the numerics probe and
    the step's ``grad_norm`` metric read it from the optimizer state
    instead of computing the norm a second time, and bench can report
    ``clip_fraction`` (the share of steps the clip actually fired)."""

    gnorm: jnp.ndarray  # f32 scalar, PRE-clip global norm
    clipped: jnp.ndarray  # bool scalar: the scale was < 1 this step


def clip_by_global_norm_recorded(
    max_norm: float,
) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` twin whose state records the
    pre-clip norm and a clipped flag (see :class:`RecordedClipState`).
    Numerically identical to optax's: scale = min(1, max_norm/gnorm)."""
    max_norm = float(max_norm)

    def init(params):
        del params
        return RecordedClipState(
            gnorm=jnp.zeros((), jnp.float32),
            clipped=jnp.zeros((), jnp.bool_),
        )

    def update(updates, state, params=None):
        del params, state
        with jax.named_scope("clip"):
            gnorm = optax.global_norm(updates)
            trigger = gnorm > max_norm
            scale = jnp.where(
                trigger, max_norm / jnp.maximum(gnorm, 1e-38), 1.0
            ).astype(jnp.float32)
            updates = jax.tree.map(
                lambda u: (u * scale).astype(u.dtype), updates
            )
        return updates, RecordedClipState(
            gnorm=gnorm.astype(jnp.float32), clipped=trigger
        )

    return optax.GradientTransformation(init, update)


def clip_stats(opt_state) -> RecordedClipState | None:
    """Find the :class:`RecordedClipState` inside a chain's state tuple
    (None when the chain has no recorded clip). Walks plain tuples only —
    optax chain states are (nested) tuples of NamedTuples."""
    if isinstance(opt_state, RecordedClipState):
        return opt_state
    if isinstance(opt_state, tuple):
        for child in opt_state:
            found = clip_stats(child)
            if found is not None:
                return found
    return None


def adamw(
    lr: float | optax.Schedule = 1e-3,
    betas: tuple = (0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.01,
    clip_grad_norm: float | None = None,
    clip_grad_value: float | None = None,
    ema_decay: float | None = None,
) -> optax.GradientTransformation:
    """AdamW with torch-parity argument names.

    ``clip_grad_norm`` fuses global-norm clipping into the chain (twin of
    ``ClipGradNormConfig(clip=0.1)``, `Stoke-DDP.py:253,164` — torch clips
    before the step; here it's one XLA-fused chain). ``clip_grad_value``
    is the elementwise clip twin (stoke ``ClipGradConfig``).
    ``ema_decay`` appends :func:`params_ema`.
    """
    chain = []
    if clip_grad_norm is not None:
        # recorded variant: the pre-clip global norm lands in the opt
        # state so TrainStep's grad_norm metric / the numerics probe
        # never compute it twice (see clip_by_global_norm_recorded)
        chain.append(clip_by_global_norm_recorded(clip_grad_norm))
    if clip_grad_value is not None:
        chain.append(optax.clip(clip_grad_value))
    chain.append(
        _scoped("adamw", optax.adamw(
            learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
            weight_decay=weight_decay,
        ))
    )
    if ema_decay is not None:
        chain.append(params_ema(ema_decay))
    return optax.chain(*chain)


def _scoped(name: str, tx: optax.GradientTransformation):
    """``tx`` with its update under ``jax.named_scope(name)``: the steps
    put the whole update under "optimizer", so AdamW's arithmetic reads
    ``optimizer/adamw`` in a compiled program's ``op_name`` (metadata
    only: no instruction changes)."""

    def update(updates, state, params=None):
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def sgd(
    lr: float | optax.Schedule = 1e-2,
    momentum: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    clip_grad_norm: float | None = None,
    clip_grad_value: float | None = None,
) -> optax.GradientTransformation:
    chain = []
    if clip_grad_norm is not None:
        chain.append(clip_by_global_norm_recorded(clip_grad_norm))
    if clip_grad_value is not None:
        chain.append(optax.clip(clip_grad_value))
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    chain.append(optax.sgd(lr, momentum=momentum or None, nesterov=nesterov))
    return optax.chain(*chain)


class FusedAdamWState(NamedTuple):
    count: jnp.ndarray  # i32 scalar
    mu: jnp.ndarray  # f32 [N] first moment, flat
    nu: jnp.ndarray  # f32 [N] second moment, flat
    ema: jnp.ndarray | None = None  # f32 [N] params EMA (ema_decay set)


class FusedAdamW:
    """Flat fused AdamW + clipping: the whole update as ~20 full-width ops.

    The per-leaf optax chain lowers to several XLA fusions per parameter
    leaf; on a 200+-leaf model (SwinIR-S: 222) that is >1000 tiny
    dispatches whose fixed per-op cost dominates the update. Here grads and params are ravelled once into a
    single vector, clip → Adam → weight decay → lr run as full-width
    vector ops, and the new params are unravelled once — the same
    economics as apex/DeepSpeed FusedAdam on CUDA, expressed as one XLA
    program region.

    Numerics match ``adamw(...)`` (same optax formulas, same eps
    placement, decay on every param like torch's AdamW default); only the
    reduction order of the global norm differs (single flat sum vs
    per-leaf partials).

    Layouts: replicated (DDP) params/grads, with optionally **sharded
    flat moments** (ZeRO-1/OSS): the [N] ``mu``/``nu`` vectors shard
    cleanly over the data axis (``Policy.opt_specs`` does it through the
    ordinary ``leaf_spec`` path), GSPMD computes the update shard-wise
    and all-gathers the flat update once — DeepSpeed's flat-partitioned
    optimizer expressed as shardings. Per-leaf grad/param sharding
    (ZeRO-2/3) has no flat story; ``TrainStep`` rejects those.

    ``update_wire_dtype`` narrows the all-gathered update vector (the
    fairscale OSS ``broadcast_fp16`` twin) — one cast on the flat vector
    instead of one per leaf.

    ``lr`` may be a float or a schedule ``f(count) -> lr`` evaluated
    inside the compiled step.
    """

    def __init__(
        self,
        lr: float | optax.Schedule = 1e-3,
        betas: tuple = (0.9, 0.999),
        eps: float = 1e-8,
        weight_decay: float = 0.01,
        clip_grad_norm: float | None = None,
        clip_grad_value: float | None = None,
        update_wire_dtype=None,
        ema_decay: float | None = None,
    ):
        self.lr = lr
        self.b1, self.b2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.clip_grad_norm = clip_grad_norm
        self.clip_grad_value = clip_grad_value
        self.update_wire_dtype = update_wire_dtype
        # params EMA as ONE more full-width vector op (exact: it sees the
        # post-lr_factor new params, unlike the tree path's chain element)
        self.ema_decay = ema_decay

    # flat buffers pad to a multiple of 1024 so a ZeRO-1 mesh axis (any
    # power of two <= 1024) divides them — DeepSpeed pads its flat
    # partitions for the same reason. Pad lanes carry zeros throughout:
    # zero grad -> zero moments -> zero update. TrainStep warns when a
    # sharded-opt policy still degenerates to replicated (e.g. an axis
    # that does not divide the padded length).
    _PAD = 1024

    def init(self, params) -> FusedAdamWState:
        n = sum(x.size for x in jax.tree.leaves(params))
        n_pad = -(-n // self._PAD) * self._PAD
        ema = None
        if self.ema_decay is not None:
            pflat = ravel_pytree(params)[0].astype(jnp.float32)
            ema = jnp.pad(pflat, (0, n_pad - pflat.size))
        return FusedAdamWState(
            count=jnp.zeros([], jnp.int32),
            mu=jnp.zeros((n_pad,), jnp.float32),
            nu=jnp.zeros((n_pad,), jnp.float32),
            ema=ema,
        )

    def apply(
        self,
        gflat: jnp.ndarray,
        opt_state: FusedAdamWState,
        params,
        lr_factor=1.0,
        gate=None,
    ):
        """One update on pre-ravelled f32 grads.

        Returns ``(new_params, new_opt_state, grad_norm)`` where
        ``grad_norm`` is the pre-clip global norm (the metric the tree
        path reports). ``gate`` (optional bool scalar) skips the whole
        update when False — the GradScaler overflow-skip, one ``where``
        on flat buffers instead of one per leaf.
        """
        with jax.named_scope("adamw"):
            return self._apply(gflat, opt_state, params, lr_factor, gate)

    def _apply(self, gflat, opt_state, params, lr_factor, gate):
        pflat, unravel = ravel_pytree(params)
        pad = opt_state.mu.size - pflat.size
        p32 = jnp.pad(pflat.astype(jnp.float32), (0, pad))
        g = jnp.pad(gflat, (0, pad))
        with jax.named_scope("clip"):
            gnorm = jnp.sqrt(jnp.sum(g * g))  # pre-clip, the metric's contract
            if self.clip_grad_norm is not None:
                c = jnp.float32(self.clip_grad_norm)
                # optax.clip_by_global_norm formula: rescale only above the cap
                g = g * jnp.where(gnorm < c, 1.0, c / gnorm)
            if self.clip_grad_value is not None:  # chain order: norm clip first
                v = self.clip_grad_value
                g = jnp.clip(g, -v, v)
        count = opt_state.count + 1
        mu = self.b1 * opt_state.mu + (1.0 - self.b1) * g
        nu = self.b2 * opt_state.nu + (1.0 - self.b2) * (g * g)
        t = count.astype(jnp.float32)
        mu_hat = mu / (1.0 - self.b1**t)
        nu_hat = nu / (1.0 - self.b2**t)
        # optax parity: schedules index from the PRE-increment count
        # (scale_by_schedule), bias correction from the incremented one
        lr_t = self.lr(opt_state.count) if callable(self.lr) else self.lr
        lr_t = jnp.asarray(lr_t, jnp.float32) * lr_factor
        upd = mu_hat / (jnp.sqrt(nu_hat) + self.eps)
        if self.weight_decay:
            upd = upd + self.weight_decay * p32
        step_vec = -lr_t * upd
        if self.update_wire_dtype is not None:
            # narrow the (possibly all-gathered) update fan-out wire; the
            # add below upcasts back — OSS broadcast_fp16 semantics
            step_vec = step_vec.astype(self.update_wire_dtype)
        new_p32 = p32 + step_vec.astype(jnp.float32)
        ema = opt_state.ema
        if self.ema_decay is not None:
            if ema is None:
                # state from a non-EMA-configured init: silently skipping
                # would run the whole training with a dead EMA feature
                raise ValueError(
                    "ema_decay is set but opt_state has no ema buffer — "
                    "re-init the state with this optimizer (or restore a "
                    "checkpoint written with ema_decay enabled)"
                )
            d = jnp.float32(self.ema_decay)
            ema = d * ema + (1.0 - d) * new_p32
        elif ema is not None:
            # mirror of the guard above: an EMA'd state driven by a
            # non-EMA optimizer would silently freeze the EMA while
            # ema_params() keeps serving it as live
            raise ValueError(
                "opt_state carries an ema buffer but this optimizer has "
                "ema_decay=None — construct FusedAdamW(ema_decay=...) to "
                "keep maintaining it (or re-init the state without EMA)"
            )
        if gate is not None:
            new_p32 = jnp.where(gate, new_p32, p32)
            mu = jnp.where(gate, mu, opt_state.mu)
            nu = jnp.where(gate, nu, opt_state.nu)
            count = jnp.where(gate, count, opt_state.count)
            if ema is not None:
                ema = jnp.where(gate, ema, opt_state.ema)
        return (
            unravel(new_p32[: pflat.size].astype(pflat.dtype)),
            FusedAdamWState(count=count, mu=mu, nu=nu, ema=ema),
            gnorm,
        )

    def ema_params(self, opt_state: FusedAdamWState, params):
        """Unravel the flat EMA into a params-shaped, params-dtyped tree
        (eval-ready). None when ``ema_decay`` was not set."""
        if opt_state.ema is None:
            return None
        return ema_params(opt_state, params)

    def apply_tree(
        self,
        grads,
        opt_state,
        params,
        lr_factor=1.0,
        scaler=None,
        scaler_state=None,
    ):
        """One update from a grads PYTREE, with optional GradScaler.

        The shared fused hot path of ``TrainStep`` and the Stoke facade:
        ravel once, flat unscale + finite gate (overflow skips the whole
        update), then :meth:`apply`. Returns ``(new_params,
        new_opt_state, new_scaler_state, grad_norm)`` — ``new_scaler_state``
        is ``scaler_state`` unchanged when no scaler is active.
        """
        gflat = ravel_pytree(grads)[0].astype(jnp.float32)
        new_scaler = scaler_state
        gate = None
        if scaler is not None and scaler_state is not None:
            gflat = gflat * (1.0 / scaler_state.scale.astype(jnp.float32))
            gate = jnp.all(jnp.isfinite(gflat))
            new_scaler = scaler.update(scaler_state, gate)
        new_params, new_opt, gnorm = self.apply(
            gflat, opt_state, params, lr_factor, gate=gate
        )
        return new_params, new_opt, new_scaler, gnorm


def fused_adamw_eligible(policy) -> bool:
    """Can :class:`FusedAdamW` replace the per-leaf chain under this
    parallelism policy?

    Replicated (DDP) and ZeRO-1/OSS layouts qualify (flat moments shard
    over dp); ZeRO-2/3 shard grads/params per leaf, which a flat vector
    cannot express. The single source of truth for the Stoke facade's
    auto-selection and the benchmark ladder.
    """
    return not (policy.shard_params or policy.shard_grads)


OPTIMIZERS = {"adamw": adamw, "sgd": sgd}


# -- schedules (pure functions of step) --------------------------------------


def onecycle(
    max_lr: float,
    total_steps: int,
    pct_start: float = 0.3,
    div_factor: float = 25.0,
    final_div_factor: float = 1e4,
) -> optax.Schedule:
    """OneCycleLR twin (cosine annealing strategy, torch defaults;
    `torch/optim/lr_scheduler.py:1584`): warm up from ``max_lr/div_factor``
    to ``max_lr`` over ``pct_start`` of training, then anneal to
    ``max_lr/final_div_factor``."""
    initial = max_lr / div_factor
    final = initial / final_div_factor
    warm = max(1, int(total_steps * pct_start))

    def schedule(step):
        step = jnp.minimum(step, total_steps)
        up = 0.5 * (1 + jnp.cos(math.pi * (1 - step / warm)))  # 0 -> 1
        lr_up = initial + (max_lr - initial) * up
        t = jnp.clip((step - warm) / max(1, total_steps - warm), 0.0, 1.0)
        down = 0.5 * (1 + jnp.cos(math.pi * t))  # 1 -> 0
        lr_down = final + (max_lr - final) * down
        return jnp.where(step < warm, lr_up, lr_down)

    return schedule


def cosine_with_warmup(
    max_lr: float, total_steps: int, warmup_steps: int = 0, final_lr: float = 0.0
) -> optax.Schedule:
    def schedule(step):
        warm = jnp.clip(step / max(1, warmup_steps), 0.0, 1.0)
        t = jnp.clip(
            (step - warmup_steps) / max(1, total_steps - warmup_steps), 0.0, 1.0
        )
        cos = final_lr + (max_lr - final_lr) * 0.5 * (1 + jnp.cos(math.pi * t))
        return jnp.where(step < warmup_steps, max_lr * warm, cos)

    return schedule


class OptimizerHandle:
    """What ``stoke_model.optimizer`` returns: a mutable lr cell.

    Torch schedulers mutate ``optimizer.param_groups[i]['lr']``; the TPU
    facade reads ``handle.lr`` on host each step and feeds it into the
    compiled update as a scalar argument — schedulers stay torch-shaped
    (`Stoke-DDP.py:300-306`) with zero retracing.
    """

    def __init__(self, base_lr: float):
        self.lr = float(base_lr)
        self.initial_lr = float(base_lr)

    def __repr__(self):
        return f"OptimizerHandle(lr={self.lr})"


class OneCycleLR:
    """Torch-call-parity wrapper (`Stoke-DDP.py:300`): per-batch ``.step()``
    writes the schedule into the optimizer handle."""

    def __init__(
        self,
        optimizer: OptimizerHandle,
        max_lr: float,
        total_steps: int | None = None,
        epochs: int | None = None,
        steps_per_epoch: int | None = None,
        pct_start: float = 0.3,
        div_factor: float = 25.0,
        final_div_factor: float = 1e4,
    ):
        if total_steps is None:
            if epochs is None or steps_per_epoch is None:
                raise ValueError("need total_steps or epochs+steps_per_epoch")
            total_steps = epochs * steps_per_epoch
        self.optimizer = optimizer
        # pure-python closed form: .step() runs per batch on the host
        # critical path, so no jnp dispatch / device sync here
        self._max_lr = max_lr
        self._initial = max_lr / div_factor
        self._final = self._initial / final_div_factor
        self._total = total_steps
        self._warm = max(1, int(total_steps * pct_start))
        self._t = 0
        # multiplier for composing with ReduceLROnPlateau (factor mode):
        # a bare torch pairing clobbers the plateau cut on the next batch —
        # route the cut through lr_scale instead so it persists
        self.lr_scale = 1.0
        optimizer.lr = self._lr_at(0)

    def _lr_at(self, step: int) -> float:
        step = min(step, self._total)
        if step < self._warm:
            up = 0.5 * (1 + math.cos(math.pi * (1 - step / self._warm)))
            lr = self._initial + (self._max_lr - self._initial) * up
        else:
            t = min(
                max((step - self._warm) / max(1, self._total - self._warm), 0.0), 1.0
            )
            down = 0.5 * (1 + math.cos(math.pi * t))
            lr = self._final + (self._max_lr - self._final) * down
        return lr * self.lr_scale

    def step(self) -> float:
        self._t += 1
        self.optimizer.lr = self._lr_at(self._t)
        return self.optimizer.lr

    def state_dict(self) -> dict:
        return {"t": self._t, "lr_scale": self.lr_scale}

    def load_state_dict(self, d: dict) -> None:
        self._t = int(d["t"])
        self.lr_scale = float(d.get("lr_scale", 1.0))
        self.optimizer.lr = self._lr_at(self._t)


class ReduceLROnPlateau:
    """Plateau scheduler, host-side (twin of
    `torch/optim/lr_scheduler.py:2285`; wired at `Stoke-DDP.py:301-306`).

    Two composition modes:
    - torch parity: pass an :class:`OptimizerHandle` — on trigger the
      handle's lr is multiplied by ``factor`` (floored at ``min_lr``);
    - factor mode (no handle): :meth:`step` returns a cumulative factor to
      feed the compiled step's ``lr_factor`` argument.
    """

    def __init__(
        self,
        optimizer: OptimizerHandle | None = None,
        mode: str = "min",
        factor: float = 0.1,
        patience: int = 10,
        threshold: float = 1e-4,
        cooldown: int = 0,
        min_lr: float = 0.0,
        min_factor: float = 0.0,
        verbose: bool = False,
    ):
        self.optimizer = optimizer
        self.mode = mode
        self.factor = factor
        self.patience = patience
        self.threshold = threshold
        self.cooldown = cooldown
        self.min_lr = min_lr
        self.min_factor = min_factor
        self.verbose = verbose
        self.current = 1.0
        self._best: float | None = None
        self._bad = 0
        self._cool = 0

    def _is_better(self, metric: float) -> bool:
        if self._best is None:
            return True
        if self.mode == "min":
            return metric < self._best * (1 - self.threshold)
        return metric > self._best * (1 + self.threshold)

    def step(self, metric: float) -> float:
        metric = float(metric)
        if self._is_better(metric):
            self._best = metric
            self._bad = 0
        elif self._cool > 0:
            self._cool -= 1
        else:
            self._bad += 1
            if self._bad > self.patience:
                self.current = max(self.current * self.factor, self.min_factor)
                if self.optimizer is not None:
                    self.optimizer.lr = max(
                        self.optimizer.lr * self.factor, self.min_lr
                    )
                    if self.verbose:
                        print(f"ReduceLROnPlateau: lr -> {self.optimizer.lr:.3e}")
                elif self.verbose:
                    print(f"ReduceLROnPlateau: lr_factor -> {self.current:.3e}")
                self._bad = 0
                self._cool = self.cooldown
        return self.current

    @property
    def factor_value(self) -> float:
        return self.current

    def state_dict(self) -> dict:
        return {
            "current": self.current, "best": self._best,
            "bad": self._bad, "cool": self._cool,
            # handle mode mutates the lr directly — persist it so resume
            # into a fresh OptimizerHandle keeps prior cuts
            "lr": None if self.optimizer is None else self.optimizer.lr,
        }

    def load_state_dict(self, d: dict) -> None:
        self.current = d["current"]
        self._best = d["best"]
        self._bad = d["bad"]
        self._cool = d["cool"]
        if self.optimizer is not None and d.get("lr") is not None:
            self.optimizer.lr = d["lr"]
