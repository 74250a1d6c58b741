"""Hierarchical bandwidth-aware gradient synchronization over hybrid meshes.

The reference's DDP matches its gradient sync to the interconnect —
bucketed all-reduce sized for the NIC (`torch/nn/parallel/distributed.py`,
``bucket_cap_mb``) — but our ``tree_all_reduce`` is topology-blind: one
flat ring per mesh axis even when :func:`make_hybrid_mesh` has placed the
dp axis across slow DCN links. On a multi-slice pod a flat dp ring moves
FULL gradient bytes across DCN from every device; the hierarchical form
("Joint Training on AMD and NVIDIA GPUs", PAPERS.md; the standard NCCL
two-level tree) moves 1/ici_size of it:

    reduce-scatter within-slice (ICI, fast)  — each device ends owning
                                               1/ici_size of the grads
    all-reduce across slices   (DCN, slow)   — on the owned shard only
    all-gather within-slice    (ICI, fast)   — reassemble the full mean

Three pieces live here:

- :class:`BucketPlan` / :func:`plan_buckets`: gradient bucketing sized
  from **measured** per-axis bytes/s. The bandwidth chain is
  ``observe.opcost.collective_bandwidth`` gauges (live, this process) →
  ``calibration.json``'s ``meta.axis_bandwidth`` (previous run) → an
  analytic constant, in that order; :func:`resolve_axis_bandwidth`
  reports which source won. Bucket target = bytes/s x overlap window, so
  one DCN collective hides under roughly one backward-compute slice —
  the DDP ``bucket_cap_mb`` idea with the cap derived, not hand-tuned.
- :class:`HierGradStep`: an f32 TrainStep sibling whose grad sync is the
  explicit two-level form inside ``shard_map`` (the jit path's implicit
  psum cannot be re-shaped into a hierarchy). DDP/ZeRO1 grads ride
  bucketed two-level all-reduces; ZeRO2 scatters to the fsdp owner on
  ICI first and only the owned shard crosses DCN. ZeRO3 is rejected
  (sharded params belong to TrainStep's gather scheduling). For a
  *quantized* DCN hop compose ``GRAFT_HIER`` with ``GRAFT_WIRE``: the
  facade then routes to :class:`~.compressed.CompressedGradStep`, whose
  hybrid-mesh path is already exactly this hierarchy with a narrow wire
  on the DCN crossing.
- :class:`SliceDegradeController` / :func:`exclude_slice`: the degraded
  mode. When the ``comm-bandwidth-degraded`` runtime rule fires (DCN
  bytes/s fell under ``GRAFT_BW_DEGRADED_FRAC`` x best) or the straggler
  monitor implicates one slice, the controller quarantines that slice's
  hosts through the membership store (``record_failure(attributed=True)``
  — the same exponential-backoff path the outage classifier uses) and
  :func:`exclude_slice` re-forms the hybrid mesh over the survivors, so
  the fleet degrades to N-1 slices instead of stalling the ring at the
  slowest link. ``time_to_degrade_s`` (signal -> decision) lands in this
  module's ``runtime_stats`` and the hier bench record.

HLO-level proof lives in ``observe.hlo.hierarchy_audit``: on the compiled
step, every DCN-crossing collective must carry <= 1/ici_size of the
gradient bytes a flat ring would. The ``dcn-flat-ring`` graftcheck rule
(analyze/hlo_rules.py) fails the build when it does not.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.collectives import hier_all_reduce, shard_map
from ..runtime.mesh import (
    _register_slice_axis,
    batch_spec,
    data_axes,
    slice_axis,
)
from .compressed import _scatter_dim
from .policy import DDP, Policy
from .spec import leaf_spec
from .state import TrainState

# Analytic bytes/s fallbacks, used ONLY when no measurement exists (no
# live opcost gauge, no calibration.json meta). ICI matches the planner's
# DEFAULT_AXIS_BW (analyze/planner.py); DCN is the conservative
# per-host figure the multi-slice scaling guides quote (~20 Gb/s).
ANALYTIC_ICI_BW = 1.8e10
ANALYTIC_DCN_BW = 2.5e9

# Overlap window the DCN bucket should hide under: roughly the backward
# time of one transformer block at the batch sizes this repo benches.
# Knob: GRAFT_HIER_OVERLAP_MS.
DEFAULT_OVERLAP_MS = 5.0

# Bucket clamp. Floor: below ~256 KiB the collective is latency-bound
# and more buckets only add dispatch overhead. Ceiling: one giant bucket
# serializes the whole sync after the last grad (DDP's bucket_cap_mb
# exists for the same reason).
MIN_BUCKET_BYTES = 1 << 18
MAX_BUCKET_BYTES = 1 << 26

# Degradation gauges, read by the fleet publisher and the hier bench the
# same no-import way all observe modules are (sys.modules lookup).
runtime_stats: dict = {
    "hier": None,        # {"dcn_axis", "ici_axis", "buckets", ...}
    "degraded": None,    # DegradeDecision.as_dict() once a slice is cut
    "time_to_degrade_s": None,
}


def resolve_axis_bandwidth(
    axis: str,
    *,
    calibration: str | None = None,
    analytic: float | None = None,
    is_dcn: bool = True,
) -> tuple[float, str]:
    """Bytes/s for one mesh axis, with provenance: ``(bw, source)``.

    Source precedence — measurement always beats constants:

    1. ``"measured"``: live ``observe.opcost.runtime_stats["axis_bandwidth"]``
       gauge (this process ran ``collective_bandwidth`` on a trace).
    2. ``"calibration"``: ``meta.axis_bandwidth[axis]`` of
       ``calibration.json`` (path argument or ``$GRAFT_CALIBRATION``) —
       a previous run's measurement.
    3. ``"analytic"``: the constant — ``analytic`` if given, else the
       DCN/ICI default picked by ``is_dcn``.
    """
    try:
        from ..observe import opcost

        bw = opcost.runtime_stats.get("axis_bandwidth", {}).get(axis)
        if bw:
            return float(bw), "measured"
    except Exception:  # noqa: BLE001 — gauges are optional inputs
        pass
    path = calibration or os.environ.get("GRAFT_CALIBRATION", "")
    if path:
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
            bw = (doc.get("meta") or {}).get("axis_bandwidth", {}).get(axis)
            if bw:
                return float(bw), "calibration"
        except (OSError, ValueError, AttributeError):
            pass
    if analytic is None:
        analytic = ANALYTIC_DCN_BW if is_dcn else ANALYTIC_ICI_BW
    return float(analytic), "analytic"


def _overlap_s(overlap_s: float | None) -> float:
    if overlap_s is not None:
        return float(overlap_s)
    raw = os.environ.get("GRAFT_HIER_OVERLAP_MS", "")
    try:
        ms = float(raw) if raw else DEFAULT_OVERLAP_MS
    except ValueError:
        ms = DEFAULT_OVERLAP_MS
    return ms / 1e3


def bucket_bytes_for(
    bytes_per_s: float,
    overlap_s: float,
    *,
    lo: int = MIN_BUCKET_BYTES,
    hi: int = MAX_BUCKET_BYTES,
) -> int:
    """Target bucket size: what the DCN hop can move inside the overlap
    window, clamped to [lo, hi]. Slow links get SMALL buckets (each one
    still hides under backward compute); fast links coalesce more."""
    return int(max(lo, min(hi, bytes_per_s * overlap_s)))


@dataclass(frozen=True)
class BucketPlan:
    """Which gradient leaves share one two-level collective.

    ``buckets`` holds tuples of leaf indices in ``jax.tree.flatten``
    order; a leaf in no bucket syncs outside the bucketed path (e.g.
    ZeRO-2 scattered leaves). ``bytes_per_s``/``source`` record the
    bandwidth the sizing used, so a plan is auditable after the fact.
    """

    target_bytes: int
    bytes_per_s: float
    source: str
    overlap_s: float
    buckets: tuple

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def describe(self) -> str:
        return (
            f"{self.n_buckets} bucket(s) @ target {self.target_bytes} B "
            f"(bw {self.bytes_per_s:.3g} B/s [{self.source}], "
            f"overlap {self.overlap_s * 1e3:g} ms)"
        )


def plan_buckets(
    params,
    *,
    bytes_per_s: float | None = None,
    source: str = "given",
    overlap_s: float | None = None,
    calibration: str | None = None,
    dcn_axis: str = "dp",
    include: "Callable[[int, Any], bool] | None" = None,
) -> BucketPlan:
    """Greedy coalescing of gradient leaves into DCN-sized buckets.

    Leaves fill buckets in flatten order (wire width f32) until the next
    leaf would overflow ``target_bytes``; a single leaf larger than the
    target gets its own bucket. ``include(i, leaf)`` filters leaves out
    of the bucketed path entirely (the step excludes scattered ZeRO-2
    leaves this way). With no explicit ``bytes_per_s`` the DCN bandwidth
    resolves through :func:`resolve_axis_bandwidth`.
    """
    if bytes_per_s is None:
        bytes_per_s, source = resolve_axis_bandwidth(
            dcn_axis, calibration=calibration, is_dcn=True
        )
    ov = _overlap_s(overlap_s)
    target = bucket_bytes_for(bytes_per_s, ov)
    leaves = jax.tree.leaves(params)
    buckets: list = []
    cur: list = []
    cur_bytes = 0
    for i, leaf in enumerate(leaves):
        if include is not None and not include(i, leaf):
            continue
        nbytes = int(np.prod(leaf.shape, dtype=np.int64)) * 4
        if cur and cur_bytes + nbytes > target:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))
    return BucketPlan(
        target_bytes=target,
        bytes_per_s=float(bytes_per_s),
        source=source,
        overlap_s=ov,
        buckets=tuple(buckets),
    )


class HierGradStep:
    """Train step whose grad sync is the explicit two-level hierarchy.

    Opt-in sibling of ``TrainStep`` (same ``loss_fn(params, batch, rng,
    model_state) -> (loss, aux)`` contract, same ``lr_factor`` /
    ``compiled_text`` AOT surface) for hybrid meshes built by
    ``make_hybrid_mesh``: the mesh MUST have a registered slice axis.
    Grad dtype stays f32 end to end — for a narrow DCN wire use
    ``CompressedGradStep`` (its hybrid path is the quantized twin of
    this hierarchy).
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        policy: Policy | None = None,
        *,
        donate: bool = False,
        bucket_plan: BucketPlan | None = None,
        overlap_s: float | None = None,
        calibration: str | None = None,
        numerics=None,
    ):
        policy = policy or DDP()
        if policy.shard_params:
            raise ValueError(
                "HierGradStep composes with DDP/ZeRO1/ZeRO2 — ZeRO3's "
                "sharded params need TrainStep's gather scheduling"
            )
        dcn = slice_axis(mesh)
        if dcn is None:
            raise ValueError(
                "HierGradStep needs a hybrid mesh with a slice axis "
                "(make_hybrid_mesh with dcn_dp > 1); on a single-slice "
                "mesh every link is ICI and TrainStep's flat sync is "
                "already optimal"
            )
        axes = data_axes(mesh)
        if dcn not in axes:
            raise ValueError(
                f"slice axis {dcn!r} is not a data axis of this mesh "
                f"(data axes: {axes})"
            )
        extra = [a for a in axes if a != dcn]
        if extra not in ([], ["fsdp"]):
            raise ValueError(
                f"unsupported data-axis layout {axes}: expected pure "
                f"({dcn!r},) or hybrid ({dcn!r}, 'fsdp')"
            )
        if not hasattr(tx, "update"):
            raise ValueError(
                f"{type(tx).__name__} has no optax-style .update — the "
                "bucketed hierarchy is a per-leaf path; use optim.adamw "
                "(the tree chain) with HierGradStep"
            )
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh
        self.policy = policy
        self.dcn_axis = dcn
        self.ici_axis = extra[0] if extra else None
        # ZeRO grads scatter over fsdp when present, else over dcn itself
        self._zaxis = self.ici_axis or dcn
        self._zsize = mesh.shape[self._zaxis]
        self.n_data_shards = 1
        for a in axes:
            self.n_data_shards *= mesh.shape[a]
        self._overlap_s = overlap_s
        self._calibration = calibration
        self.bucket_plan = bucket_plan
        from ..observe.numerics import NumericsProbe

        self.numerics = (
            NumericsProbe() if numerics is True else (numerics or None)
        )
        self._jitted = jax.jit(
            self._step, donate_argnums=(0,) if donate else ()
        )

    # -- leaf layout -------------------------------------------------------

    def _grad_spec(self, shape) -> P:
        """Where the reduced grad leaf lives: scattered to its ZeRO owner,
        replicated otherwise (replicated leaves ride the buckets)."""
        if not self.policy.shard_grads:
            return P()
        return leaf_spec(
            shape, self._zaxis, self._zsize, self.policy.min_shard_size
        )

    def _scattered(self, shape) -> bool:
        return _scatter_dim(self._grad_spec(shape), self._zaxis) is not None

    def _ensure_plan(self, params) -> BucketPlan:
        """Build (once) the bucket plan over the replicated leaves. The
        plan is trace-time static — it must exist before the first jit
        trace and never change after (a new plan means a new step)."""
        if self.bucket_plan is None:
            self.bucket_plan = plan_buckets(
                params,
                overlap_s=self._overlap_s,
                calibration=self._calibration,
                dcn_axis=self.dcn_axis,
                include=lambda i, leaf: not self._scattered(leaf.shape),
            )
            runtime_stats["hier"] = {
                "dcn_axis": self.dcn_axis,
                "ici_axis": self.ici_axis,
                "n_buckets": self.bucket_plan.n_buckets,
                "bucket_target_bytes": self.bucket_plan.target_bytes,
                "bw_bytes_per_s": self.bucket_plan.bytes_per_s,
                "bw_source": self.bucket_plan.source,
            }
        return self.bucket_plan

    # -- cost surface ------------------------------------------------------

    def dcn_cost(self, params) -> dict:
        """Analytic per-device bytes on the DCN hop for one step, against
        the flat-ring twin. Hop convention matches ``TrainStep.comm_cost``
        (reduce-scatter n, all-reduce 2n). The acceptance bar: with an
        ICI axis of size k, ``dcn_bytes`` must be ~1/k of
        ``dcn_bytes_flat_twin``; with no ICI axis the two coincide."""
        ici = int(self.mesh.shape[self.ici_axis]) if self.ici_axis else 1
        dcn = ici_b = flat = 0
        for p in jax.tree.leaves(params):
            n = int(np.prod(p.shape, dtype=np.int64))
            if self._scattered(p.shape):
                # scatter to owner (n on zaxis), then AR of the owned
                # 1/zsize shard across slices
                if self.ici_axis is not None:
                    ici_b += n * 4
                    dcn += 2 * (n // self._zsize) * 4
                else:
                    dcn += n * 4  # the dcn scatter IS the minimal hop
                flat += 2 * n * 4
                continue
            # bucketed two-level AR: RS(ici) n + AR(dcn) 2n/ici + AG(ici) n
            if self.ici_axis is not None:
                ici_b += 2 * n * 4
            dcn += 2 * -(-n // ici) * 4
            flat += 2 * n * 4
        return {
            "dcn_axis": self.dcn_axis,
            "ici_axis": self.ici_axis,
            "ici_size": ici,
            "dcn_bytes": int(dcn),
            "ici_bytes": int(ici_b),
            "dcn_bytes_flat_twin": int(flat),
        }

    def comm_cost(self, params) -> dict:
        """`CostSurface` view for the planner — f32 wire, so
        ``wire_bytes == fp32_bytes`` = two-level bytes (DCN + ICI hops)
        vs the flat twin's single-ring accounting in ``TrainStep``."""
        dc = self.dcn_cost(params)
        size = int(self.mesh.shape[self.dcn_axis])
        if self.ici_axis:
            size *= int(self.mesh.shape[self.ici_axis])
        total = dc["dcn_bytes"] + dc["ici_bytes"]
        return {
            "collective": "hier-all-reduce",
            "fp32_bytes": total,
            "wire_bytes": total,
            "wire_format": None,
            "axis": self.dcn_axis,
            "axis_size": size,
            "dcn_bytes": dc["dcn_bytes"],
            "dcn_bytes_flat_twin": dc["dcn_bytes_flat_twin"],
        }

    # -- the step ----------------------------------------------------------

    def _sync_sharded(self, g, spec: P):
        """ZeRO-2 leaf: f32 scatter to owner on ICI, slice-AR on DCN."""
        if self.ici_axis is not None:
            d = _scatter_dim(spec, self.ici_axis)
            g = lax.psum_scatter(
                g, self.ici_axis, scatter_dimension=d, tiled=True
            )
            g = lax.psum(g, self.dcn_axis)  # owned 1/fsdp shard only
        else:
            d = _scatter_dim(spec, self.dcn_axis)
            g = lax.psum_scatter(
                g, self.dcn_axis, scatter_dimension=d, tiled=True
            )
        return g / self.n_data_shards

    def _step(self, state: TrainState, batch, lr_factor):
        rng = jax.random.fold_in(state.rng, state.step)
        model_state = state.model_state
        plan = self.bucket_plan
        gspecs = jax.tree.map(
            lambda p: self._grad_spec(p.shape), state.params
        )

        def local(params, batch):
            def lfn(p):
                return self.loss_fn(p, batch, rng, model_state)

            (loss, _aux), grads = jax.value_and_grad(lfn, has_aux=True)(params)
            # check_vma=False below: grads are purely local here; every
            # cross-device byte is explicit in the collectives we emit.
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            flat_g, tree = jax.tree.flatten(grads)
            flat_s = jax.tree.leaves(
                gspecs, is_leaf=lambda x: isinstance(x, P)
            )
            out = list(flat_g)
            bucketed = set()
            for bucket in plan.buckets:
                bucketed.update(bucket)
                parts = [flat_g[i].reshape(-1) for i in bucket]
                cat = (
                    jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                )
                red = hier_all_reduce(
                    cat, ici_axis=self.ici_axis, dcn_axis=self.dcn_axis
                ) / self.n_data_shards
                off = 0
                for i in bucket:
                    n = flat_g[i].size
                    out[i] = red[off : off + n].reshape(flat_g[i].shape)
                    off += n
            for i, (g, s) in enumerate(zip(flat_g, flat_s)):
                if i in bucketed:
                    continue
                out[i] = self._sync_sharded(g, s)
            means = jax.tree.unflatten(tree, out)
            for a in data_axes(self.mesh):
                loss = lax.pmean(loss, a)
            return loss, means

        pspec = jax.tree.map(lambda _: P(), state.params)
        bspec = jax.tree.map(lambda _: batch_spec(self.mesh), batch)
        loss, grads = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(pspec, bspec),
            out_specs=(P(), gspecs),
            check_vma=False,  # reductions are replicated/owned by construction
        )(state.params, batch)

        if self.numerics is not None:
            grads = self.numerics.inject(grads, state.step)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        updates = jax.tree.map(lambda u: u * lr_factor, updates)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt
        )
        metrics = {"loss": loss.astype(jnp.float32)}
        if self.numerics is not None:
            from ..optim import clip_stats

            rc = clip_stats(new_opt)
            metrics["numerics"] = self.numerics.aux(
                grads,
                params=state.params,
                updates=updates,
                model_state=model_state,
                grad_norm=rc.gnorm if rc is not None else None,
            )
        return new_state, metrics

    # -- AOT surface (mirrors TrainStep so analyze/facade drive either) ----

    def precompile(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compile the step without executing it (see TrainStep.precompile)."""
        self._ensure_plan(state.params)
        with self.mesh:
            self._jitted.lower(state, batch, jnp.float32(lr_factor)).compile()

    def compiled_text(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiled HLO of this step, for ``observe.hlo.hierarchy_audit``
        (prove the DCN crossing carries the reduce-scattered payload)."""
        self._ensure_plan(state.params)
        with self.mesh:
            return (
                self._jitted.lower(state, batch, jnp.float32(lr_factor))
                .compile()
                .as_text()
            )

    def memory_analysis(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiler memory accounting for this step (`observe.memory`)."""
        from ..observe.memory import compiled_memory_stats

        self._ensure_plan(state.params)
        with self.mesh:
            compiled = self._jitted.lower(
                state, batch, jnp.float32(lr_factor)
            ).compile()
        return compiled_memory_stats(compiled)

    def __call__(self, state: TrainState, batch, lr_factor: float = 1.0):
        from ..observe import trace as telemetry
        from ..resilience.faults import fault_point

        self._ensure_plan(state.params)
        # the slow-DCN chaos site: a FaultPlan's "sleep" here models a
        # degraded inter-slice link stretching every sync
        fault_point("comm.dcn")
        with telemetry.dispatch_span(self, "HierGradStep"):
            return self._jitted(state, batch, jnp.float32(lr_factor))


# -- slow-slice degradation --------------------------------------------------


@dataclass(frozen=True)
class DegradeDecision:
    """The controller's verdict: cut this slice, keep these."""

    excluded_slice: int
    surviving_slices: tuple
    reason: str
    time_to_degrade_s: float
    quarantined_hosts: tuple = ()

    def as_dict(self) -> dict:
        return {
            "excluded_slice": self.excluded_slice,
            "surviving_slices": list(self.surviving_slices),
            "reason": self.reason,
            "time_to_degrade_s": round(self.time_to_degrade_s, 6),
            "quarantined_hosts": list(self.quarantined_hosts),
        }


class SliceDegradeController:
    """Decides when a slow slice leaves the hierarchy.

    Two independent signals feed it, matching the tentpole's triggers:

    - :meth:`note_axis_bandwidth` — the same measurement stream the
      ``comm-bandwidth-degraded`` runtime rule watches: DCN bytes/s
      under ``GRAFT_BW_DEGRADED_FRAC`` (default 0.5) x the best seen
      arms the controller. Bandwidth is an axis-level signal — it says
      the DCN ring is slow, not WHICH slice drags it.
    - :meth:`implicate` / :meth:`note_straggler` — names the slice (the
      straggler monitor's per-rank step times, or the outage
      classifier's host attribution, already localize blame).

    :meth:`decide` returns a :class:`DegradeDecision` once BOTH hold: a
    slice is implicated and either the bandwidth is degraded or the
    implication itself carries blame. The decision quarantines the
    slice's hosts through the membership store (attributed failures →
    exponential-backoff quarantine, the path grow-back already refuses)
    and stamps ``time_to_degrade_s`` = first signal → decision, the
    bound the bench record publishes. The mesh surgery itself is
    :func:`exclude_slice` — the controller never touches jax state, so
    it runs on the host thread next to the training loop.
    """

    def __init__(
        self,
        n_slices: int,
        *,
        dcn_axis: str = "dp",
        store=None,
        hosts_by_slice: "dict[int, list[str]] | None" = None,
        threshold_frac: float | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if n_slices < 2:
            raise ValueError(
                f"degradation needs >= 2 slices to choose from, got {n_slices}"
            )
        if threshold_frac is None:
            raw = os.environ.get("GRAFT_BW_DEGRADED_FRAC", "")
            try:
                threshold_frac = float(raw) if raw else 0.5
            except ValueError:
                threshold_frac = 0.5
        self.n_slices = int(n_slices)
        self.dcn_axis = dcn_axis
        self.store = store
        self.hosts_by_slice = hosts_by_slice or {}
        self.threshold_frac = float(threshold_frac)
        self._clock = clock
        self._best_bw = 0.0
        self._bw_degraded_since: float | None = None
        self._implicated: dict[int, tuple[str, float]] = {}
        self._decision: DegradeDecision | None = None

    # -- signals -----------------------------------------------------------

    def note_axis_bandwidth(self, bytes_per_s: float) -> bool:
        """Feed one DCN bandwidth sample; True once degradation is armed."""
        bw = float(bytes_per_s)
        self._best_bw = max(self._best_bw, bw)
        if bw < self.threshold_frac * self._best_bw:
            if self._bw_degraded_since is None:
                self._bw_degraded_since = self._clock()
        else:
            self._bw_degraded_since = None  # recovered; disarm
        return self._bw_degraded_since is not None

    def implicate(self, slice_id: int, reason: str = "implicated") -> None:
        """Blame one slice (outage classifier / straggler monitor)."""
        if not 0 <= slice_id < self.n_slices:
            raise ValueError(
                f"slice {slice_id} out of range [0, {self.n_slices})"
            )
        self._implicated.setdefault(slice_id, (reason, self._clock()))

    def note_straggler(self, rank: int, ranks_per_slice: int) -> None:
        """Map a straggling rank (observe.goodput) onto its slice."""
        self.implicate(
            rank // max(1, ranks_per_slice), f"straggler rank {rank}"
        )

    # -- verdict -----------------------------------------------------------

    def decide(self) -> DegradeDecision | None:
        """The degradation verdict, once; None while signals are partial."""
        if self._decision is not None:
            return self._decision
        if not self._implicated:
            return None
        slice_id, (reason, t_first) = min(
            self._implicated.items(), key=lambda kv: kv[1][1]
        )
        if self._bw_degraded_since is not None:
            t_first = min(t_first, self._bw_degraded_since)
            reason = f"comm-bandwidth-degraded + {reason}"
        quarantined: list[str] = []
        hosts = self.hosts_by_slice.get(slice_id, [])
        if self.store is not None:
            for hid in hosts:
                try:
                    self.store.record_failure(
                        hid,
                        attributed=True,
                        detail=f"slow slice {slice_id}: {reason}",
                    )
                    quarantined.append(hid)
                except Exception:  # noqa: BLE001 — quarantine is advisory
                    pass
        survivors = tuple(
            s for s in range(self.n_slices) if s != slice_id
        )
        self._decision = DegradeDecision(
            excluded_slice=slice_id,
            surviving_slices=survivors,
            reason=reason,
            time_to_degrade_s=max(0.0, self._clock() - t_first),
            quarantined_hosts=tuple(quarantined),
        )
        runtime_stats["degraded"] = self._decision.as_dict()
        runtime_stats["time_to_degrade_s"] = (
            self._decision.time_to_degrade_s
        )
        return self._decision


def exclude_slice(mesh: Mesh, excluded: int) -> Mesh:
    """Re-form a hybrid mesh over the surviving slices.

    Drops slice ``excluded`` along the mesh's registered slice axis and
    returns a mesh of the same axis names over the remaining devices —
    the hierarchy then re-forms over N-1 slices instead of stalling the
    N-slice ring at the slow link. With two slices the survivor mesh
    keeps the (now size-1) DCN axis but loses its slice-axis
    registration: every remaining link is ICI and ``HierGradStep`` will
    correctly refuse it in favor of the flat sync.
    """
    dcn = slice_axis(mesh)
    if dcn is None:
        raise ValueError(
            "mesh has no registered slice axis — nothing to exclude "
            "(build it with make_hybrid_mesh, dcn_dp > 1)"
        )
    names = tuple(mesh.axis_names)
    arr = np.asarray(mesh.devices)
    ax = names.index(dcn)
    n = arr.shape[ax]
    if not 0 <= excluded < n:
        raise ValueError(f"slice {excluded} out of range [0, {n})")
    if n <= 1:
        raise ValueError("cannot exclude the only slice")
    keep = [s for s in range(n) if s != excluded]
    sub = np.take(arr, keep, axis=ax)
    survivor = Mesh(sub, names)
    if len(keep) > 1:
        _register_slice_axis(survivor, dcn)
    return survivor
