"""Quantized-gradient data parallelism: low-precision wire formats with
error feedback.

Extends the reference's wire-compression idea (fp16 OSS broadcast,
`/root/reference/Stoke-DDP.py:197-199`) to the gradient reduction itself,
the direction EQuARX takes inside XLA (PAPERS.md): on bandwidth-limited
links (DCN between slices, large pods) the grad all-reduce dominates step
time, and an 8-bit wire quarters it.

Wire formats are pluggable (:data:`WIRE_FORMATS`): per-tensor int8,
block-scaled int8, and block-scaled fp8 (e4m3 / e5m2). Each leaf rides
the wire as ``(payload, scales)`` where the payload is the narrow dtype
and scales are one fp32 per tensor (per-tensor) or per ``block`` elements
(block-scaled, ~1.5% overhead at the default block of 256, but robust to
outlier blocks that would otherwise flatten the rest of the tensor).

Transport (per gradient leaf, per step):

  1. add the previous step's quantization residual (error feedback —
     keeps the compression UNBIASED over time; plain 8-bit rounding
     stalls convergence),
  2. lay the leaf out as ``[W, L]`` rows — row ``i`` is the slice shard
     ``i`` will own after the reduction (the ZeRO-2 scatter chunk, or an
     even split of the flattened leaf for a full all-reduce), padded with
     zeros to the block boundary,
  3. encode rows locally and ``all_to_all`` payload + scales over the
     compressed axis: each shard receives every peer's encoded
     contribution *to its own chunk*, dequantizes with the sender's
     scales, and sums in f32. This is the reduce-scatter decomposition
     that provably keeps the narrow dtype on the wire — a plain
     ``psum(int8.astype(int32))`` compiles to an s32 all-reduce, 4x the
     bytes (`analyze.hlo_rules.wire_backoff` audits the compiled HLO for
     exactly this),
  4. ZeRO-2 stops here (reduce-to-owner, the quantized twin of
     ShardedDDP's hooks, `Fairscale-DDP.py:89`). The full all-reduce
     re-encodes the reduced chunk and ``all_gather``\\ s it — a second
     narrow hop whose requantization error is half an ulp of the *mean*
     gradient (accepted, not error-fed: it is not observable per-shard),
  5. the new residual ``x - decode(encode(x))`` is stored in the param's
     own dtype for the next step.

Leaves with fewer than ``min_wire_elems`` elements stay on the plain f32
``psum``/``psum_scatter`` (biases and norm scales are latency-bound, not
bandwidth-bound — quantizing them buys nothing and costs accuracy).

``CompressedGradStep`` is an opt-in TrainStep sibling: same
``loss_fn(params, batch, rng, model_state) -> (loss, aux)`` contract,
same optimizer update semantics, same ``lr_factor`` / ``compiled_text``
surface (so the facade and ``graftcheck`` drive it interchangeably).
Composition surface:

- **policy**: ``DDP`` (default — narrow all-reduce, replicated grads),
  ``ZeRO1`` (same wire; the sharded opt state rides create_train_state),
  or ``ZeRO2`` (narrow reduce-scatter: each shard receives only its owned
  grad slice, wire volume 1/n of the all-reduce on top of the 4x width
  win). ``ZeRO3`` is rejected: sharded params need per-block gather
  scheduling that belongs to ``TrainStep``.
- **hybrid ICI x DCN mesh** (``make_hybrid_mesh``: dp = slices over DCN,
  fsdp inside a slice): the fsdp reduction runs in full f32 on the fast
  ICI links (scattered to the owner under ZeRO-2), and ONLY the dp hop —
  the slow DCN crossing whose bandwidth problem this module cites — is
  quantized.

The grad collectives run inside ``shard_map`` (the implicit psum of the
jit path cannot be intercepted for quantization); ``check_vma=False``
keeps grads local per shard, and the reduction/axis-size IS the mean.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.collectives import shard_map
from ..runtime.mesh import batch_spec, data_axes
from .policy import DDP, Policy
from .spec import leaf_spec
from .state import TrainState

# Floor on the quantization scale. An all-zero leaf (or block) has
# amax 0; the scale must stay strictly positive so ``x / scale`` is
# finite and decodes back to exact zeros (pinned by
# test_quantize_all_zero_leaf_is_exact).
SCALE_EPS = 1e-12

# Leaves below this many elements ride the plain f32 collective: the
# payload is latency-bound there and block-scale overhead would eat the
# width win. Mirrors the spirit of analyze.hlo_rules.BACKOFF_MIN_LEAF_ELEMS.
MIN_WIRE_ELEMS = 2048

DEFAULT_BLOCK = 256


@dataclass(frozen=True)
class WireFormat:
    """One low-precision gradient wire encoding.

    ``payload_dtype`` is what the collectives carry; ``block`` is the
    number of elements sharing one fp32 scale (``None`` = one scale per
    tensor). ``encode``/``decode`` operate on ``[rows, L]`` layouts where
    ``L`` is a multiple of ``block`` — the transport owns padding.
    """

    name: str
    payload_dtype: Any
    block: int | None = None
    min_wire_elems: int = MIN_WIRE_ELEMS

    @property
    def qmax(self) -> float:
        """Largest representable magnitude of the payload dtype."""
        if jnp.issubdtype(jnp.dtype(self.payload_dtype), jnp.integer):
            return float(jnp.iinfo(self.payload_dtype).max)
        return float(jnp.finfo(self.payload_dtype).max)

    @property
    def bits(self) -> int:
        return jnp.dtype(self.payload_dtype).itemsize * 8

    def scale_count(self, row_elems: int) -> int:
        """fp32 scales per row of ``row_elems`` (block-padded) elements."""
        if self.block is None:
            return 1
        return max(1, row_elems // self.block)

    def encode(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``[W, L]`` f32 -> (payload ``[W, L]`` narrow, scales ``[W, S]``)."""
        w, l = x.shape
        x = x.astype(jnp.float32)
        if self.block is None:
            blocks = x.reshape(w, 1, l)
        else:
            blocks = x.reshape(w, l // self.block, self.block)
        amax = jnp.max(jnp.abs(blocks), axis=-1)
        scales = jnp.maximum(amax / self.qmax, SCALE_EPS)
        y = blocks / scales[..., None]
        if jnp.issubdtype(jnp.dtype(self.payload_dtype), jnp.integer):
            q = jnp.round(y)
        else:
            q = y
        q = jnp.clip(q, -self.qmax, self.qmax).astype(self.payload_dtype)
        return q.reshape(w, l), scales.astype(jnp.float32)

    def decode(self, payload: jax.Array, scales: jax.Array) -> jax.Array:
        """Inverse of :meth:`encode`, back to ``[W, L]`` f32."""
        w, l = payload.shape
        s = scales.shape[1]
        blocks = payload.astype(jnp.float32).reshape(w, s, l // s)
        return (blocks * scales[..., None]).reshape(w, l)


WIRE_FORMATS: dict[str, WireFormat] = {
    "int8": WireFormat("int8", jnp.int8, block=None),
    "int8_block": WireFormat("int8_block", jnp.int8, block=DEFAULT_BLOCK),
    "fp8_e4m3": WireFormat(
        "fp8_e4m3", jnp.float8_e4m3fn, block=DEFAULT_BLOCK
    ),
    "fp8_e5m2": WireFormat(
        "fp8_e5m2", jnp.float8_e5m2, block=DEFAULT_BLOCK
    ),
}

_OFF = ("", "off", "none", "fp32", "0", "false")


def wire_format(spec: "str | WireFormat | None") -> WireFormat | None:
    """Resolve a wire-format spelling to a :class:`WireFormat`.

    Accepts a registry name (``"int8_block"``), a ``name:block`` override
    (``"fp8_e4m3:128"``), an already-built :class:`WireFormat`, or an
    off-spelling (``None`` / ``"off"`` / ``"fp32"``) -> ``None``.
    """
    if spec is None or isinstance(spec, WireFormat):
        return spec
    s = str(spec).strip().lower()
    if s in _OFF:
        return None
    name, _, blk = s.partition(":")
    if name not in WIRE_FORMATS:
        raise ValueError(
            f"unknown wire format {name!r}: expected one of "
            f"{sorted(WIRE_FORMATS)} (optionally name:block), or 'off'"
        )
    fmt = WIRE_FORMATS[name]
    if blk:
        if fmt.block is None:
            raise ValueError(
                f"wire format {name!r} is per-tensor scaled; a block size "
                f"({blk!r}) does not apply"
            )
        b = int(blk)
        if b <= 0:
            raise ValueError(f"wire block size must be positive, got {b}")
        fmt = dataclasses.replace(fmt, block=b)
    return fmt


def _quantize(g, residual, axis_name):
    """(g + residual) -> (int8 payload, shared scale, new residual).

    Legacy per-tensor helper retained for the unbiasedness pin test: one
    scale per leaf, shared across the axis with ``pmax`` so int8 payloads
    sum exactly. The scale floor is :data:`SCALE_EPS` — an all-zero leaf
    quantizes to zeros with a zero residual instead of dividing by zero.
    """
    g = g.astype(jnp.float32) + residual
    local_max = jnp.max(jnp.abs(g))
    scale = lax.pmax(local_max, axis_name) / 127.0
    safe = jnp.maximum(scale, SCALE_EPS)
    q = jnp.clip(jnp.round(g / safe), -127, 127).astype(jnp.int8)
    new_residual = g - q.astype(jnp.float32) * safe
    return q, safe, new_residual


def _scatter_dim(spec: P, axis_name: str) -> int | None:
    """Index of the dimension ``spec`` shards over ``axis_name``, if any."""
    for i, s in enumerate(spec):
        names = s if isinstance(s, tuple) else (s,)
        if axis_name in names:
            return i
    return None


class CompressedGradStep:
    """Train step whose gradient reduction rides a narrow wire format.

    Opt-in sibling of ``TrainStep``. ``wire`` picks the encoding (any
    :func:`wire_format` spelling; default per-tensor ``"int8"``).
    Residual state for error feedback is PER-SHARD — stored with leading
    mesh axes ``[dp(, fsdp), ...]`` sharded over them in
    ``TrainState.model_state['grad_residual']`` (auto-initialized on
    first call); each shard's residual tracks its own local quantization
    error on exactly the tensor it quantizes (the full leaf, or its
    fsdp-owned slice on a hybrid mesh), in the param's own dtype.
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        policy: Policy | None = None,
        *,
        axis_name: str = "dp",
        donate: bool = False,
        wire: "str | WireFormat | None" = "int8",
        numerics=None,
    ):
        policy = policy or DDP()
        if policy.shard_params:
            raise ValueError(
                "CompressedGradStep composes with DDP/ZeRO1/ZeRO2 — ZeRO3's "
                "sharded params need TrainStep's gather scheduling"
            )
        axes = data_axes(mesh)
        if axis_name not in axes:
            raise ValueError(
                f"compressed axis {axis_name!r} is not a data axis of this "
                f"mesh (data axes: {axes}) — grads are quantized over the "
                "dp hop (the DCN crossing on a hybrid mesh)"
            )
        extra = [a for a in axes if a != axis_name]
        if extra not in ([], ["fsdp"]):
            raise ValueError(
                f"unsupported data-axis layout {axes}: expected pure "
                f"({axis_name!r},) or hybrid ({axis_name!r}, 'fsdp')"
            )
        fmt = wire_format(wire)
        if fmt is None:
            raise ValueError(
                "CompressedGradStep needs a wire format — for a plain f32 "
                "wire use TrainStep"
            )
        if not hasattr(tx, "update"):
            # optim.FusedAdamW ravels grads into one flat vector; the
            # quantized wire is per-leaf (block scales follow leaf shape)
            raise ValueError(
                f"{type(tx).__name__} has no optax-style .update — the "
                "quantized wire is a per-leaf path; use optim.adamw (the "
                "tree chain) with CompressedGradStep"
            )
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh
        self.policy = policy
        self.wire = fmt
        self.axis_name = axis_name
        self.ici_axis = extra[0] if extra else None
        # ZeRO grads shard over fsdp when present, else over dp itself;
        # that axis also decides where the quantized scatter lands
        self._zaxis = self.ici_axis or axis_name
        self._zsize = mesh.shape[self._zaxis]
        self._wsize = mesh.shape[axis_name]  # width of the quantized hop
        self.n_data_shards = 1
        for a in axes:
            self.n_data_shards *= mesh.shape[a]
        # numerics observability (observe/numerics.py): same contract as
        # TrainStep's probe, plus the error-feedback residual health only
        # this step can report (a growing residual norm means the
        # quantizer is diverging, not converging)
        from ..observe.numerics import NumericsProbe

        self.numerics = (
            NumericsProbe() if numerics is True else (numerics or None)
        )
        self._jitted = jax.jit(
            self._step, donate_argnums=(0,) if donate else ()
        )

    # -- per-leaf layout ---------------------------------------------------

    def _grad_spec(self, shape) -> P:
        """Where the reduced grad leaf lives: scattered to its owner under
        a grad-sharding policy, replicated otherwise."""
        if not self.policy.shard_grads:
            return P()
        return leaf_spec(
            shape, self._zaxis, self._zsize, self.policy.min_shard_size
        )

    def _quant_shape(self, shape) -> tuple:
        """Shape of the tensor each shard actually quantizes: on a hybrid
        mesh the fsdp scatter runs first (f32, ICI), so the dp-quantized
        tensor is the fsdp-owned slice."""
        if self.ici_axis is None:
            return tuple(shape)
        d = _scatter_dim(self._grad_spec(shape), self.ici_axis)
        if d is None:
            return tuple(shape)
        out = list(shape)
        out[d] //= self._zsize
        return tuple(out)

    def _on_wire(self, shape, spec: P) -> bool:
        """Whether this leaf's dp reduction is quantized (size floor, and
        the ZeRO-2 row layout needs the scatter dim to split W ways)."""
        n = 1
        for s in self._quant_shape(shape):
            n *= s
        if n < self.wire.min_wire_elems:
            return False
        d = None if self.ici_axis is not None else _scatter_dim(spec, self.axis_name)
        if d is not None and shape[d] % self._wsize:
            return False
        return True

    def wire_cost(self, params) -> dict:
        """Analytic bytes-on-wire accounting for the dp hop of one step.

        Returns ``{"wire_format", "wire_bytes", "fp32_bytes",
        "wire_fraction_quantized"}`` where ``wire_bytes`` counts payload +
        scale bytes each shard sends on the quantized hop(s) and
        ``fp32_bytes`` is what the same leaves would cost uncompressed.
        Floored leaves are charged at f32 width in both columns.
        """
        fmt = self.wire
        wire = fp32 = quantized = total = 0
        for p in jax.tree.leaves(params):
            spec = self._grad_spec(p.shape)
            shape = self._quant_shape(p.shape)
            n = 1
            for s in shape:
                n *= s
            total += n
            # bytes each shard moves for this leaf on the dp hop: a
            # reduce-scatter sends n, an all-reduce sends 2n (reduce +
            # gather hops)
            d = (
                None
                if self.ici_axis is not None
                else _scatter_dim(spec, self.axis_name)
            )
            hops = 1 if d is not None else 2
            fp32 += hops * n * 4
            if not self._on_wire(p.shape, spec):
                wire += hops * n * 4
                continue
            quantized += n
            blk = fmt.block or n
            nblocks = -(-n // blk)
            payload = nblocks * blk * jnp.dtype(fmt.payload_dtype).itemsize
            scales = fmt.scale_count(nblocks * blk) * 4
            wire += hops * (payload + scales)
        return {
            "wire_format": fmt.name
            + (f":{fmt.block}" if fmt.block not in (None, DEFAULT_BLOCK) else ""),
            "wire_bytes": int(wire),
            "fp32_bytes": int(fp32),
            "wire_fraction_quantized": (quantized / total) if total else 0.0,
        }

    def comm_cost(self, params) -> dict:
        """`CostSurface` view of :meth:`wire_cost` — the unified keys the
        planner consumes (`TrainStep.comm_cost` is the f32 twin). The
        collective is what the quantized hop replaces: reduce-scatter
        when the ZeRO-2 row layout scatters, all-reduce otherwise."""
        wc = self.wire_cost(params)
        size = int(self.mesh.shape.get(self.axis_name, 1))
        if self.ici_axis:
            size *= int(self.mesh.shape.get(self.ici_axis, 1))
        scattered = self.ici_axis is None and bool(self.policy.shard_grads)
        return {
            "collective": "reduce-scatter" if scattered else "all-reduce",
            "fp32_bytes": wc["fp32_bytes"],
            "wire_bytes": wc["wire_bytes"],
            "wire_format": wc["wire_format"],
            "wire_fraction_quantized": wc["wire_fraction_quantized"],
            "axis": self.axis_name,
            "axis_size": size,
        }

    def init_residuals(self, params):
        """Zero per-shard error-feedback residuals, leading mesh axes
        ``[dp(, fsdp)]`` sharded so each shard owns its own residual.
        Residual dtype follows the param dtype (a bf16 model should not
        pay f32 residual memory)."""
        from jax.sharding import NamedSharding

        lead_axes = (self.axis_name,) + (
            (self.ici_axis,) if self.ici_axis else ()
        )
        lead_shape = tuple(self.mesh.shape[a] for a in lead_axes)
        sh = NamedSharding(self.mesh, P(*lead_axes))
        return jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros(lead_shape + self._quant_shape(p.shape), p.dtype),
                sh,
            ),
            params,
        )

    # -- the step ----------------------------------------------------------

    def _reduce_one(self, g, r, spec: P):
        """One leaf: (ICI f32 reduce) -> error feedback -> narrow dp wire."""
        dp = self.axis_name
        fmt = self.wire
        shape = g.shape
        if self.ici_axis is not None:
            d = _scatter_dim(spec, self.ici_axis)
            if d is not None:  # scatter to owner on the fast links, f32
                g = lax.psum_scatter(
                    g, self.ici_axis, scatter_dimension=d, tiled=True
                )
            else:
                g = lax.psum(g, self.ici_axis)
        d = None if self.ici_axis is not None else _scatter_dim(spec, dp)
        if not self._on_wire(shape, spec):
            # floored: plain f32 collective, residual passes through
            if d is not None:
                total = lax.psum_scatter(
                    g, dp, scatter_dimension=d, tiled=True
                )
            else:
                total = lax.psum(g, dp)
            return total / self.n_data_shards, r

        w = self._wsize
        x = g.astype(jnp.float32) + r.astype(jnp.float32)
        blk = fmt.block or 1
        if d is not None:
            # ZeRO-2 rows: row i is exactly the dim-d chunk shard i owns
            moved = jnp.moveaxis(x, d, 0)
            rows = moved.reshape(w, -1)
            pad = (-rows.shape[1]) % blk
            rows = jnp.pad(rows, ((0, 0), (0, pad)))

            def restore(t):  # [w, L] -> local leaf shape
                t = t[:, : t.shape[1] - pad] if pad else t
                return jnp.moveaxis(t.reshape(moved.shape), 0, d)

        else:
            # all-reduce rows: even split of the flattened leaf
            flat = x.reshape(-1)
            pad = (-flat.size) % (w * blk)
            rows = jnp.pad(flat, (0, pad)).reshape(w, -1)

            def restore(t):  # [w, L] -> local leaf shape
                t = t.reshape(-1)
                t = t[: t.size - pad] if pad else t
                return t.reshape(x.shape)

        payload, scales = fmt.encode(rows)
        # error feedback: what encode lost locally feeds the next step
        new_r = restore(rows - fmt.decode(payload, scales)).astype(r.dtype)
        # reduce-scatter = all_to_all + local dequant-sum: shard i receives
        # every peer's encoded chunk i WITH the peer's scales — narrow
        # payload on the wire, exact f32 accumulation on chip
        p_recv = lax.all_to_all(payload, dp, split_axis=0, concat_axis=0)
        s_recv = lax.all_to_all(scales, dp, split_axis=0, concat_axis=0)
        chunk = jnp.sum(fmt.decode(p_recv, s_recv), axis=0)
        chunk = chunk / self.n_data_shards  # [L]: the mean of my chunk
        if d is not None:
            out = chunk[: chunk.size - pad] if pad else chunk
            owner = list(moved.shape)
            owner[0] //= w
            return jnp.moveaxis(out.reshape(owner), 0, d), new_r
        # full all-reduce: re-encode the reduced chunk and gather narrow
        p2, s2 = fmt.encode(chunk[None])
        gp = lax.all_gather(p2[0], dp, axis=0, tiled=True)
        gs = lax.all_gather(s2, dp, axis=0, tiled=True)
        mean = restore(fmt.decode(gp.reshape(w, -1), gs))
        return mean, new_r

    def _step(self, state: TrainState, batch, lr_factor):
        rng = jax.random.fold_in(state.rng, state.step)
        residuals = state.model_state["grad_residual"]
        extra_state = {
            k: v for k, v in state.model_state.items() if k != "grad_residual"
        }
        n_lead = 2 if self.ici_axis else 1
        # gspecs double as the out_specs: the reduced leaf each shard
        # HOLDS (its owned slice under ZeRO-2) reassembles through them
        gspecs = jax.tree.map(
            lambda p: self._grad_spec(p.shape), state.params
        )

        def local(params, residuals, batch):
            residuals = jax.tree.map(
                lambda r: r.reshape(r.shape[n_lead:]), residuals
            )

            def lfn(p):
                return self.loss_fn(p, batch, rng, extra_state)

            (loss, _aux), grads = jax.value_and_grad(lfn, has_aux=True)(params)
            # check_vma=False (below) disables vma tracking, so NO auto-psum
            # happens here: grads are purely local per-shard-mean grads.
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            flat_g, tree = jax.tree.flatten(grads)
            flat_r = jax.tree.leaves(residuals)
            flat_s = jax.tree.leaves(
                gspecs, is_leaf=lambda x: isinstance(x, P)
            )
            out = [
                self._reduce_one(g, r, s)
                for g, r, s in zip(flat_g, flat_r, flat_s)
            ]
            means = jax.tree.unflatten(tree, [m for m, _ in out])
            new_res = jax.tree.unflatten(tree, [r for _, r in out])
            for a in data_axes(self.mesh):
                loss = lax.pmean(loss, a)
            new_res = jax.tree.map(
                lambda r: r.reshape((1,) * n_lead + r.shape), new_res
            )
            return loss, means, new_res

        pspec = jax.tree.map(lambda _: P(), state.params)
        lead = (self.axis_name,) + ((self.ici_axis,) if self.ici_axis else ())
        rspec = jax.tree.map(lambda _: P(*lead), residuals)
        bspec = jax.tree.map(lambda _: batch_spec(self.mesh), batch)
        loss, grads, new_res = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(pspec, rspec, bspec),
            out_specs=(P(), gspecs, rspec),
            check_vma=False,  # reductions are replicated/owned by construction
        )(state.params, residuals, batch)

        if self.numerics is not None:
            grads = self.numerics.inject(grads, state.step)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        updates = jax.tree.map(lambda u: u * lr_factor, updates)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            model_state={**extra_state, "grad_residual": new_res},
        )
        metrics = {"loss": loss.astype(jnp.float32)}
        if self.numerics is not None:
            from ..optim import clip_stats

            rc = clip_stats(new_opt)
            metrics["numerics"] = self.numerics.aux(
                grads,
                params=state.params,
                updates=updates,
                model_state=extra_state,
                residuals=new_res,
                grad_norm=rc.gnorm if rc is not None else None,
            )
        return new_state, metrics

    def _with_residuals(self, state: TrainState) -> TrainState:
        if "grad_residual" in state.model_state:
            return state
        return state.replace(
            model_state={
                **state.model_state,
                "grad_residual": self.init_residuals(state.params),
            }
        )

    # -- AOT surface (mirrors TrainStep so analyze/facade drive either) ----

    def precompile(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compile the step without executing it (see TrainStep.precompile)."""
        state = self._with_residuals(state)
        with self.mesh:
            self._jitted.lower(state, batch, jnp.float32(lr_factor)).compile()

    def compiled_text(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiled HLO of this step, for `observe.hlo` collective audits
        (prove the wire actually carries the narrow dtype)."""
        state = self._with_residuals(state)
        with self.mesh:
            return (
                self._jitted.lower(state, batch, jnp.float32(lr_factor))
                .compile()
                .as_text()
            )

    def memory_analysis(self, state: TrainState, batch, lr_factor: float = 1.0):
        """Compiler memory accounting for this step (`observe.memory`)."""
        from ..observe.memory import compiled_memory_stats

        state = self._with_residuals(state)
        with self.mesh:
            compiled = self._jitted.lower(
                state, batch, jnp.float32(lr_factor)
            ).compile()
        return compiled_memory_stats(compiled)

    def __call__(self, state: TrainState, batch, lr_factor: float = 1.0):
        from ..observe import trace as telemetry

        state = self._with_residuals(state)
        with telemetry.dispatch_span(self, "CompressedGradStep"):
            return self._jitted(state, batch, jnp.float32(lr_factor))
