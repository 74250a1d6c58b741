"""graftcheck CLI: AOT-lower a step on CPU, print the findings report.

Runs entirely on host CPU — ``compiled_text`` goes through
``jit.lower().compile()`` without executing a step, so a dp2,fsdp2 TPU
layout can be vetted on a laptop before burning a pod slot::

    python -m pytorch_distributedtraining_tpu.analyze \
        --model swinir --mesh dp2,fsdp2 --policy zero2

    python -m pytorch_distributedtraining_tpu.analyze --pp 4 \
        --pp-schedule 1f1b             # MLP PipelineStep wire-plan check

    python -m pytorch_distributedtraining_tpu.analyze \
        --fixture donation-conflict    # seeded-violation self-demo

    python -m pytorch_distributedtraining_tpu.analyze --source
        # whole-repo source plane: SPMD-hazard AST lint + knob registry

Exit codes: 0 clean (warn/info allowed), 1 error-severity findings,
2 usage/environment problems.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

_MESH_TOKEN = re.compile(r"^(dp|fsdp|tp|sp|pp)(\d+)$")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m pytorch_distributedtraining_tpu.analyze",
        description=(
            "graftcheck: trace-time + HLO static analysis of a train "
            "step, AOT on CPU"
        ),
    )
    p.add_argument(
        "--model", default="mlp", choices=("mlp", "espcn", "swinir"),
        help="model whose train step to analyze (default mlp)",
    )
    p.add_argument(
        "--mesh", default="dp1",
        help="mesh axes as NAME<int> tokens, e.g. dp2,fsdp2 (default dp1)",
    )
    p.add_argument(
        "--policy", default="ddp",
        choices=("ddp", "zero1", "zero2", "zero3"),
        help="sharding policy (default ddp)",
    )
    p.add_argument(
        "--remat", default=None,
        help="remat policy: full|dots|names|offload (default off)",
    )
    p.add_argument(
        "--wire", default=None,
        help="analyze a CompressedGradStep carrying gradients in this "
        "wire format (int8 | int8_block | fp8_e4m3 | fp8_e5m2, with an "
        "optional :BLOCK suffix); the wire-backoff rule then audits "
        "bytes-on-wire in the compiled HLO",
    )
    p.add_argument(
        "--pp", type=int, default=0,
        help="pipeline stages: analyze an MLP PipelineStep on a pp mesh",
    )
    p.add_argument(
        "--pp-schedule", default="1f1b",
        choices=("gpipe", "1f1b", "interleaved"),
        help="pipeline schedule for --pp (default 1f1b)",
    )
    p.add_argument(
        "--pp-micro", type=int, default=8,
        help="microbatches for --pp (default 8)",
    )
    p.add_argument(
        "--batch", type=int, default=16, help="global batch size",
    )
    p.add_argument(
        "--donate", action=argparse.BooleanOptionalAction, default=False,
        help="build the step with state donation (default off: the CLI "
        "only lowers, and ZeRO CPU lowering aliases partially)",
    )
    p.add_argument(
        "--fixture", default=None,
        help="analyze a named seeded-violation fixture instead of a "
        "model (see --list-fixtures)",
    )
    p.add_argument(
        "--source", action="store_true",
        help="run the source plane over the whole repo (AST lint: "
        "host-divergence, knob registry, fault-site drift, contracts) "
        "instead of analyzing a step; with --fixture, run a src-* "
        "seeded snippet",
    )
    p.add_argument(
        "--write-knobs", action="store_true",
        help="with --source: regenerate docs/KNOBS.md from the knob "
        "registry before reporting",
    )
    p.add_argument(
        "--ignore", default=None,
        help="comma-separated rule names to suppress "
        "(default: $GRAFT_ANALYZE_IGNORE)",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    p.add_argument(
        "--list-fixtures", action="store_true",
        help="print the seeded-violation fixture names and exit",
    )
    return p


def _parse_mesh(spec: str, pp: int) -> dict:
    kw: dict = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        m = _MESH_TOKEN.match(tok)
        if m is None:
            raise SystemExit(
                f"error: bad mesh token {tok!r}; expected e.g. dp2,fsdp2"
            )
        kw[m.group(1)] = int(m.group(2))
    if pp:
        kw["pp"] = pp
    return kw


def _ensure_devices(n: int) -> None:
    """Ask the CPU backend for >= n devices; must run before jax init."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _build_model_step(args, mesh_kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import optim
    from ..losses import mse_loss
    from ..parallel import (
        DDP,
        CompressedGradStep,
        TrainStep,
        ZeRO1,
        ZeRO2,
        ZeRO3,
        create_train_state,
    )
    from ..runtime.mesh import MeshSpec, make_mesh

    policy_kw = {"min_shard_size": 1}
    if args.remat:
        policy_kw["remat"] = args.remat
    policy = {
        "ddp": DDP, "zero1": ZeRO1, "zero2": ZeRO2, "zero3": ZeRO3,
    }[args.policy](**policy_kw)
    spec = MeshSpec(**mesh_kw)
    # a host with MORE devices than the mesh (e.g. under the test
    # harness's 8-way CPU env) analyzes the same layout on a subset
    mesh = make_mesh(spec, devices=jax.devices()[: spec.size])

    rng = np.random.default_rng(0)
    if args.model == "mlp":
        from .fixtures import TinyMLP

        model = TinyMLP()
        x = rng.normal(size=(args.batch, 8)).astype(np.float32)
        y = rng.normal(size=(args.batch, 1)).astype(np.float32)
        init_x = jnp.zeros((1, 8))

        def apply(params, xx):
            return model.apply({"params": params}, xx)
    else:
        if args.model == "espcn":
            from ..models import Net

            model = Net(upscale_factor=2)
        else:
            from ..models import SwinIR

            # tiny SwinIR twin: same code paths, CPU-affordable compile
            model = SwinIR(depths=[2], embed_dim=12, num_heads=[2])
        hr = rng.random((args.batch, 16, 16, 3)).astype(np.float32)
        x = hr.reshape(args.batch, 8, 2, 8, 2, 3).mean(axis=(2, 4))
        y = hr
        init_x = jnp.zeros((1, 8, 8, 3))

        def apply(params, xx):
            return model.apply({"params": params}, xx)

    def loss_fn(params, batch, rng_, ms):
        lr_img, hr_img = batch
        return mse_loss(apply(params, lr_img), hr_img), {}

    tx = optim.adamw(lr=1e-3)
    state, sh = create_train_state(
        init_fn=lambda r: (model.init(r, init_x)["params"], {}),
        tx=tx, mesh=mesh, policy=policy,
    )
    if args.wire:
        if args.policy == "zero3":
            raise SystemExit(
                "error: --wire composes with ddp/zero1/zero2 only "
                "(ZeRO-3's sharded params need TrainStep)"
            )
        step = CompressedGradStep(
            loss_fn, tx, mesh, policy, donate=args.donate, wire=args.wire
        )
    else:
        step = TrainStep(
            loss_fn, tx, mesh, policy, state_shardings=sh, donate=args.donate
        )
    return step, state, (x, y)


def _build_pipeline_step(args, mesh_kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import optim
    from ..parallel import (
        PipelineStep,
        Policy,
        create_train_state,
        pipeline_state_shardings,
    )
    from ..runtime.mesh import MeshSpec, make_mesh

    spec = MeshSpec(**mesh_kw)
    mesh = make_mesh(spec, devices=jax.devices()[: spec.size])
    d, layers, micro = 8, max(args.pp, 1), args.pp_micro

    def init_fn(r):
        k1, k2, k3, k4 = jax.random.split(r, 4)
        return {
            "h": {
                "w": jax.random.normal(k1, (layers, d, d)) * 0.3,
                "b": jax.random.normal(k2, (layers, d)) * 0.1,
            },
            "emb": jax.random.normal(k3, (d, d)) * 0.3,
            "out": jax.random.normal(k4, (d, 1)) * 0.3,
        }, {}

    def block_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def embed_fn(other, mb, rng_):
        return mb["x"] @ other["emb"]

    def head_fn(other, y, mb, rng_):
        return jnp.mean((y @ other["out"] - mb["y"]) ** 2)

    tx = optim.adamw(lr=1e-3)
    policy = Policy()
    state, sh = create_train_state(
        init_fn=init_fn, tx=tx, mesh=mesh, policy=policy
    )
    sh = pipeline_state_shardings(sh, state, mesh, "h")
    state = jax.device_put(state, sh)
    step = PipelineStep(
        block_fn, tx, mesh, policy,
        n_micro=micro, schedule=args.pp_schedule, stages_key="h",
        embed_fn=embed_fn, head_fn=head_fn, state_shardings=sh,
        donate=args.donate,
    )
    rng = np.random.default_rng(0)
    batch = {
        "x": rng.normal(size=(args.batch, d)).astype(np.float32),
        "y": rng.normal(size=(args.batch, 1)).astype(np.float32),
    }
    return step, state, batch


def _main_source(args, ignore) -> int:
    """The --source path: whole-repo AST lint, no step, no mesh.

    Exit codes match the step path: 0 clean, 1 error findings, 2 on a
    fixture expectation miss or usage problem.
    """
    from .source_rules import source_report

    if args.write_knobs:
        from .knobs import write_knobs_md

        print(f"wrote {write_knobs_md()}")

    if args.fixture:
        from .fixtures import build_source_fixture

        try:
            facts, extras, expected = build_source_fixture(args.fixture)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        report = source_report(facts=facts, extras=extras, ignore=ignore)
        print(f"analyzing source fixture {args.fixture!r}")
        print(report.render())
        if expected is not None:
            rule_name, sev = expected
            hit = [
                f for f in report.by_rule(rule_name) if f.severity is sev
            ]
            print(
                f"fixture expectation [{sev}] {rule_name}: "
                + ("hit" if hit else "MISSED")
            )
            if not hit:
                return 2
        return report.exit_code

    report = source_report(ignore=ignore)
    print("analyzing repo source (plane: source)")
    print(report.render())
    # one JSON summary line, for tooling that reads JSON lines only
    import json

    print(json.dumps({
        "stage": "source",
        "rules": len(report.rules_run),
        "ok": report.ok,
        **report.counts(),
    }))
    return report.exit_code


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_rules:
        from .runner import rule_catalog

        for name, plane, doc in sorted(rule_catalog()):
            print(f"{name:24s} [{plane:7s}] {doc}")
        return 0
    if args.list_fixtures:
        from .fixtures import FIXTURES, SOURCE_FIXTURES

        for name in sorted(FIXTURES) + sorted(SOURCE_FIXTURES):
            print(name)
        return 0

    ignore_cli = (
        frozenset(
            p.strip() for p in args.ignore.split(",") if p.strip()
        )
        if args.ignore is not None
        else None
    )

    # src-* fixtures are source-plane snippets; --fixture src-… implies
    # --source so the two fixture families share one flag
    if args.source or (args.fixture or "").startswith("src-"):
        return _main_source(args, ignore_cli)

    mesh_kw = _parse_mesh(args.mesh, args.pp)
    n_devices = 1
    for v in mesh_kw.values():
        n_devices *= v
    _ensure_devices(max(n_devices, 1))

    from ..runtime import force_platform

    force_platform("cpu")  # analysis is always an AOT CPU pass
    import jax

    if len(jax.devices()) < n_devices:
        print(
            f"error: mesh {args.mesh!r} needs {n_devices} devices but the "
            f"CPU backend initialized with {len(jax.devices())} (jax was "
            "already imported before the CLI could request more)",
            file=sys.stderr,
        )
        return 2

    ignore = ignore_cli

    from .runner import analyze_step

    if args.fixture:
        from .fixtures import build_fixture

        step, state, batch, expected = build_fixture(args.fixture)
        label = f"fixture {args.fixture!r}"
    elif args.pp:
        step, state, batch = _build_pipeline_step(args, mesh_kw)
        label = (
            f"PipelineStep(mlp) pp{args.pp}/{args.pp_schedule} "
            f"mesh={mesh_kw}"
        )
        expected = None
    else:
        step, state, batch = _build_model_step(args, mesh_kw)
        label = f"{args.model} mesh={mesh_kw} policy={args.policy}"
        if args.wire:
            label += f" wire={args.wire}"
        expected = None

    report = analyze_step(step, state, batch, ignore=ignore)
    print(f"analyzing {label}")
    print(report.render())
    if expected is not None:
        rule_name, sev = expected
        hit = [f for f in report.by_rule(rule_name) if f.severity is sev]
        print(
            f"fixture expectation [{sev}] {rule_name}: "
            + ("hit" if hit else "MISSED")
        )
        if not hit:
            return 2
    return report.exit_code


if __name__ == "__main__":
    sys.exit(main())
