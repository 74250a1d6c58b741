"""Metrics sinks, wandb shim."""

import json
import os

import numpy as np

from pytorch_distributedtraining_tpu.observe import (
    JSONLSink,
    make_sink,
    wandb,
)


def test_jsonl_sink_roundtrip(tmp_path):
    p = tmp_path / "m.jsonl"
    sink = JSONLSink(str(p))
    sink.log({"loss": np.float32(0.5), "vec": np.arange(2)}, step=3)
    sink.log({"loss": 0.25})
    sink.finish()
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert lines[0]["loss"] == 0.5 and lines[0]["_step"] == 3
    assert lines[0]["vec"] == [0, 1]
    assert "_step" not in lines[1]


def test_make_sink_falls_back_offline(tmp_path, monkeypatch):
    monkeypatch.setenv("WANDB_MODE", "disabled")
    sink = make_sink("proj", path=str(tmp_path / "x.jsonl"))
    assert isinstance(sink, JSONLSink)


def test_wandb_shim_reference_pattern(tmp_path, monkeypatch):
    # the offline fallback lands under $GRAFT_RUN_DIR (never the cwd —
    # the old cwd default committed a metrics.jsonl into the repo root)
    monkeypatch.setenv("GRAFT_RUN_DIR", str(tmp_path))
    monkeypatch.setenv("WANDB_MODE", "disabled")
    wandb.finish()
    assert wandb.login()
    wandb.init(project="p", config={"epochs": 2}, reinit=True)
    wandb.init()  # the reference's init-on-every-log bug: must be a no-op
    wandb.log({"train_loss": 1.0})
    assert wandb.config.epochs == 2
    wandb.finish()
    assert os.path.exists(tmp_path / "metrics.jsonl")

