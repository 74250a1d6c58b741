"""``BENCHMARK.json`` against the rules of the contract that can be checked
here, so that a file the driver would refuse fails before any chip call."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(one_line(w) for w in manifest["command"])
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    # a full check with the full 24 cells has to fit into 43,200 s
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 2 <= cells <= 24
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, cells // 4)


def test_configs_and_cells(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert len(configs) == len(manifest["configs"])
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["why"]) and one_line(c["source"])
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        pairs.add((w["config"], w["traffic"]))
        path = os.path.join(ROOT, "chipbench", "workloads", w["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
    assert len(pairs) == len(manifest["workloads"])
    assert {w["config"] for w in manifest["workloads"]} == set(configs)


def test_metrics(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))

    def where(metric):
        assert set(metric.get("workloads", cells)) <= set(cells)
        return set(metric.get("workloads", cells))

    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in SOURCES and one_line(m["layer"])
        # reported only where the metric it moves is
        assert where(m) <= where(end[m["moves"]]), m["name"]
        reader = m["name"].split(".", 1)[0] + ".py"
        assert os.path.isfile(
            os.path.join(ROOT, "chipbench", "layer_metrics", reader)
        )
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        assert cell in where(end["setup_s"])
        assert any(cell in where(m) for n, m in end.items() if n != "setup_s")
        assert any(cell in where(m) for m in manifest["per_layer"])
