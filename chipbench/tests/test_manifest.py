"""BENCHMARK.json <-> the files it names, and the contract's shape rules
that can be checked without a run."""

import os
import re

import pytest

from chipbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.load()


def test_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["paths"] == ["chipbench"]
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert all(not w.startswith("/") and ".." not in w for w in M["command"])


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    assert cfg["file"].startswith("chipbench/")
    body = manifest._read_json(os.path.join(manifest.ROOT, cfg["file"]))
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    for key in ("guarantees", "assumed", "entry", "mode", "validators",
                "adversarial", "rehearse", "expect"):
        assert key in body, key
    assert any(w["config"] == cfg["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    full = manifest.cell(M, cell["name"])
    assert full["traffic_file"]["generator"] == "closed_loop"
    assert full["config_file"]["name"] == cell["config"]
    assert manifest.end_to_end(M, cell["name"]) and manifest.per_layer(M, cell["name"])


def test_pairs_and_names_unique():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert metric["moves"] in {m["name"] for m in M["end_to_end"]}
    assert callable(manifest.reader(metric["name"]))
    with open(os.path.join(manifest.ROOT, "PERF.md")) as fh:
        assert f"| {metric['layer']} |" in fh.read(), "layer not in PERF.md section 3"


def test_setup_s_is_there():
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in M["end_to_end"])


def test_only_named_characters_in_paths():
    for base, _dirs, files in os.walk(manifest.HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
