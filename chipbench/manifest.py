"""The benchmark's data: `BENCHMARK.json` and the files it names.

A cell is found by name in the manifest's `workloads`; its configuration
by name in `configs` (-> the configuration's `file`); its traffic mix at
`chipbench/traffic/<traffic>.json`; the configuration's entry point at
`chipbench/entries/<entry>.py`; a per-layer metric's reader at
`chipbench/metrics/<metric>.py`.  Adding a cell, a configuration, a mix,
an entry point or a per-layer metric is adding files and manifest
entries; no file that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class ManifestError(Exception):
    pass


def load() -> dict:
    with open(MANIFEST) as fh:
        return json.load(fh)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json "
                        f"(have: {[e['name'] for e in entries]})")


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell(manifest: dict, name: str) -> dict:
    """The cell with its configuration's file and its traffic mix read in."""
    entry = dict(_by_name(manifest["workloads"], name, "workload"))
    cfg = _by_name(manifest["configs"], entry["config"], "configuration")
    entry["config_file"] = _read_json(os.path.join(ROOT, cfg["file"]))
    entry["traffic_file"] = _read_json(
        os.path.join(HERE, "traffic", entry["traffic"] + ".json"))
    return entry


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(manifest: dict, cell_name: str) -> list[dict]:
    return [m for m in manifest["end_to_end"] if _applies(m, cell_name)]


def per_layer(manifest: dict, cell_name: str) -> list[dict]:
    return [m for m in manifest["per_layer"] if _applies(m, cell_name)]


def _module(kind: str, name: str, what: str):
    """The module of chipbench/<kind>/<name>.py, loaded by its path."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"{what} {name!r} has no file at "
                            f"{os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}." + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(metric_name: str):
    """The `read(obs)` function of chipbench/metrics/<metric_name>.py."""
    return _module("metrics", metric_name, "per-layer metric").read


ENTRY_FUNCTIONS = ("build", "bind", "expected", "implied", "path")


def entry(entry_name: str):
    """The module chipbench/entries/<entry_name>.py: a configuration's
    `entry`, with the five functions an entry point brings."""
    module = _module("entries", entry_name, "entry point")
    missing = [f for f in ENTRY_FUNCTIONS if not callable(getattr(module, f, None))]
    if missing:
        raise ManifestError(f"entry point {entry_name!r} lacks {missing}")
    return module
