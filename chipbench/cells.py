"""Finds a cell's files by the names in ``BENCHMARK.json``: the
configuration (its ``file``), the traffic mix
(``chipbench/traffic/<traffic>.json``), the suite the mix names
(``chipbench/suites/<suite>.json``) and each per-layer metric
(``chipbench/layer_metrics/<name>.json``). A new cell, suite,
configuration, mix or counter metric is new files plus one entry; nothing
here names any of them."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CellError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load(root: str, relative: str) -> dict:
    path = os.path.join(root, relative)
    if not os.path.isfile(path):
        raise CellError(f"{relative}: no such file")
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise CellError(f"{relative}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _load(root, "BENCHMARK.json")


def load_cell(name: str, root: str = ROOT) -> dict:
    """``{"workload", "config", "traffic", "suite", "layer_metrics",
    "end_to_end"}``."""
    bench = load_benchmark(root)
    workload = next((w for w in bench["workloads"] if w["name"] == name), None)
    if workload is None:
        known = [w["name"] for w in bench["workloads"]]
        raise CellError(f"no workload {name!r} in BENCHMARK.json: {known}")
    entry = next((c for c in bench["configs"]
                  if c["name"] == workload["config"]), None)
    if entry is None:
        raise CellError(f"workload {name!r}: no config {workload['config']!r}")
    config = _load(root, entry["file"])
    traffic = _load(root, f"chipbench/traffic/{workload['traffic']}.json")
    suite = _load(root, f"chipbench/suites/{traffic['suite']}.json")
    metrics = []
    for m in bench["per_layer"]:
        if name in m.get("workloads", [name]):
            spec = _load(root, f"chipbench/layer_metrics/{m['name']}.json")
            metrics.append({**spec, "name": m["name"], "unit": m["unit"]})
    return {"workload": workload, "config": config, "traffic": traffic,
            "suite": suite, "layer_metrics": metrics,
            "end_to_end": bench["end_to_end"]}


def plugin(package: str, name: str):
    """``chipbench.<package>.<name>``: a driver, a generator or a reader."""
    if not name.replace("_", "").isalnum():
        raise CellError(f"{package}: bad name {name!r}")
    try:
        return importlib.import_module(f"chipbench.{package}.{name}")
    except ModuleNotFoundError as e:
        raise CellError(f"no chipbench/{package}/{name}.py") from e
