"""The harness finds everything by the names in BENCHMARK.json, and a new
cell is new files plus entries — no edit to a file that is there."""

import json
import os
import re

import pytest

from chipbench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_cell_resolves_to_its_files():
    bench = cells.load_benchmark()
    assert bench["paths"] == ["chipbench"]
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert cell["suite"]["name"] == cell["traffic"]["suite"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"rows_per_s", "setup_s"}
        assert cell["layer_metrics"], "every cell reports a per-layer metric"
        cells.plugin("drivers", cell["traffic"]["driver"])
        cells.plugin("generators", cell["config"]["generator"])


def test_names_units_and_entries_keep_to_the_contract():
    bench = cells.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"] for m in bench["end_to_end"]}
    workloads = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        on_file = json.load(open(os.path.join(cells.ROOT, c["file"])))
        assert on_file["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and set(m["workloads"]) <= workloads
        spec = json.load(open(os.path.join(
            cells.ROOT, "chipbench", "layer_metrics", m["name"] + ".json")))
        assert (spec["layer"], spec["source"]) == (m["layer"], m["source"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for why in [x["why"] for x in bench["configs"] + bench["workloads"]]:
        assert 1 <= len(why) <= 200 and "\n" not in why and "\t" not in why
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_a_missing_file_or_name_is_rejected(benchmark_copy):
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load_cell("no.such.cell")
    root = benchmark_copy
    os.remove(root / "chipbench" / "suites" / "scan.json")
    with pytest.raises(cells.CellError, match="suites/scan.json"):
        cells.load_cell("profile10m.scan", str(root))
    os.remove(root / "chipbench" / "layer_metrics" / "fetches_per_suite.json")
    with pytest.raises(cells.CellError, match="fetches_per_suite.json"):
        cells.load_cell("append1b.serial", str(root))
    with pytest.raises(cells.CellError, match="no chipbench/drivers"):
        cells.plugin("drivers", "no_such_loop")


def test_a_cell_suite_config_mix_and_counter_metric_come_as_new_files(
        benchmark_copy):
    """In a temporary copy: five new data files and four new entries, no
    file that was there edited but BENCHMARK.json; the new cell runs."""
    import time

    import deequ_tpu  # noqa: F401
    from chipbench import run

    root = benchmark_copy
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in (root / "chipbench").rglob("*") if x.is_file())}
    config = json.load(open(root / "chipbench/configs/profile10m.json"))
    config.update(name="narrow1m", rows=8000)
    (root / "chipbench/configs/narrow1m.json").write_text(json.dumps(config))
    (root / "chipbench/traffic/moments.json").write_text(json.dumps(
        {"name": "moments", "driver": "resident_loop", "suite": "moments"}))
    (root / "chipbench/suites/moments.json").write_text(json.dumps({
        "name": "moments",
        "check": {"level": "ERROR", "description": "moments", "constraints": [
            {"method": "has_mean", "args": ["c2"], "lo": 90.0, "hi": 110.0}]},
        "analyzers": [{"analyzer": "Size", "args": []},
                      {"analyzer": "Mean", "args": ["c2"]},
                      {"analyzer": "Maximum", "args": ["c9"]}]}))
    (root / "chipbench/layer_metrics/scan_passes_per_suite.json").write_text(
        json.dumps({"name": "scan_passes_per_suite", "layer": "executors",
                    "source": "program_counter", "kind": "counter_ratio",
                    "terms": [["scan_passes", 1]], "per": "suites"}))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "narrow1m", "source": "test",
                             "file": "chipbench/configs/narrow1m.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "narrow1m.moments", "config": "narrow1m",
                               "traffic": "moments", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "scan_passes_per_suite", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "executors",
        "moves": "rows_per_s", "workloads": ["narrow1m.moments"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("narrow1m.moments", str(root))
    assert [m["name"] for m in cell["layer_metrics"]] == ["scan_passes_per_suite"]
    result = run.run_cell(cell, 5, 0.2, True, {"platform": "tpu",
                          "kind": "TPU v5 lite", "count": 1},
                          t0=time.perf_counter())
    assert result["correct"] is True, result
    assert result["metrics"]["scan_passes_per_suite"]["value"] == 1.0
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"
