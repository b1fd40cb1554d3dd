"""The benchmark's quantile-and-correlation cell ``quantiles12m50.qscan``
(PR 30), with the mesh off as on its one chip, on the CPU and at a tiny row
count: nothing here is a chip run, and no number these tests read is a
device metric."""

import copy
import time

import numpy as np
import pytest

from chipbench import cells, compare, layer_metrics, run, suite_build
from chipbench.drivers import resident_loop
from chipbench.drivers.common import counters
from chipbench.generators import profile_table
from deequ_tpu.ops import scan_engine
from deequ_tpu.ops.scan_engine import SCAN_STATS, total_resident_bytes
from deequ_tpu.parallel.mesh import use_mesh

CELL = "quantiles12m50.qscan"
ROWS = 40_003  # no chunk size used here divides it
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
QUANTILES = (0.25, 0.5, 0.75, 0.9, 0.99)


@pytest.fixture(autouse=True)
def one_chip():
    with use_mesh(None):
        yield


@pytest.fixture(scope="module")
def tiny_cell():
    cell = copy.deepcopy(cells.load_cell(CELL))
    cell["config"]["rows"] = ROWS
    return cell


def run_tiny(cell, seed, trace=False):
    return run.run_cell(cell, seed, 0.3, trace, dict(FAKE_DEVICE),
                        t0=time.perf_counter())


def test_load_cell_finds_every_file_of_the_cell():
    cell = cells.load_cell(CELL)
    config, suite = cell["config"], cell["suite"]
    assert cell["workload"]["chips"] == 1
    assert len(cell["workload"]["why"]) <= 200
    assert cell["traffic"]["driver"] == "resident_loop"
    assert cell["traffic"]["suite"] == suite["name"] == "quantiles50"
    assert config["rows"] * 8 == 100_000_000 and config["reduced"] == ["rows"]
    ten = cells.load_cell("profile10m.scan")["config"]
    assert config["generator"] == ten["generator"]
    assert config["generator_params"]["numeric"] == \
        ten["generator_params"]["numeric"]
    assert config["generator_params"]["n_numeric"] == 50
    assert config["precision"] == ten["precision"]
    assert config["guarantees"] == {
        "exact": [], "moment_rel_error": 1e-9, "quantile_rank_error": 0.01,
        "on_device_error": "fail", "degradation_events": 0,
        "every_metric_is_success": True}
    entries = suite["analyzers"]
    assert [(e["analyzer"], e["args"]) for e in entries[:50]] == [
        ("ApproxQuantile", [f"c{i}", QUANTILES[i % 5]]) for i in range(50)]
    assert [(e["analyzer"], e["args"]) for e in entries[50:]] == [
        ("Correlation", [f"c{2 * j}", f"c{2 * j + 1}"]) for j in range(25)]
    by_key = {(e["analyzer"], tuple(e["args"])) for e in entries}
    from chipbench import reference
    for c in suite["check"]["constraints"]:  # each names an entry of the suite
        kind, args, _ = reference.constraint_analyzer(c)
        assert (kind, args) in by_key, c
    names = [m["name"] for m in cell["layer_metrics"]]
    assert set(names) == {
        "host_ms_per_suite", "fetches_per_suite", "programs_built_in_window",
        "fetched_mb_per_suite", "scan_hbm_roofline", "device_idle_pct",
        "plan_ms_per_suite", "device_wait_ms_per_suite",
        "evaluate_ms_per_suite", "unspanned_ms_per_suite",
        "select_passes_per_suite", "sort_passes_per_suite",
        "sketch_fold_ms_per_suite", "summaries_folded_per_suite",
        "hist_onehot_per_suite", "hist_scatter_per_suite",
        "unfed_ms_per_suite", "fetch_copy_ms_per_suite",
        "run_own_ms_per_suite", "harness_ms_per_suite",
        "staged_mb_per_suite", "idle_while_fed_ms_per_suite"}
    assert len(names) == len(set(names))
    # the selection step reads the suite's bytes once: 50 nullable f64 columns
    from chipbench import work
    assert work.suite_bytes(config, suite, config["rows"]) == 12_500_000 * 450


@pytest.mark.parametrize("seed", [11, 2**31 + 30])
def test_the_cell_runs_correct_with_each_number_beside_its_limit(
        tiny_cell, seed):
    result = run_tiny(tiny_cell, seed)
    assert result["correct"] is True, result["notes"]
    assert result["failed"] == 0 and result["window"]["operations"] >= 1
    checks = result["checks"]
    assert checks["quantile_rank"]["limit"] == 0.01
    assert 0 < checks["quantile_rank"]["value"] <= 0.01
    assert checks["moment_rel"]["value"] <= checks["moment_rel"]["limit"] == 1e-9
    for name in ("exact_mismatches", "verdict_mismatches", "failed_metrics",
                 "degradation_events", "operations_failed"):
        assert checks[name]["value"] == 0, name
    c = result["layer_counters"]
    assert c["fetches_per_suite"] == 1
    assert c["programs_built_in_window"] == 0
    assert c["select_passes_per_suite"] == 50  # one chunk at this size
    assert c["sort_passes_per_suite"] == 0
    assert c["summaries_folded_per_suite"] == 50
    assert c["hist_onehot_per_suite"] + c["hist_scatter_per_suite"] == 150
    assert c["sketch_fold_ms_per_suite"] > 0
    # the host seams still add up: the files' identity stands, and the new
    # seam is inside what no file names yet
    assert c["host_ms_per_suite"] == pytest.approx(
        c["plan_ms_per_suite"] + c["evaluate_ms_per_suite"]
        + c["unspanned_ms_per_suite"], abs=1e-6)
    assert c["sketch_fold_ms_per_suite"] < c["unspanned_ms_per_suite"]
    assert total_resident_bytes() == 0


def test_a_traced_run_reports_the_counters_and_no_device_number(tiny_cell):
    result = run_tiny(tiny_cell, 13, trace=True)
    assert result["correct"] is True, result["notes"]
    assert result["metrics"]["select_passes_per_suite"]["value"] == 50
    assert result["metrics"]["sketch_fold_ms_per_suite"]["value"] > 0
    assert "scan_hbm_roofline" not in result["metrics"]  # a CPU trace
    assert "device_idle_pct" not in result["metrics"]


def test_chunks_fold_into_one_sketch_a_column_within_the_stated_error(
        tiny_cell, monkeypatch):
    """Four resident chunks: every column's sketch is the fold of four
    chunk summaries, fetched once, and holds 0.01 against ``numpy.sort``."""
    monkeypatch.setattr(scan_engine, "MAX_RESIDENT_CHUNK_ROWS", 12_000)
    config, suite = tiny_cell["config"], tiny_cell["suite"]
    data = profile_table.generate(ROWS, 17, config["generator_params"])
    driver = resident_loop.Driver(config, tiny_cell["traffic"], suite, data)
    driver.prepare()
    chunks = len(driver.table._device_cache.device_chunks)
    assert chunks == 4
    before = counters()
    _, answers = driver._run()
    delta = {k: v - before.get(k, 0) for k, v in counters().items()}
    driver.release()
    assert not SCAN_STATS.degradation_events
    assert delta["device_select_passes"] == 50 * chunks
    assert delta["device_sort_passes"] == 0
    assert delta["kll_summaries_folded"] == 50 * chunks
    assert delta["seam_sketch_fold_count"] == 50
    assert delta["seam_sketch_fold_seconds"] > 0
    assert delta["device_fetches"] == 1
    assert delta["programs_built"] == 0  # the second suite of the table
    assert answers["failed"] == []
    worst = 0.0
    for entry, value in zip(suite["analyzers"][:50], answers["values"]):
        col = next(c for c in data["columns"] if c["name"] == entry["args"][0])
        ordered = np.sort(col["values"][col["mask"]])
        rank = np.searchsorted(ordered, value, side="right") / len(ordered)
        worst = max(worst, abs(rank - entry["args"][1]))
        assert abs(rank - entry["args"][1]) <= 0.01, entry
    assert worst > 0  # a sketch, not the sorted column


def test_the_float32_control_is_not_correct_by_the_correlations(tiny_cell):
    config, suite = tiny_cell["config"], tiny_cell["suite"]
    data = profile_table.generate(ROWS, 11, config["generator_params"])
    records = [{"k": k, "rows": resident_loop.rows_per_operation(config)}
               for k in range(2)]
    verdict = compare.control_verdict(resident_loop.slices, config, suite,
                                      data, records)
    assert verdict["correct"] is False
    assert verdict["checks"]["moment_rel"]["value"] > 1e-9
    assert verdict["checks"]["quantile_rank"]["value"] <= 0.01


def test_the_new_counter_files_evaluate_on_a_counter_delta():
    cell = cells.load_cell(CELL)
    specs = {m["name"]: m for m in cell["layer_metrics"]}
    totals = {"suites": 4, "device_select_passes": 600,
              "device_sort_passes": 0, "kll_summaries_folded": 600,
              "seam_sketch_fold_seconds": 0.8, "hist_onehot_dispatches": 1800,
              "hist_scatter_dispatches": 0}
    ctx = {"counters": totals}
    read = lambda name: layer_metrics.evaluate(specs[name], ctx)  # noqa: E731
    assert read("select_passes_per_suite") == 150.0
    assert read("sort_passes_per_suite") == 0.0
    assert read("summaries_folded_per_suite") == 150.0
    assert read("sketch_fold_ms_per_suite") == pytest.approx(200.0)
    assert read("hist_onehot_per_suite") == 450.0
    assert read("hist_scatter_per_suite") == 0.0
    # a program without the seam or the counter (the parent): nothing to
    # read, no raise
    del totals["seam_sketch_fold_seconds"], totals["kll_summaries_folded"]
    assert read("sketch_fold_ms_per_suite") is None
    assert read("summaries_folded_per_suite") is None
    assert read("select_passes_per_suite") == 150.0
