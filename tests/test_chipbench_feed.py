"""The six per-layer metrics of PR 34 (the feed gauge's ``unfed``, the
``fetch.copy`` seam, the enclosing seams' own time, the harness's share
of its span, the staged bytes, and the reader ``idle_while_fed``): each
file on a synthetic context, the entries of ``BENCHMARK.json``, and the
counters of every cell run on the CPU at a tiny size. Nothing here is a
chip run, and no number these tests read is a device metric."""

import copy
import time
from contextlib import nullcontext

import pytest

from chipbench import cells, layer_metrics, run
from chipbench.readers import idle_while_fed
from deequ_tpu.obs.recorder import feed_gauge
from deequ_tpu.ops.scan_engine import total_resident_bytes
from deequ_tpu.parallel.mesh import use_mesh

ALL = ["profile10m.scan", "append1b.serial", "profile80m.sharded",
       "quantiles12m50.qscan", "strings12m.sscan"]
STAGED = ["append1b.serial", "profile80m.sharded", "quantiles12m50.qscan"]
ENTRY, EXECUTORS = "entry points and planner", "executors"
#: name -> (unit, source, layer, cells, the value on COUNTERS below)
METRICS = {
    "unfed_ms_per_suite": ("ms", "program_span", EXECUTORS, ALL, 30.0),
    "fetch_copy_ms_per_suite": ("ms", "program_span", EXECUTORS, ALL, 5.0),
    "run_own_ms_per_suite": ("ms", "program_span", ENTRY, ALL, 3.5),
    "harness_ms_per_suite": ("ms", "program_span", ENTRY, ALL, 10.0),
    "staged_mb_per_suite": ("MB", "program_counter", "ingest", STAGED, 79.0),
}
COUNTERS = {
    "suites": 4, "unfed_seconds": 0.120, "seam_fetch_copy_seconds": 0.020,
    "seam_run_seconds": 0.004, "seam_scan_attempt_seconds": 0.010,
    "run_span_seconds": 2.040, "run_seconds": 2.000,
    "bytes_staged": 316_000_000,
}
TRACE = {"window_s": 1.5, "busy_s": 1.2, "traced_ops": 3}
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _spec(name, cell="quantiles12m50.qscan"):
    specs = {m["name"]: m for m in cells.load_cell(cell)["layer_metrics"]}
    return specs[name]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_counter_metric_evaluates_and_is_left_out_without_its_counters(name):
    unit, source, layer, _, value = METRICS[name]
    spec = _spec(name)
    assert (spec["unit"], spec["source"], spec["layer"]) == (unit, source, layer)
    assert spec["kind"] == "counter_ratio" and spec["per"] == "suites"
    assert layer_metrics.evaluate(spec, {"counters": COUNTERS}) == \
        pytest.approx(value)
    # a program that lacks the counter (the parent): nothing, and no raise
    for term, _ in spec["terms"]:
        lacking = {k: v for k, v in COUNTERS.items() if k != term}
        assert layer_metrics.evaluate(spec, {"counters": lacking}) is None


def test_idle_while_fed_is_the_idle_less_what_the_host_withheld():
    spec = _spec("idle_while_fed_ms_per_suite")
    assert spec["kind"] == "reader" and spec["reader"] == "idle_while_fed"
    ctx = {"trace": TRACE, "counters": COUNTERS}
    # 100 ms idle a traced operation, less 30 unfed and 10 of the harness
    assert layer_metrics.evaluate(spec, ctx) == pytest.approx(60.0)
    assert idle_while_fed.read(ctx) == pytest.approx(60.0)
    # the traced operations are not the window's: under zero is reported
    busy = dict(TRACE, busy_s=1.44)
    assert idle_while_fed.read({"trace": busy, "counters": COUNTERS}) == \
        pytest.approx(-20.0)


@pytest.mark.parametrize("lacking", [
    "trace", "window_s", "traced_ops", "unfed_seconds", "run_seconds",
    "run_span_seconds", "suites"])
def test_idle_while_fed_reads_nothing_where_a_part_is_missing(lacking):
    trace = {k: v for k, v in TRACE.items() if k != lacking}
    counters = {k: v for k, v in COUNTERS.items() if k != lacking}
    ctx = {"trace": {} if lacking == "trace" else trace, "counters": counters}
    assert idle_while_fed.read(ctx) is None
    assert idle_while_fed.read({"counters": COUNTERS}) is None


def test_the_entries_are_appended_with_their_cells_and_nothing_else_moved():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[31:37] == [
        "unfed_ms_per_suite", "fetch_copy_ms_per_suite",
        "run_own_ms_per_suite", "harness_ms_per_suite",
        "staged_mb_per_suite", "idle_while_fed_ms_per_suite"]
    assert names[30] == "hll_presence_folds_per_suite"
    # the profiler's cell appends its three after them, and itself to each
    # list; the LINEITEM cell its two, and itself to each list; the
    # profiler's DataTypes from the counts one more
    assert len(names) == 43
    layers = {m["layer"] for m in bench["per_layer"][:31]}
    for entry in bench["per_layer"][31:37]:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["moves"] == "rows_per_s" and entry["better"] == "lower"
        assert entry["layer"] in layers  # no layer is named anew
        if entry["name"] in METRICS:
            unit, source, layer, where, _ = METRICS[entry["name"]]
        else:
            unit, source, layer, where = "ms", "device_trace", "device", ALL
        assert (entry["unit"], entry["source"], entry["layer"],
                entry["workloads"]) == (unit, source, layer, where + [
                    "mixed12m100.profile", "lineitem-sf10.lcheck"])


# -- every cell, on the CPU at a tiny size ------------------------------------


def _tiny(name):
    cell = copy.deepcopy(cells.load_cell(name))
    config = cell["config"]
    config["rows"] = 16_000
    if "partition_rows" in config:
        config["partition_rows"] = 2_000
    if name == "strings12m.sscan":
        config["generator_params"]["dictionary_sizes"] = [
            300, 1_500, 6_000, 14_000]
    return cell


_RESULTS = {}


def _result(name, trace=False):
    """One CPU run of the tiny cell, made once for the cases that read it."""
    key = (name, trace)
    if key not in _RESULTS:
        cell = _tiny(name)
        one_chip = cell["workload"]["chips"] == 1
        with use_mesh(None) if one_chip else nullcontext():
            _RESULTS[key] = run.run_cell(
                cell, 2**31 + 34, 0.3, trace, dict(FAKE_DEVICE),
                t0=time.perf_counter())
    return _RESULTS[key]


@pytest.mark.parametrize("name", ALL)
def test_unspanned_is_the_roots_own_time_the_harness_and_two_seams(name):
    """What ``unspanned_ms_per_suite`` held unsplit: the program's time
    under no named seam, the benchmark's own code inside its span, and the
    two seams no term of its file names yet (where the cell opens them)."""
    result = _result(name)
    assert result["correct"] is True, result["notes"]
    c = result["layer_counters"]
    parts = c["run_own_ms_per_suite"] + c["harness_ms_per_suite"]
    parts += c.get("grouping_ms_per_suite", 0.0)
    parts += c.get("sketch_fold_ms_per_suite", 0.0)
    assert c["unspanned_ms_per_suite"] == pytest.approx(parts, abs=1e-9)
    assert c["run_own_ms_per_suite"] > 0 and c["harness_ms_per_suite"] > 0
    # the split of fetch leaves what the accepted metrics read as it was
    assert c["fetches_per_suite"] == 1
    assert 0 < c["fetch_copy_ms_per_suite"] < c["device_wait_ms_per_suite"]
    assert c["programs_built_in_window"] == 0
    assert c["host_ms_per_suite"] == pytest.approx(
        c["plan_ms_per_suite"] + c["evaluate_ms_per_suite"]
        + c.get("pack_ms_per_suite", 0.0) + c.get("states_ms_per_suite", 0.0)
        + c["unspanned_ms_per_suite"], abs=1e-6)
    # some of the run fed the device, some did not, and every run ended
    # with all it had dispatched known ready. Unfed time is a part of the
    # root seams' wall, which is the span less the harness: host and
    # device wait leave out the stage seam (dispatch_seconds' other part;
    # only the cells that stage in the window report it). The plan comes
    # before the first dispatch (unfed), and the wait for the fetch after
    # it (fed) on any backend
    span_ms = c["host_ms_per_suite"] + c["device_wait_ms_per_suite"]
    run_ms = (span_ms + c.get("stage_ms_per_suite", 0.0)
              - c["harness_ms_per_suite"])
    assert 0 < c["unfed_ms_per_suite"] < run_ms
    assert feed_gauge() == 0 and total_resident_bytes() == 0


@pytest.mark.parametrize("name", ALL)
def test_staged_bytes_are_reported_where_the_cell_stages(name):
    c = _result(name)["layer_counters"]
    if name == "append1b.serial":
        # a host-packed partition: 20 columns as (hi, lo) float32 pairs
        # and their masks
        assert c["staged_mb_per_suite"] > 2_000 * 20 * 8 / 1e6
    elif name in STAGED:
        # one resident chunk at this size: no fold, no accumulator
        assert c["staged_mb_per_suite"] == 0.0
    else:
        assert "staged_mb_per_suite" not in c


@pytest.mark.parametrize("name", ["profile10m.scan", "append1b.serial"])
def test_a_traced_cpu_run_reports_the_counters_and_no_idle_split(name):
    metrics = _result(name, trace=True)["metrics"]
    for counter in ("unfed_ms_per_suite", "fetch_copy_ms_per_suite",
                    "run_own_ms_per_suite", "harness_ms_per_suite"):
        assert metrics[counter]["unit"] == "ms"
        assert metrics[counter]["value"] > 0
    # a CPU trace has no device plane: the reader finds nothing to read
    assert "idle_while_fed_ms_per_suite" not in metrics
    assert "device_idle_pct" not in metrics
