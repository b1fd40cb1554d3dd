"""The benchmark's string cell ``strings12m.sscan`` (PR 32), with the mesh
off as on its one chip, on the CPU and at a tiny row count and tiny
dictionaries (cut in the test's copy): nothing here is a chip run, and no
number these tests read is a device metric.

The float32 control has nothing to hold in this cell: every answer is an
integer (HLL registers from a 64-bit hash, exact counts), so no precision
below the stated one exists for the reference to be computed in. What
stands in its place is ``TopBins``' tightness (each way a truncated
histogram can be wrong is NOT equal) and the program held to the
reference register for register and count for count."""

import copy
import time

import numpy as np
import pytest

from chipbench import cells, layer_metrics, reference, run, work
from chipbench.drivers import resident_topn_loop
from chipbench.drivers.common import counters
from chipbench.generators import string_table
from chipbench.topbins import DETAIL_BINS, TopBins
from deequ_tpu.ops import device_policy, hll, segment
from deequ_tpu.ops.scan_engine import SCAN_STATS, total_resident_bytes
from deequ_tpu.parallel.mesh import use_mesh

CELL = "strings12m.sscan"
ROWS = 60_003
SIZES = [300, 1_500, 6_000, 14_000]  # two classes under 1,000 bins, two over
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(autouse=True)
def one_chip():
    with use_mesh(None):
        yield


@pytest.fixture(scope="module")
def tiny_cell():
    cell = copy.deepcopy(cells.load_cell(CELL))
    cell["config"]["rows"] = ROWS
    cell["config"]["generator_params"]["dictionary_sizes"] = SIZES
    return cell


def run_tiny(cell, seed, trace=False):
    return run.run_cell(cell, seed, 0.3, trace, dict(FAKE_DEVICE),
                        t0=time.perf_counter())


def test_load_cell_finds_every_file_and_the_files_state_the_cut():
    cell = cells.load_cell(CELL)
    config, suite = cell["config"], cell["suite"]
    assert cell["workload"]["chips"] == 1
    assert len(cell["workload"]["why"]) <= 200
    assert cell["traffic"]["driver"] == "resident_topn_loop"
    assert cell["traffic"]["suite"] == suite["name"] == "strings20"
    assert config["rows"] * 8 == 100_000_000 and config["reduced"] == ["rows"]
    assert config["reduced_why"] and config["deployment"] and config["assumed"]
    params = config["generator_params"]
    assert config["generator"] == "string_table" and params["n_string"] == 20
    assert params["dictionary_sizes"] == [50_000, 250_000, 1_000_000, 3_000_000]
    cap = device_policy.HIST_ONEHOT_MXU_MAX_SEGMENTS
    assert [s + 2 > cap for s in params["dictionary_sizes"]] == [
        False, True, True, True]  # one class under the one-hot cap
    assert max(params["dictionary_sizes"]) < min(
        segment.DENSE_KEYSPACE_LIMIT, config["rows"] // 4)
    assert config["guarantees"] == {
        "exact": ["ApproxCountDistinct", "Histogram"], "moment_rel_error": 0.0,
        "on_device_error": "fail", "degradation_events": 0,
        "every_metric_is_success": True}
    entries = suite["analyzers"]
    assert [(e["analyzer"], e["args"]) for e in entries] == [
        ("ApproxCountDistinct", [f"s{i}"]) for i in range(20)] + [
        ("Histogram", [f"s{i}"]) for i in range(20)]
    by_key = {(e["analyzer"], tuple(e["args"])) for e in entries}
    for c in suite["check"]["constraints"]:  # each names an entry of the suite
        kind, args, _ = reference.constraint_analyzer(c)
        assert (kind, args) in by_key, c
    names = [m["name"] for m in cell["layer_metrics"]]
    assert set(names) == {
        "host_ms_per_suite", "fetches_per_suite", "programs_built_in_window",
        "fetched_mb_per_suite", "scan_hbm_roofline", "device_idle_pct",
        "plan_ms_per_suite", "device_wait_ms_per_suite",
        "evaluate_ms_per_suite", "unspanned_ms_per_suite",
        "hist_onehot_per_suite", "hist_scatter_per_suite",
        "grouping_passes_per_suite", "grouping_ms_per_suite",
        "hll_folds_per_suite", "hist_wide_per_suite", "lut_builds_in_window",
        "hll_presence_folds_per_suite",
        "unfed_ms_per_suite", "fetch_copy_ms_per_suite",
        "run_own_ms_per_suite", "harness_ms_per_suite",
        "idle_while_fed_ms_per_suite"}
    assert len(names) == len(set(names))
    # a suite reads every column's int32 codes once: no validity byte
    assert work.suite_bytes(config, suite, config["rows"]) == 12_500_000 * 80


@pytest.mark.parametrize("seed", [11, 2**31 + 30, 77])
def test_the_cell_runs_correct_with_the_counters_read(tiny_cell, seed):
    result = run_tiny(tiny_cell, seed)
    assert result["correct"] is True, result["notes"]
    assert result["failed"] == 0 and result["window"]["operations"] >= 1
    for name, check in result["checks"].items():
        assert check["value"] == 0, name
    c = result["layer_counters"]
    # ONE fetch since PR 33: the twenty top-k's and, beside them, the
    # registers of the twenty columns; no scan is dispatched
    assert c["fetches_per_suite"] == 1
    assert c["programs_built_in_window"] == 0
    assert c["lut_builds_in_window"] == 0
    assert c["grouping_passes_per_suite"] == 20
    assert c["hll_folds_per_suite"] == 0
    assert c["hll_presence_folds_per_suite"] == 20
    assert c["hist_onehot_per_suite"] + c["hist_scatter_per_suite"] == 20
    assert c["hist_wide_per_suite"] == 20  # the CPU's cap is 32 slots
    assert c["grouping_ms_per_suite"] > 0
    # the files' identity stands; the new seam is inside what no file names
    assert c["host_ms_per_suite"] == pytest.approx(
        c["plan_ms_per_suite"] + c["evaluate_ms_per_suite"]
        + c["unspanned_ms_per_suite"], abs=1e-6)
    assert c["grouping_ms_per_suite"] < c["unspanned_ms_per_suite"]
    assert total_resident_bytes() == 0


def test_a_traced_run_reports_the_counters_and_no_device_number(tiny_cell):
    result = run_tiny(tiny_cell, 13, trace=True)
    assert result["correct"] is True, result["notes"]
    metrics = result["metrics"]
    assert metrics["grouping_passes_per_suite"]["value"] == 20
    assert metrics["hll_folds_per_suite"]["value"] == 0
    assert metrics["hll_presence_folds_per_suite"]["value"] == 20
    assert metrics["fetches_per_suite"]["value"] == 1
    assert metrics["lut_builds_in_window"]["value"] == 0
    assert metrics["grouping_ms_per_suite"]["value"] > 0
    assert "scan_hbm_roofline" not in metrics  # a CPU trace
    assert "device_idle_pct" not in metrics


def test_every_fetch_of_a_suite_is_counted_and_waits_inside_a_seam(tiny_cell):
    """One suite of the persisted table: ONE fetch for all twenty
    Histograms and the registers of the twenty ApproxCountDistincts that
    ride with them (PR 33: no scan is dispatched), under the ``fetch``
    seam; the grouping seam holds host time only."""
    config, suite = tiny_cell["config"], tiny_cell["suite"]
    data = string_table.generate(ROWS, 17, config["generator_params"])
    driver = resident_topn_loop.Driver(config, tiny_cell["traffic"], suite, data)
    driver.prepare()
    before = counters()
    _, answers = driver._run()
    delta = {k: v - before.get(k, 0) for k, v in counters().items()}
    driver.release()
    assert not SCAN_STATS.degradation_events and answers["failed"] == []
    assert delta["device_fetches"] == delta["seam_fetch_count"] == 1
    assert delta["seam_grouping_count"] == 1  # twenty Histograms, one pass
    assert delta["grouping_passes"] == 20
    assert delta["hll_presence_folds"] == 20 and delta["hll_folds"] == 0
    assert delta["scan_passes"] == 0
    assert delta["hist_scatter_dispatches"] + delta["hist_onehot_dispatches"] == 20
    assert delta["programs_built"] == 0 and delta["lut_builds"] == 0
    # 20 x (1 + 2k + 512) int32: the top-k's, k = min(1000, dictionary +
    # 1), and the registers
    assert delta["bytes_fetched"] == 4 * sum(
        1 + 2 * min(DETAIL_BINS, SIZES[i % 4] + 1)
        + (1 << hll.precision_from_relative_sd()) for i in range(20))
    histograms = answers["values"][20:]
    assert all(isinstance(h, TopBins) for h in histograms)
    assert [len(h.bins) for h in histograms[:4]] == [301, 1000, 1000, 1000]


@pytest.mark.parametrize("threads", [1, 3])
def test_the_generator_gives_one_table_per_seed_whatever_the_pool(threads):
    params = {"n_string": 6, "dictionary_sizes": SIZES, "zipf_exponent": 1.0,
              "null_share": 0.01}
    base = string_table.generate(5_000, 2**31 + 5, params, threads=8)
    again = string_table.generate(5_000, 2**31 + 5, params, threads=threads)
    other = string_table.generate(5_000, 2**31 + 6, params, threads=threads)
    for i, (a, b, c) in enumerate(zip(base["columns"], again["columns"],
                                      other["columns"])):
        assert a["name"] == f"s{i}" and a["codes"].dtype == np.int32
        assert np.array_equal(a["codes"], b["codes"])
        assert not np.array_equal(a["codes"], c["codes"])
        assert list(a["dictionary"][:2]) == [f"s{i}_0", f"s{i}_1"]
        assert len(a["dictionary"]) == SIZES[i % 4]
        assert a["codes"].min() == -1 and a["codes"].max() < SIZES[i % 4]
    # even columns are Zipf (the first value far ahead), odd ones uniform
    zipf, flat = base["columns"][2]["codes"], base["columns"][3]["codes"]
    assert np.count_nonzero(zipf == 0) > 20 * np.count_nonzero(flat == 0)


def _full(n_bins=1500):
    """A full histogram of ``n_bins`` labels with ties at the cut: counts
    3000, 2999, ... down to a plateau of equal counts around rank 1,000."""
    counts = [max(3000 - j, 2005) for j in range(n_bins)]
    return {f"v{j}": c for j, c in enumerate(counts)}


def _top(w, k=DETAIL_BINS):
    ranked = sorted(w.items(), key=lambda kv: -kv[1])
    return dict(ranked[:k])


TOPBINS_CASES = {
    "right": (lambda w, b: (len(w), b), True),
    "the other side of the tie at the cut": (
        lambda w, b: (len(w), {**{k: v for k, v in b.items() if k != "v999"},
                               "v1400": w["v1400"]}), True),
    "a count off by one": (
        lambda w, b: (len(w), {**b, "v3": b["v3"] + 1}), False),
    "a bin that is not in w": (
        lambda w, b: (len(w), {**{k: v for k, v in b.items() if k != "v5"},
                               "nope": b["v5"]}), False),
    "a bin below the cut for one above it": (
        lambda w, b: (len(w), {**{k: v for k, v in b.items() if k != "v2"},
                               "v1400": w["v1400"]}), False),
    "number_of_bins off by one": (lambda w, b: (len(w) + 1, b), False),
    "999 bins where 1,000 are due": (
        lambda w, b: (len(w), {k: v for k, v in b.items() if k != "v999"}),
        False),
}


@pytest.mark.parametrize("case", list(TOPBINS_CASES))
def test_topbins_is_tight(case):
    build, equal = TOPBINS_CASES[case]
    w = _full()
    assert w["v999"] == w["v1400"] == 2005 and w["v2"] > 2005  # the tie
    got = TopBins(*build(w, _top(w)))
    assert (got == w) is equal and (got != w) is (not equal)
    assert len(repr(got)) < 200
    small = {"a": 3, "b": 1}  # under the cut: every bin is due
    assert TopBins(2, dict(small)) == small
    assert TopBins(2, {"a": 3}) != small


@pytest.mark.parametrize("variant", ["scatter", "onehot"])
def test_the_program_equals_the_reference_on_every_variant(
        variant, monkeypatch):
    """Seeded codes at a small size with the caps set low or high, so that
    the wide path (scatter past the cap) and the one-hot tier both run:
    counts bit-equal to ``np.bincount``, registers equal to
    ``reference.registers``, the top bins right by ``TopBins``."""
    from deequ_tpu import analyzers
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from chipbench import suite_build

    monkeypatch.setattr(segment, "HOST_GROUP_LIMIT", 0)
    monkeypatch.setattr(device_policy, "HIST_MIN_ROWS", 0)
    monkeypatch.setattr(device_policy, "HIST_ONEHOT_CPU_MAX_SEGMENTS",
                        64 if variant == "scatter" else 1 << 20)
    rows = 30_011
    params = {"n_string": 4, "dictionary_sizes": [90, 700, 2_500, 5_000],
              "zipf_exponent": 1.0, "null_share": 0.01}
    data = string_table.generate(rows, 2**31 + 9, params)
    table = suite_build.table_of(data)
    table.persist()
    try:
        before = counters()
        for col in data["columns"]:
            counts = np.asarray(segment._resident_string_bincount(
                table, col["name"], True, None))
            assert counts.dtype == np.int64
            assert np.array_equal(counts, np.bincount(
                col["codes"] + 1, minlength=len(col["dictionary"]) + 1))
        suite = [analyzers.ApproxCountDistinct(c["name"])
                 for c in data["columns"]]
        suite += [analyzers.Histogram(c["name"]) for c in data["columns"]]
        ctx = AnalysisRunner.do_analysis_run(table, suite)
        delta = {k: v - before.get(k, 0) for k, v in counters().items()}
    finally:
        table.unpersist()
    assert delta[f"hist_{variant}_dispatches"] == 8  # 4 alone + 4 batched
    assert delta["hist_wide_dispatches"] == (8 if variant == "scatter" else 0)
    p = reference.hll_precision()
    for i, col in enumerate(data["columns"]):
        valid = col["codes"] >= 0
        idx, rank = reference.idx_rank_strings(col["dictionary"], p)
        want = reference.registers(idx[col["codes"][valid]],
                                   rank[col["codes"][valid]], p)
        state_regs = hll.registers_from_idx_rank(
            idx[np.maximum(col["codes"], 0)], rank[np.maximum(col["codes"], 0)],
            valid, p, np)
        assert np.array_equal(np.asarray(state_regs), want)
        assert ctx.metric(suite[i]).value.get() == reference.hll_estimate(want)
        dist = ctx.metric(suite[4 + i]).value.get()
        full = reference.answer(
            {"analyzer": "Histogram"},
            {"counts": np.bincount(col["codes"] + 1,
                                   minlength=len(col["dictionary"]) + 1),
             "dictionary": col["dictionary"]})
        got = TopBins(dist.number_of_bins,
                      {k: int(v.absolute) for k, v in dist.values.items()})
        assert got == full, (col["name"], got)


def test_the_new_counter_files_evaluate_on_a_counter_delta():
    cell = cells.load_cell(CELL)
    specs = {m["name"]: m for m in cell["layer_metrics"]}
    totals = {"suites": 4, "one": 1, "grouping_passes": 80,
              "seam_grouping_seconds": 0.6, "hll_folds": 80,
              "hll_presence_folds": 80,
              "hist_wide_dispatches": 60, "lut_builds": 0}
    ctx = {"counters": totals}
    read = lambda name: layer_metrics.evaluate(specs[name], ctx)  # noqa: E731
    assert read("grouping_passes_per_suite") == 20.0
    assert read("grouping_ms_per_suite") == pytest.approx(150.0)
    assert read("hll_folds_per_suite") == 20.0
    assert read("hll_presence_folds_per_suite") == 20.0
    assert read("hist_wide_per_suite") == 15.0
    assert read("lut_builds_in_window") == 0.0
    # a program without the seam or the counters (the parent): nothing to
    # read, no raise
    for gone in ("seam_grouping_seconds", "hll_folds", "hist_wide_dispatches",
                 "lut_builds", "hll_presence_folds"):
        del totals[gone]
    assert read("grouping_ms_per_suite") is None
    assert read("hll_folds_per_suite") is None
    assert read("hll_presence_folds_per_suite") is None
    assert read("hist_wide_per_suite") is None
    assert read("lut_builds_in_window") is None
    assert read("grouping_passes_per_suite") == 20.0
