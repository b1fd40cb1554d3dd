"""The seven per-layer metrics that read the program's seam counters
(``SCAN_STATS.seam_*_seconds``): pure data files, listed in the cells that
have something to read there, and each a number on the CPU fixture cells.
Nothing here is a device number."""

import json
import os
import time

import pytest

from chipbench import cells

BOTH = ["profile10m.scan", "append1b.serial"]
APPEND = ["append1b.serial"]
# metric -> (seams whose exclusive seconds it sums, cells that list it)
SEAM_METRICS = {
    "plan_ms_per_suite": (["plan", "build"], BOTH),
    "pack_ms_per_suite": (["pack"], APPEND),
    "stage_ms_per_suite": (["stage"], APPEND),
    "device_wait_ms_per_suite": (["dispatch", "drain", "fetch"], BOTH),
    "states_ms_per_suite": (["states", "repository"], APPEND),
    "evaluate_ms_per_suite": (["evaluate"], BOTH),
}
UNSPANNED = "unspanned_ms_per_suite"
HOST_SIDE = ["plan_ms_per_suite", "pack_ms_per_suite", "states_ms_per_suite",
             "evaluate_ms_per_suite", UNSPANNED]


def _spec(name):
    path = os.path.join(cells.ROOT, "chipbench", "layer_metrics",
                        name + ".json")
    with open(path) as f:
        return json.load(f)


def _entry(name):
    return next(m for m in cells.load_benchmark()["per_layer"]
                if m["name"] == name)


@pytest.mark.parametrize("name", list(SEAM_METRICS) + [UNSPANNED])
def test_entry_and_data_file_keep_to_the_issue(name):
    entry, spec = _entry(name), _spec(name)
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == "program_span" == spec["source"]
    assert entry["moves"] == "rows_per_s"
    assert entry["layer"] == spec["layer"]
    assert spec["kind"] == "counter_ratio"
    assert spec["scale"] == 1000.0 and spec["per"] == "suites"
    if name == UNSPANNED:
        assert entry["workloads"] == BOTH
        every = sorted({s for seams, _ in SEAM_METRICS.values() for s in seams}
                       | {"stage"})
        assert sorted(spec["terms"]) == sorted(
            [["run_span_seconds", 1]]
            + [[f"seam_{s}_seconds", -1] for s in every])
    else:
        seams, workloads = SEAM_METRICS[name]
        assert entry["workloads"] == workloads
        assert spec["terms"] == [[f"seam_{s}_seconds", 1] for s in seams]


def test_the_accepted_entries_stand_first_and_unchanged():
    """This PR appends: the seven accepted per-layer entries keep their
    places, and the new ones come behind them."""
    names = [m["name"] for m in cells.load_benchmark()["per_layer"]]
    assert names[:7] == [
        "host_ms_per_suite", "fetches_per_suite", "programs_built_in_window",
        "packed_mb_per_s", "fetched_mb_per_suite", "scan_hbm_roofline",
        "device_idle_pct"]
    assert names[7:] == list(SEAM_METRICS) + [UNSPANNED]


@pytest.mark.parametrize("cell_name", BOTH)
def test_every_data_file_reads_a_number_on_the_fixture_cell(
        cell_name, tiny_cell):
    """All seven files against one tiny run of each cell (the files a cell
    does not list are laid in here, to show what their counters read):
    numbers, none negative, and the host-side five sum to
    ``host_ms_per_suite`` as the seams partition the benchmark's span."""
    import deequ_tpu  # noqa: F401
    from chipbench import run
    from chipbench.tests.conftest import FAKE_DEVICE

    cell = tiny_cell(cell_name)
    listed = {m["name"] for m in cell["layer_metrics"]}
    for name in list(SEAM_METRICS) + [UNSPANNED]:
        workloads = _entry(name)["workloads"]
        assert (name in listed) == (cell_name in workloads)
        if name not in listed:
            cell["layer_metrics"].append({**_spec(name), "name": name,
                                          "unit": "ms"})
    result = run.run_cell(cell, 11, 0.3, False, dict(FAKE_DEVICE),
                          t0=time.perf_counter())
    assert result["correct"] is True
    got = result["layer_counters"]
    for name in list(SEAM_METRICS) + [UNSPANNED]:
        assert isinstance(got[name], float), name
        assert got[name] >= 0.0, (name, got[name])
    assert got["device_wait_ms_per_suite"] > 0
    assert got["evaluate_ms_per_suite"] > 0 and got["plan_ms_per_suite"] > 0
    if cell_name in APPEND:
        assert got["pack_ms_per_suite"] > 0 and got["stage_ms_per_suite"] > 0
        assert got["states_ms_per_suite"] > 0
    else:  # resident, no providers: those seams never open
        assert got["pack_ms_per_suite"] == 0.0
        assert got["states_ms_per_suite"] == 0.0
    # stage + device_wait is what host_ms_per_suite subtracts
    assert sum(got[n] for n in HOST_SIDE) == pytest.approx(
        got["host_ms_per_suite"], rel=1e-6, abs=1e-6)


def test_a_program_without_seams_leaves_the_metrics_out():
    """On the parent commit ``SCAN_STATS`` has no ``seam_*`` field: the
    data files then read nothing, and the line leaves the metrics out."""
    from chipbench import layer_metrics

    counters = {"suites": 3, "run_span_seconds": 1.0,
                "dispatch_seconds": 0.1, "drain_wait_seconds": 0.2}
    for name in list(SEAM_METRICS) + [UNSPANNED]:
        spec = {**_spec(name), "name": name}
        assert layer_metrics.evaluate(spec, {"counters": counters}) is None
