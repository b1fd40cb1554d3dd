"""The benchmark's four-chip cell ``profile80m.sharded`` (PR 26), on 4 of
the 8 forced CPU devices and at a tiny row count: nothing here is a chip
run, and no number these tests read is a device metric."""

import copy
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import cells, compare, layer_metrics, run
from chipbench.drivers import sharded_loop
from chipbench.generators import profile_table
from chipbench.readers import scan_hbm_roofline, sharded_scan_hbm_roofline
from deequ_tpu.ops.scan_engine import persist_table, total_resident_bytes
from deequ_tpu.parallel.mesh import current_mesh, use_mesh

CELL = "profile80m.sharded"
ROWS = 16_003  # the mesh does not divide it
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


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
    bench = cells.load_benchmark()
    assert cell["workload"]["chips"] == 4
    assert cell["config"]["placement"]["devices"] == cell["workload"]["chips"]
    assert cell["config"]["rows"] == 80_000_000
    assert cell["traffic"]["driver"] == "sharded_loop"
    assert cell["suite"]["name"] == "scan"
    assert cells.plugin("drivers", "sharded_loop") is sharded_loop
    names = [m["name"] for m in cell["layer_metrics"]]
    # membership and the count, not positions: later PRs append metrics
    assert {"sharded_scan_hbm_roofline", "chunks_per_suite",
            "collectives_per_suite", "plane_ops_per_suite"} <= set(names)
    assert "scan_hbm_roofline" not in names and "pack_ms_per_suite" not in names
    assert len(names) == len(set(names)) == 19
    # what the contract holds a cell to: one four-chip cell always may
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    ten = cells.load_cell("profile10m.scan")["config"]
    for key in ("schema", "generator", "generator_params", "precision",
                "guarantees"):
        assert cell["config"][key] == ten[key], key


@pytest.mark.parametrize("seed", [5, 2**31 + 26])
def test_the_cell_runs_sharded_and_correct(tiny_cell, seed):
    result = run_tiny(tiny_cell, seed)
    assert result["correct"] is True, result["notes"]
    assert result["failed"] == 0 and result["window"]["operations"] >= 1
    setup = result["window"]["setup"]
    assert len(setup["per_device_resident_bytes"]) == 4
    assert len(set(setup["per_device_resident_bytes"])) == 1
    assert setup["chunks"] == 1 and setup["chunk_rows"] == ROWS + 1
    assert setup["persist_s"] > 0 and setup["persist_pack_s"] > 0
    counters = result["layer_counters"]
    assert counters["fetches_per_suite"] == 1
    assert counters["programs_built_in_window"] == 0
    assert counters["chunks_per_suite"] == 1
    assert counters["collectives_per_suite"] >= 100  # a leaf or more an op
    # the driver leaves neither a mesh nor residency behind
    assert current_mesh() is not None and len(current_mesh().devices.flat) == 8
    assert total_resident_bytes() == 0


def test_a_traced_run_reports_the_counters_and_no_device_number(tiny_cell):
    result = run_tiny(tiny_cell, 9, trace=True)
    assert result["correct"] is True
    assert result["metrics"]["chunks_per_suite"]["value"] == 1
    assert result["metrics"]["collectives_per_suite"]["value"] >= 100
    assert "sharded_scan_hbm_roofline" not in result["metrics"]  # a CPU trace
    assert "device_idle_pct" not in result["metrics"]


def test_the_float32_control_is_not_correct(tiny_cell):
    config, suite = tiny_cell["config"], tiny_cell["suite"]
    data = profile_table.generate(ROWS, 5, config["generator_params"])
    records = [{"k": k, "rows": sharded_loop.rows_per_operation(config)}
               for k in range(3)]
    verdict = compare.control_verdict(sharded_loop.slices, config, suite, data,
                                      records)
    assert verdict["correct"] is False
    assert verdict["checks"]["moment_rel"]["value"] > 1e-9


# -- the placement proof ------------------------------------------------------


def _table(rows=4096):
    from chipbench import suite_build

    cell = cells.load_cell(CELL)
    return suite_build.table_of(profile_table.generate(
        rows, 3, cell["config"]["generator_params"]))


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("rows",))


PLACEMENT = {"devices": 4, "axis": "rows", "share_tolerance": 0.02}


def test_the_proof_holds_for_a_table_sharded_over_four():
    table = _table()
    with use_mesh(_mesh(4)):
        cache = persist_table(table, chunk_rows=1024)
        nbytes = cache.nbytes  # unpersist() zeroes it
        proof = sharded_loop.prove_placement(cache, PLACEMENT)
        table.unpersist()
    assert proof["chunks"] == 4 and proof["chunk_rows"] == 1024
    assert proof["per_device_resident_bytes"] == [nbytes // 4] * 4


@pytest.mark.parametrize("n", [None, 2, 8])
def test_the_proof_raises_for_another_number_of_devices(n):
    table = _table()
    with use_mesh(None if n is None else _mesh(n)):
        cache = persist_table(table)
        with pytest.raises(sharded_loop.PlacementError, match="device"):
            sharded_loop.prove_placement(cache, PLACEMENT)
        table.unpersist()
    with pytest.raises(sharded_loop.PlacementError):
        sharded_loop.prove_placement(None, PLACEMENT)


def test_the_proof_raises_for_uneven_bytes():
    """One plane of one chunk put whole on the first device."""
    table = _table()
    with use_mesh(_mesh(4)):
        cache = persist_table(table, chunk_rows=1024)
        chunk = list(cache.device_chunks[0])
        chunk[1] = jax.device_put(np.asarray(chunk[1]), jax.devices()[0])
        cache.device_chunks[0] = tuple(chunk)
        with pytest.raises(sharded_loop.PlacementError, match="bytes by device"):
            sharded_loop.prove_placement(cache, PLACEMENT)
        table.unpersist()


def test_the_proof_raises_for_row_shards_that_are_not_a_quarter():
    """``row_valid`` replicated: every device holds the whole chunk's rows."""
    table = _table()
    mesh = _mesh(4)
    with use_mesh(mesh):
        cache = persist_table(table, chunk_rows=1024)
        chunk = list(cache.device_chunks[0])
        chunk[6] = jax.device_put(np.asarray(chunk[6]), NamedSharding(mesh, P()))
        cache.device_chunks[0] = tuple(chunk)
        with pytest.raises(sharded_loop.PlacementError, match="row shards"):
            sharded_loop.prove_placement(cache, PLACEMENT)
        table.unpersist()


# -- the cell's own per-layer metrics -----------------------------------------


def test_four_chips_at_the_peak_read_100_where_one_chips_formula_reads_400():
    cell = cells.load_cell(CELL)
    peaks = run.load_peaks("TPU v5 lite")
    rows = cell["config"]["rows"]
    need = rows * 20 * 9  # 20 nullable f64 columns: 8 bytes + a validity byte
    busy_per_op = need / (4 * peaks["hbm_bytes_per_s"])
    ctx = {"cell": cell, "peaks": peaks, "rows_per_op": rows,
           "trace": {"busy_s": 3 * busy_per_op, "traced_ops": 3,
                     "window_s": 1.0}}
    assert sharded_scan_hbm_roofline.read(ctx) == pytest.approx(100.0)
    assert scan_hbm_roofline.read(ctx) == pytest.approx(400.0)
    spec = next(m for m in cell["layer_metrics"]
                if m["name"] == "sharded_scan_hbm_roofline")
    assert layer_metrics.evaluate(spec, ctx) == pytest.approx(100.0)
    for nothing in ({}, {"busy_s": 0.0, "traced_ops": 3}):
        assert layer_metrics.evaluate(spec, dict(ctx, trace=nothing)) is None


def test_the_two_counter_files_evaluate_on_a_counter_delta():
    cell = cells.load_cell(CELL)
    specs = {m["name"]: m for m in cell["layer_metrics"]}
    totals = {"suites": 180, "chunks_processed": 540,
              "mesh_collectives": 540 * 220}
    ctx = {"counters": totals}
    assert layer_metrics.evaluate(specs["chunks_per_suite"], ctx) == 3.0
    assert layer_metrics.evaluate(specs["collectives_per_suite"], ctx) == 660.0
    # a program without the counter (the parent): nothing to read, no raise
    del totals["mesh_collectives"]
    assert layer_metrics.evaluate(specs["collectives_per_suite"], ctx) is None
    assert layer_metrics.evaluate(specs["chunks_per_suite"], ctx) == 3.0
