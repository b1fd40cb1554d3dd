"""A resident table row-sharded over a mesh (PR 26): the residency budget
is what ONE device holds, resident chunks are sized for the mesh, the
collectives of the sharded step are counted and named, and a table that
neither the chunk nor the mesh divides gives the answers of the whole."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from chipbench import cells, compare, suite_build
from chipbench.drivers import resident_loop
from chipbench.generators import profile_table
from deequ_tpu import VerificationSuite
from deequ_tpu.analyzers import Maximum, Mean, Minimum, Size, StandardDeviation
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.ops import scan_engine as eng
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    DeviceTableCache,
    persist_table,
    resident_bytes_per_device,
    run_scan,
    total_resident_bytes,
)
from deequ_tpu.parallel.mesh import ROW_AXIS, use_mesh

DEVICES = (1, 2, 4, 8)


def mesh_of(n):
    """Row mesh over the first ``n`` forced devices; one device is the
    unsharded path (``use_mesh(None)``)."""
    if n == 1:
        return None
    return Mesh(np.array(jax.devices()[:n]), (ROW_AXIS,))


def small_table(rows=4096, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarTable([
        Column("a", DType.FRACTIONAL, values=rng.normal(10.0, 2.0, rows),
               mask=rng.random(rows) > 0.05),
        Column("b", DType.INTEGRAL, values=rng.integers(0, 1000, rows)),
    ])


def packed_bytes(table, chunk_rows):
    """The table's packed size: the same under every mesh whose size
    divides ``chunk_rows``."""
    with use_mesh(None):
        nbytes = persist_table(table, chunk_rows=chunk_rows).nbytes
        table.unpersist()
    return nbytes


# -- (a) the budget is per device ---------------------------------------------


@pytest.mark.parametrize("n", DEVICES[1:])
def test_total_over_budget_persists_when_the_share_fits(n, monkeypatch):
    table = small_table()
    nbytes = packed_bytes(table, 1024)
    share = nbytes // n
    monkeypatch.setattr(DeviceTableCache, "MAX_RESIDENT_BYTES", share)
    assert nbytes > DeviceTableCache.MAX_RESIDENT_BYTES
    with use_mesh(mesh_of(n)):
        cache = persist_table(table, chunk_rows=1024)
        assert cache.device_count == n and len(cache.device_chunks) == 4
        assert cache.per_device_bytes == share
        assert total_resident_bytes() == nbytes
        assert resident_bytes_per_device() == share
        held = {}
        for chunk in cache.device_chunks:
            for buf in chunk:
                for s in buf.addressable_shards:
                    held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
        assert set(held.values()) == {share}  # the fullest device holds it
        table.unpersist()
    assert total_resident_bytes() == 0 and resident_bytes_per_device() == 0


@pytest.mark.parametrize("n", DEVICES)
def test_share_over_budget_raises_the_typed_error(n, monkeypatch):
    table = small_table()
    nbytes = packed_bytes(table, 1024)
    monkeypatch.setattr(DeviceTableCache, "MAX_RESIDENT_BYTES",
                        nbytes // n - 1)
    with use_mesh(mesh_of(n)):
        with pytest.raises(MemoryError, match=f"each of {n} device"):
            persist_table(table, chunk_rows=1024)
    assert table._device_cache is None
    assert total_resident_bytes() == 0


@pytest.mark.parametrize("slack,fits", [(0, True), (-1, False)])
def test_one_device_is_held_to_the_total_as_before(slack, fits, monkeypatch):
    table = small_table()
    nbytes = packed_bytes(table, 1024)
    monkeypatch.setattr(DeviceTableCache, "MAX_RESIDENT_BYTES", nbytes + slack)
    with use_mesh(None):
        if fits:
            cache = persist_table(table, chunk_rows=1024)
            assert cache.per_device_bytes == cache.nbytes == nbytes
            assert resident_bytes_per_device() == total_resident_bytes()
            table.unpersist()
        else:
            with pytest.raises(MemoryError):
                persist_table(table, chunk_rows=1024)
    assert total_resident_bytes() == 0


def test_two_tables_shares_add_up_on_a_device(monkeypatch):
    first, second = small_table(seed=1), small_table(seed=2)
    share = packed_bytes(first, 1024) // 4
    with use_mesh(mesh_of(4)):
        persist_table(first, chunk_rows=1024)
        monkeypatch.setattr(DeviceTableCache, "MAX_RESIDENT_BYTES",
                            2 * share - 1)
        with pytest.raises(MemoryError):
            persist_table(second, chunk_rows=1024)
        monkeypatch.setattr(DeviceTableCache, "MAX_RESIDENT_BYTES", 2 * share)
        persist_table(second, chunk_rows=1024)
        assert resident_bytes_per_device() == 2 * share
        first.unpersist()
        second.unpersist()
    assert total_resident_bytes() == 0


@pytest.mark.parametrize("n", [1, 4])
def test_a_resident_table_takes_one_loop_and_no_second_copy(n, monkeypatch):
    """On one device as on a mesh, a persisted table of N chunks is N
    dispatches of ONE program, N fold merges and one fetch, and the scan
    leaves on the device what ``persist()`` put there."""
    from deequ_tpu.ops.device_policy import install_scan_fault_hook

    table = small_table(rows=4000)
    ops = moments_ops(table)
    dispatched, merges = [], []
    merge = eng._DeviceFoldPlan.merge
    monkeypatch.setattr(
        eng._DeviceFoldPlan, "merge",
        lambda plan, acc, new: merges.append(1) or merge(plan, acc, new))
    with use_mesh(mesh_of(n)):
        cache = persist_table(table, chunk_rows=1024)
        assert len(cache.device_chunks) == 4
        assert total_resident_bytes() == cache.nbytes
        previous = install_scan_fault_hook(
            lambda boundary, ctx: dispatched.append(ctx["chunk_index"]))
        try:
            SCAN_STATS.reset()
            run_scan(table, ops)
        finally:
            install_scan_fault_hook(previous)
        assert dispatched == [0, 1, 2, 3] and len(merges) == 4
        assert SCAN_STATS.programs_built == 1
        assert len(cache.programs) == 1
        assert SCAN_STATS.chunks_processed == 4
        assert SCAN_STATS.device_fetches == 1
        assert total_resident_bytes() == cache.nbytes
        assert cache.per_device_bytes == cache.nbytes // n
        table.unpersist()
    assert total_resident_bytes() == 0


def test_the_registry_reports_both_ledgers():
    from deequ_tpu.obs.registry import REGISTRY

    table = small_table()
    with use_mesh(mesh_of(4)):
        cache = persist_table(table, chunk_rows=1024)
        hbm = REGISTRY.snapshot()["hbm"]
        assert hbm["resident_bytes"] == cache.nbytes
        assert hbm["resident_bytes_per_device"] == cache.nbytes // 4
        table.unpersist()
    assert REGISTRY.snapshot()["hbm"]["resident_bytes_per_device"] == 0


# -- (b) the share tied to the whole ------------------------------------------


@pytest.fixture(scope="module")
def scan_cell():
    return cells.load_cell("profile10m.scan")


@pytest.fixture(scope="module")
def ragged(scan_cell):
    """50,003 rows (a prime) in chunks of 12,345 (rounded up to the mesh):
    five chunks, the last one a third full; 1% nulls in every column."""
    return profile_table.generate(
        50_003, 11, scan_cell["config"]["generator_params"])


def suite_answers(cell, data, n, chunk_rows=12_345):
    table = suite_build.table_of(data)
    analyzers = suite_build.analyzers_of(cell["suite"])
    with use_mesh(mesh_of(n)):
        cache = persist_table(table, chunk_rows=chunk_rows)
        assert len(cache.device_chunks) == 5
        assert data["rows"] % cache.chunk and data["rows"] % max(n, 2)
        result = (
            VerificationSuite.on_data(table)
            .add_check(suite_build.check_of(cell["suite"], data["rows"]))
            .add_required_analyzers(analyzers)
            .run()
        )
        table.unpersist()
    return suite_build.answers_of(result, analyzers)


@pytest.fixture(scope="module")
def unsharded_answers(scan_cell, ragged):
    return suite_answers(scan_cell, ragged, 1)


@pytest.mark.parametrize("n", DEVICES)
def test_every_metric_of_the_scan_suite_equals_the_reference(
        n, scan_cell, ragged, unsharded_answers):
    config, suite = scan_cell["config"], scan_cell["suite"]
    answers = suite_answers(scan_cell, ragged, n)
    record = {"k": 0, "rows": ragged["rows"], "answers": answers}
    want, verdicts = compare.reference_for(
        resident_loop.slices, config, suite, ragged, [record])
    verdict = compare.decide(config, suite, [record], want, verdicts, 0, 0)
    assert verdict["correct"], verdict
    assert verdict["checks"]["exact_mismatches"]["value"] == 0
    assert verdict["checks"]["moment_rel"]["value"] <= 1e-9
    for entry, got, solo in zip(suite["analyzers"], answers["values"],
                                unsharded_answers["values"]):
        assert abs(got - solo) <= 1e-12 * max(1.0, abs(solo)), entry
    assert answers["verdict_rows"] == unsharded_answers["verdict_rows"]


# -- (c) resident chunks sized for the mesh -----------------------------------


def test_the_resident_chunk_scales_with_the_mesh_up_to_the_cap(monkeypatch):
    """Two i64 columns pack to 10 bytes a row: with the per-device target
    cut to the smallest chunk (2^18 rows) the chunk is n x 2^18 rows, held
    at a cap of 2^20, and a suite folds as many chunks fewer."""
    rows = 2_100_000
    rng = np.random.default_rng(3)
    table = ColumnarTable([
        Column("x", DType.INTEGRAL, values=rng.integers(0, 1 << 20, rows)),
        Column("y", DType.INTEGRAL, values=rng.integers(0, 1 << 20, rows)),
    ])
    monkeypatch.setattr(eng, "RESIDENT_CHUNK_BYTES", 10 << 18)
    monkeypatch.setattr(eng, "MAX_RESIDENT_CHUNK_ROWS", 1 << 20)
    analyzers = [Size(), Mean("x"), Maximum("y")]
    seen, answers = {}, {}
    for n in DEVICES:
        with use_mesh(mesh_of(n)):
            cache = table.persist()._device_cache
            SCAN_STATS.reset()
            got = AnalysisRunner.do_analysis_run(table, analyzers)
            seen[n] = (cache.chunk, len(cache.device_chunks),
                       SCAN_STATS.chunks_processed, SCAN_STATS.device_fetches)
            answers[n] = [got.metric(a).value.get() for a in analyzers]
            table.unpersist()
    one = 1 << 18
    assert seen == {1: (one, 9, 9, 1), 2: (2 * one, 5, 5, 1),
                    4: (4 * one, 3, 3, 1), 8: (4 * one, 3, 3, 1)}
    assert all(answers[n] == answers[1] for n in DEVICES)


def test_the_profiler_table_takes_three_chunks_on_four_devices():
    """80M x 20 fractional columns (180 packed bytes a row): 7 chunks of
    11.9M rows on one device, 3 under the 2^25 cap on four."""
    dtypes = [DType.FRACTIONAL] * 20
    chunk = {n: eng._auto_chunk_rows_from_dtypes(
        dtypes, eng.RESIDENT_CHUNK_BYTES * n, eng.MAX_RESIDENT_CHUNK_ROWS)
        for n in DEVICES}
    assert chunk[1] == (2 << 30) // 180 and chunk[2] == 2 * (2 << 30) // 180
    assert chunk[4] == chunk[8] == 1 << 25
    assert [-(-80_000_000 // chunk[n]) for n in DEVICES] == [7, 4, 3, 3]


# -- (d) the collectives, counted and named -----------------------------------


def moments_ops(table):
    ops, _, failures = AnalysisRunner._build_scan_ops(
        table, [Size(), Mean("a"), StandardDeviation("a"), Minimum("a"),
                Maximum("b")])
    assert not failures
    return ops


@pytest.mark.parametrize("resident", [True, False], ids=["resident", "packed"])
@pytest.mark.parametrize("n", [1, 4])
def test_mesh_collectives_is_leaves_times_dispatched_chunks(n, resident):
    table = small_table(rows=4000)
    ops = moments_ops(table)
    leaves = sum(len(jax.tree.leaves(op.tags)) for op in ops)
    assert leaves >= len(ops)
    with use_mesh(mesh_of(n)):
        if resident:
            persist_table(table, chunk_rows=1024)
        SCAN_STATS.reset()
        run_scan(table, ops, chunk_rows=1024)
        assert SCAN_STATS.chunks_processed == 4
        assert SCAN_STATS.mesh_collectives == (0 if n == 1 else 4 * leaves)
        assert SCAN_STATS.device_fetches == 1
        table.unpersist()


def test_mesh_collectives_counts_a_streams_dispatches():
    from deequ_tpu.data.streaming import stream_table

    table = small_table(rows=4000)
    ops = moments_ops(table)
    leaves = sum(len(jax.tree.leaves(op.tags)) for op in ops)
    with use_mesh(mesh_of(4)):
        SCAN_STATS.reset()
        run_scan(stream_table(table, batch_rows=1000), ops)
        assert SCAN_STATS.mesh_collectives == 4 * leaves
    with use_mesh(None):
        SCAN_STATS.reset()
        run_scan(stream_table(table, batch_rows=1000), ops)
        assert SCAN_STATS.mesh_collectives == 0


def test_the_sharded_step_names_its_collectives_by_tag():
    """``deequ.collective.<tag>`` around each branch of ``_tag_collective``
    (metadata only): a device trace names psum and all_gather time."""
    table = small_table(rows=1024)
    ops = moments_ops(table)
    tags = {t for op in ops for t in jax.tree.leaves(op.tags)}
    assert {"sum", "min", "max"} <= tags
    packer = eng._ChunkPacker({n: table[n] for n in ("a", "b")}, 1024)
    texts = {}
    for n in (1, 4):
        step_fn, _, _ = eng._build_step_fns(
            ops, packer.unpack_view(), mesh_of(n), 1024 // n)
        texts[n] = step_fn.lower(*packer.pack(0, 1024), {}).as_text(
            debug_info=True)
    for tag in tags:
        assert f"deequ.collective.{tag}/" in texts[4], tag
    assert "deequ.collective" not in texts[1]
    with pytest.raises(ValueError, match="unknown reduce tag"):
        eng._tag_collective("mean", 0.0, ROW_AXIS)


def test_persist_stage_names_the_devices_it_puts_to():
    from deequ_tpu.obs import FlightRecorder, recording_scope

    table = small_table()
    rec = FlightRecorder()
    with recording_scope(rec), use_mesh(mesh_of(4)):
        persist_table(table, chunk_rows=1024)
        table.unpersist()
    staged = [s for s in rec.records()
              if s.name == "persist.stage" and "chunk" in s.args]
    assert len(staged) == 4
    assert all(s.args["devices"] == 4 for s in staged)


def test_the_seam_identity_holds_on_the_mesh_path(scan_cell, ragged):
    """PR 24's identity, as ``chipbench``'s data files state it: plan +
    pack + states + evaluate + unspanned == host_ms_per_suite, from one
    set of counters, with the suite run under a mesh."""
    from chipbench import layer_metrics
    from chipbench.drivers.common import Window

    table = suite_build.table_of(ragged)
    analyzers = suite_build.analyzers_of(scan_cell["suite"])
    check = suite_build.check_of(scan_cell["suite"], ragged["rows"])

    def one(_k):
        (VerificationSuite.on_data(table).add_check(check)
         .add_required_analyzers(analyzers).run())
        return ragged["rows"], None

    def metric(name, totals):
        spec = cells._load(cells.ROOT, f"chipbench/layer_metrics/{name}.json")
        return layer_metrics.evaluate(spec, {"counters": totals})

    with use_mesh(mesh_of(4)):
        persist_table(table, chunk_rows=12_345)
        one(0)  # builds the program: the window's suites dispatch
        window = Window(0.3)
        window.drive("suite.run", one)
        table.unpersist()
    totals = window.totals()
    assert totals["suites"] >= 1 and not window.failed
    assert totals["seam_build_count"] == 0
    parts = ["plan_ms_per_suite", "pack_ms_per_suite", "states_ms_per_suite",
             "evaluate_ms_per_suite", "unspanned_ms_per_suite"]
    assert sum(metric(p, totals) for p in parts) == pytest.approx(
        metric("host_ms_per_suite", totals), abs=1e-6)
    assert metric("chunks_per_suite", totals) == 5
    assert metric("fetches_per_suite", totals) == 1
    ops, _, _ = AnalysisRunner._build_scan_ops(table, analyzers)
    assert metric("collectives_per_suite", totals) == 5 * sum(
        len(jax.tree.leaves(op.tags)) for op in ops)
