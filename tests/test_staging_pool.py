"""The staging pool of the host-packed scan (ops/scan_engine.py:
``_StagingPool`` / ``_StagingLease``): a chunk's planes are packed into
byte buffers that stay mapped, leased to that chunk, and handed back only
once its device result is known ready. On the CPU backend ``device_put``
may alias a numpy buffer without a copy, so a plane handed back too early
or shared between two chunks shows up HERE as a wrong answer.

The tests lower the pool's size cut so that small tables go through it;
the cut itself (planes under it are ``np.empty``'s) has a test of its own.
"""

import math
import time

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    Completeness,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
)
from deequ_tpu.checks import Check, CheckLevel
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import DeviceHangException
from deequ_tpu.ops import scan_engine
from deequ_tpu.ops.device_policy import DEVICE_HEALTH
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    _ChunkPacker,
    _StagingLease,
    _staging_capacity,
    fetch_deferred,
    install_scan_fault_hook,
    persist_table,
    run_scan,
)
from deequ_tpu.parallel.mesh import use_mesh
from deequ_tpu.resilience import FaultInjectingScanHook
from deequ_tpu.verification import VerificationSuite
from test_df32_pack import BLOCK, _mixed_columns

POOL = scan_engine._STAGING_POOL


@pytest.fixture(autouse=True)
def pool(monkeypatch):
    """An empty pool that takes every plane, however small."""
    POOL.clear()
    monkeypatch.setattr(POOL, "min_plane_bytes", 1)
    DEVICE_HEALTH.reset()
    prev = install_scan_fault_hook(None)
    yield POOL
    install_scan_fault_hook(prev)
    DEVICE_HEALTH.reset()
    POOL.clear()


@pytest.fixture(params=["mesh8", "single"])
def mesh_mode(request):
    if request.param == "single":
        with use_mesh(None):
            yield request.param
    else:
        yield request.param


def poison(pool):
    for buf in pool._free:
        buf.fill(0xFF)


def free_ids(pool):
    return {id(b) for b in pool._free}


def numeric_table(seed, n=3000, cols=4):
    """Fractional columns with nulls and an integral one: pair, narrow and
    mask planes, the shapes equal for every seed."""
    rng = np.random.default_rng(seed)
    columns = [
        Column(f"c{i}", DType.FRACTIONAL, rng.normal(100.0 + i, 5.0, n),
               rng.random(n) >= 0.05)
        for i in range(cols)
    ]
    columns.append(
        Column("k", DType.INTEGRAL, rng.integers(0, 1000, n).astype(np.int64))
    )
    return ColumnarTable(columns)


def analyzers(cols=4):
    out = [Size()]
    for i in range(cols):
        out += [Completeness(f"c{i}"), Mean(f"c{i}"), Minimum(f"c{i}"),
                Maximum(f"c{i}"), StandardDeviation(f"c{i}")]
    return out + [Mean("k"), Maximum("k")]


def scan_ops(table):
    return [a.scan_op(table) for a in analyzers()]


def same_results(got, want):
    """Two run_scan results, every leaf bit for bit."""
    import jax

    a, b = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


# -- (a) the planes are the parent's, whatever the buffer held ---------------

CHUNK = BLOCK + 64
PARTITIONS = {
    # rows packed into a chunk of how many slots, after a first pack of
    # CHUNK rows in CHUNK slots went back to the pool
    "same": (CHUNK, CHUNK, True),
    "padded_tail": (BLOCK - 1000, CHUNK, True),
    "shorter": (CHUNK - 777, CHUNK - 777, True),
    "some_rows_longer": (CHUNK + 100, CHUNK + 100, True),
    "twice_as_long": (2 * CHUNK, 2 * CHUNK, False),
    "empty": (0, CHUNK, True),
}


@pytest.mark.parametrize("case", sorted(PARTITIONS))
def test_pooled_planes_equal_fresh_planes_after_poison(case, pool):
    n, chunk, hits = PARTITIONS[case]
    first = _StagingLease()
    _ChunkPacker(_mixed_columns(CHUNK), CHUNK).pack(0, CHUNK, take=first.take)
    first.release()
    assert pool.free_bytes() > 0 and SCAN_STATS.staging_bytes_reused == 0
    poison(pool)

    packer = _ChunkPacker(_mixed_columns(n or CHUNK), chunk)
    want = packer.pack(0, n)
    lease = _StagingLease()
    got = packer.pack(0, n, take=lease.take)
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.flags.c_contiguous
        assert g.tobytes() == w.tobytes()
    pooled = sum(g.nbytes for g in got if g.nbytes)
    if hits:
        assert SCAN_STATS.staging_bytes_reused == pooled
    else:  # its largest planes fit nothing the first pack left
        assert SCAN_STATS.staging_bytes_reused < pooled
    # planes of one chunk never share a buffer
    planes = [g for g in got if g.nbytes]
    for i, a in enumerate(planes):
        for b in planes[i + 1:]:
            assert not np.shares_memory(a, b)


def test_default_take_is_fresh_planes_and_leaves_the_pool_alone(pool):
    packer = _ChunkPacker(_mixed_columns(CHUNK), CHUNK)
    a, b = packer.pack(0, CHUNK), packer.pack(0, CHUNK)
    for x, y in zip(a, b):
        assert x.tobytes() == y.tobytes()
        assert x.base is None and (not x.nbytes or not np.shares_memory(x, y))
    assert pool.free_bytes() == 0 and SCAN_STATS.staging_bytes_reused == 0


def test_planes_under_the_size_cut_are_np_emptys(pool, monkeypatch):
    monkeypatch.setattr(pool, "min_plane_bytes", 4 * CHUNK + 1)
    lease = _StagingLease()
    got = _ChunkPacker(_mixed_columns(CHUNK), CHUNK).pack(
        0, CHUNK, take=lease.take)
    # (2, CHUNK) f32/f64/i32 planes are over the cut, (1, CHUNK) i32 and the
    # bool planes under it
    assert [g.base is not None for g in got] == [
        True, True, True, True, False, False, False, False]
    lease.release()
    assert len(pool._free) == 4


@pytest.mark.parametrize("nbytes", [1, 4096, 100_000_000, (1 << 27) + 1])
def test_capacity_is_at_most_an_eighth_over(nbytes):
    cap = _staging_capacity(nbytes)
    assert nbytes <= cap <= nbytes + max(nbytes // 8, 1)
    assert _staging_capacity(cap) == cap


# -- (b) successive suites over fresh tables ---------------------------------


def suite_metrics(table):
    check = Check(CheckLevel.ERROR, "staging")
    for i in range(4):
        check = (check.is_complete(f"c{i}")
                 .has_mean(f"c{i}", lambda m: m > 0)
                 .has_standard_deviation(f"c{i}", lambda s: s > 0)
                 .has_min(f"c{i}", lambda v: v > 0)
                 .has_max(f"c{i}", lambda v: v < 1e6))
    result = VerificationSuite.on_data(table).add_check(check).run()
    values = {repr(a): m.value.get() for a, m in result.metrics.items()}
    assert len(values) == 20
    return values, result.scan_stats


def test_ten_suites_over_fresh_tables_equal_an_empty_pools(mesh_mode, pool):
    want = []
    for seed in range(10):
        pool.clear()  # what a process that never packed before would see
        want.append(suite_metrics(numeric_table(seed))[0])
    pool.clear()
    for seed in range(10):
        got, stats = suite_metrics(numeric_table(seed))
        assert got == want[seed]  # floats compared exactly
        if seed == 0:
            assert stats["staging_bytes_reused"] == 0
        else:
            assert stats["staging_bytes_reused"] == stats["bytes_packed"] > 0
    assert pool.free_bytes() > 0


@pytest.mark.parametrize("fold", ["device_fold", "host_fold"])
@pytest.mark.parametrize("window", [1, 2, 3])
def test_multi_chunk_scans_reuse_within_and_across_scans(
        fold, window, mesh_mode, pool, monkeypatch):
    if fold == "host_fold":
        monkeypatch.setattr(scan_engine, "_folds_on_device", lambda ops: False)
    tables = [numeric_table(seed, n=4000) for seed in range(3)]
    want = []
    for t in tables:
        pool.clear()
        want.append(run_scan(t, scan_ops(t), chunk_rows=512, window=window))
    pool.clear()
    SCAN_STATS.reset()
    for i, t in enumerate(tables):
        before = SCAN_STATS.staging_bytes_reused
        same_results(
            run_scan(t, scan_ops(t), chunk_rows=512, window=window), want[i])
        # eight chunks, at most window + 1 held at once: a scan reuses
        # its own planes, and the next scan maps nothing new
        reused = SCAN_STATS.staging_bytes_reused - before
        assert reused > 0
        if i:
            assert reused == SCAN_STATS.bytes_packed // (i + 1)


def test_streamed_scan_reuses_and_answers_the_same(mesh_mode, pool):
    from deequ_tpu.data.streaming import stream_table

    table = numeric_table(5, n=4000)
    want = run_scan(table, scan_ops(table), chunk_rows=512)
    pool.clear()
    SCAN_STATS.reset()
    got = run_scan(stream_table(table, batch_rows=512), scan_ops(table))
    same_results(got, want)
    assert SCAN_STATS.staging_bytes_reused > 0
    assert pool.free_bytes() > 0


# -- (c) two scans in flight --------------------------------------------------


@pytest.mark.parametrize("resolve", ["reverse", "one_fetch", "forward"])
def test_deferred_scans_hold_disjoint_buffers(resolve, mesh_mode, pool):
    t1, t2 = numeric_table(1), numeric_table(2)
    want1 = run_scan(t1, scan_ops(t1))
    want2 = run_scan(t2, scan_ops(t2))
    assert pool.free_bytes() > 0
    poison(pool)

    d1 = run_scan(t1, scan_ops(t1), defer=True)
    d2 = run_scan(t2, scan_ops(t2), defer=True)
    held1 = {id(b) for lease in d1._leases for b in lease._bufs}
    held2 = {id(b) for lease in d2._leases for b in lease._bufs}
    assert held1 and held2 and not held1 & held2
    assert not (held1 | held2) & free_ids(pool)
    if resolve == "one_fetch":
        fetch_deferred([d1, d2])
        assert held1 | held2 <= free_ids(pool)
    order = [d1, d2] if resolve == "forward" else [d2, d1]
    got = {id(d): d.result() for d in order}
    same_results(got[id(d1)], want1)
    same_results(got[id(d2)], want2)
    assert held1 | held2 <= free_ids(pool)


def test_a_deferred_scan_never_resolved_returns_nothing(pool):
    t = numeric_table(3)
    d = run_scan(t, scan_ops(t), defer=True)
    assert d._leases and pool.free_bytes() == 0
    del d
    assert pool.free_bytes() == 0


# -- (d) a scan that raises ---------------------------------------------------


def plane_caps(rows):
    """Capacities of the buffers a one-chunk scan of numeric_table maps."""
    return {_staging_capacity(4 * 4 * rows), _staging_capacity(4 * rows),
            _staging_capacity(8 * rows), _staging_capacity(rows)}


def test_an_oom_attempt_returns_nothing_and_the_retry_is_right(
        mesh_mode, pool):
    t = numeric_table(4, n=4096)
    want = run_scan(t, scan_ops(t), chunk_rows=2048)
    pool.clear()
    hook = FaultInjectingScanHook(faults={0: ("oom", 1)})
    install_scan_fault_hook(hook)
    got = run_scan(t, scan_ops(t))
    assert [k for k, *_ in hook.injected] == ["oom"]
    assert SCAN_STATS.oom_bisections == 1
    same_results(got, want)
    # the failed attempt packed 4096-row planes: none of them came back
    caps = {b.nbytes for b in pool._free}
    assert caps and caps <= plane_caps(2048)


@pytest.mark.parametrize("governed", [False, True])
def test_a_hung_attempt_returns_nothing_and_the_next_scan_is_right(
        governed, pool):
    t = numeric_table(6)
    want = run_scan(t, scan_ops(t))
    pool.clear()
    install_scan_fault_hook(FaultInjectingScanHook(
        faults={0: ("hang", math.inf)}, hang_seconds=0.6))
    how = {"run_deadline": 0.2} if governed else {"device_deadline": 0.2}
    with pytest.raises(DeviceHangException):
        run_scan(t, scan_ops(t), **how)
    assert pool.free_bytes() == 0
    same_results(run_scan(t, scan_ops(t)), want)
    mine = free_ids(pool)
    assert mine
    # the abandoned worker wakes, may run its attempt to the end, and still
    # hands nothing back
    time.sleep(0.8)
    assert free_ids(pool) == mine


# -- (e) the bound ------------------------------------------------------------


def test_retained_bytes_stay_under_the_bound(mesh_mode, pool, monkeypatch):
    t = numeric_table(7, n=4000)
    want = run_scan(t, scan_ops(t), chunk_rows=512)
    assert pool.free_bytes() > 0
    pool.clear()
    # under one chunk's planes: 16 + 4 + 4 + 1 bytes a row, 512 rows
    one_chunk = sum(plane_caps(512))
    monkeypatch.setattr(pool, "max_bytes", one_chunk - 1)
    same_results(run_scan(t, scan_ops(t), chunk_rows=512), want)
    assert 0 < pool.free_bytes() <= pool.max_bytes


def test_the_default_bound_is_what_one_scan_can_hold():
    assert scan_engine.STAGING_POOL_MAX_BYTES == (
        (scan_engine.DEFAULT_SCAN_WINDOW + 1) * scan_engine.DEFAULT_CHUNK_BYTES)
    assert POOL.max_bytes == scan_engine.STAGING_POOL_MAX_BYTES


def test_threads_never_hold_one_buffer_twice(pool, monkeypatch):
    """More threads than cores lease, fill, check and release planes of a
    few sizes under a short switch interval: a buffer handed to two
    leases at once shows as a foreign byte, a lost update as a wrong
    ``free_bytes``."""
    import sys
    import threading

    monkeypatch.setattr(pool, "max_bytes", 40 * 4096)
    errors = []
    deadline = time.monotonic() + 2.0

    def worker(tag):
        rng = np.random.default_rng(tag)
        try:
            while time.monotonic() < deadline and not errors:
                lease = _StagingLease()
                planes = [
                    lease.take((int(rng.integers(1, 4)), 1024), np.float32)
                    for _ in range(3)
                ]
                for plane in planes:
                    plane.fill(tag)
                time.sleep(0)
                for plane in planes:
                    if not (plane == tag).all():
                        errors.append(f"thread {tag} read a foreign byte")
                if rng.random() < 0.9:
                    lease.release()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(t + 1,))
               for t in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert pool.free_bytes() == sum(b.nbytes for b in pool._free)
    assert 0 < pool.free_bytes() <= pool.max_bytes
    assert len(free_ids(pool)) == len(pool._free)


# -- (f) persist() keeps fresh planes ----------------------------------------


def test_resident_chunks_never_share_memory_with_the_pool(mesh_mode, pool):
    resident = numeric_table(8)
    ops = scan_ops(resident)
    # buffers of exactly these shapes are free before persist() packs
    run_scan(numeric_table(9), ops)
    assert pool.free_bytes() > 0
    free_before = free_ids(pool)
    cache = persist_table(resident)
    assert free_ids(pool) == free_before  # persist() took none of them
    first = run_scan(resident, ops)
    assert SCAN_STATS.resident_passes == 1
    for seed in range(10, 14):
        other = numeric_table(seed)
        run_scan(other, scan_ops(other))
    poison(pool)
    same_results(run_scan(resident, ops), first)
    assert SCAN_STATS.resident_passes == 2
    if mesh_mode == "single":
        for chunk in cache.device_chunks:
            for arr in chunk:
                host = np.asarray(arr)
                assert not any(
                    np.shares_memory(host, buf) for buf in pool._free)
