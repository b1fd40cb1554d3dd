"""The fused step's plane statistics (scan_engine.PlaneStats): every op
the planner routes there reads its scalars out of ONE batched reduction
along the rows of the packed (hi, lo) planes. The same ops through their
own per-column ``update`` are the oracle: counts and extrema the same
bits, sums and moments within 1e-14, and both against numpy in float64
over the values the planes hold."""

import math

import jax
import numpy as np
import pytest

from deequ_tpu.analyzers import (
    Completeness,
    Compliance,
    Correlation,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.ops.df32 import split_pair_np
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    PlaneStats,
    _build_step_fns,
    _ChunkPacker,
    _unflatten_partials,
)
from deequ_tpu.ops.scan_plan import plan_scan_ops

FIVE = (Completeness, Mean, StandardDeviation, Minimum, Maximum, Sum)
REL = 1e-14


def _pair_exact(x):
    """``x`` rounded to what an (hi, lo) pair holds, so a float64
    reference over it reads the very values the planes carry."""
    hi, lo = split_pair_np(np.asarray(x, dtype=np.float64))
    with np.errstate(invalid="ignore"):
        return hi.astype(np.float64) + lo.astype(np.float64)


def _fractional(name, n, seed, nulls=0.01, loc=100.0):
    rng = np.random.default_rng(seed)
    values = _pair_exact(rng.normal(loc, 5.0, n))
    mask = rng.random(n) >= nulls if nulls else None
    return Column(name, DType.FRACTIONAL, values=values, mask=mask)


def _table_plain(n, nulls=0.01, cols=4):
    return ColumnarTable.from_columns(
        [_fractional(f"c{i}", n, 10 + i, nulls, 100.0 + i) for i in range(cols)]
    )


def _table_all_null(n=1000):
    t = [_fractional("c0", n, 1), _fractional("c2", n, 3)]
    dead = Column(
        "c1", DType.FRACTIONAL, values=np.zeros(n), mask=np.zeros(n, bool)
    )
    return ColumnarTable.from_columns([t[0], dead, t[1]])


def _table_non_finite(n=2000):
    rng = np.random.default_rng(5)
    a = _pair_exact(rng.normal(0.0, 3.0, n))
    a[[3, 700]] = np.inf
    b = _pair_exact(rng.normal(0.0, 3.0, n))
    b[[5]] = np.inf
    b[[900]] = -np.inf
    c = _pair_exact(rng.normal(0.0, 3.0, n))
    c[[11, 12]] = np.nan
    mask = rng.random(n) >= 0.05
    mask[[3, 5, 11, 700, 900]] = True
    return ColumnarTable.from_columns([
        Column("c0", DType.FRACTIONAL, values=a, mask=mask),
        Column("c1", DType.FRACTIONAL, values=b, mask=mask.copy()),
        Column("c2", DType.FRACTIONAL, values=c, mask=mask.copy()),
        _fractional("c3", n, 8),
    ])


def _table_interleaved(n=3000):
    """Pair columns between a narrow-i32, a wide-f64 and a string column,
    one of them null-free and one touched by no routed op: neither the
    routed hi/lo rows nor their mask rows are consecutive."""
    rng = np.random.default_rng(9)
    ints = Column(
        "i0", DType.INTEGRAL, values=rng.integers(-50, 50, n).astype(np.int64),
        mask=rng.random(n) >= 0.1,
    )
    wide = Column(
        "w0", DType.FRACTIONAL, values=rng.normal(0.0, 1.0, n) * 1e200,
        mask=rng.random(n) >= 0.1,
    )
    text = Column(
        "s0", DType.STRING, codes=rng.integers(-1, 3, n).astype(np.int32),
        dictionary=np.array(["a", "b", "c"], dtype=object),
    )
    return ColumnarTable.from_columns([
        _fractional("c0", n, 20), ints, _fractional("c1", n, 21), wide, text,
        _fractional("c2", n, 22, nulls=0), _fractional("c3", n, 23),
        _fractional("c4", n, 24),
    ])


def _routed_everywhere(table):
    return [
        A(c) for c in table.column_names
        if table[c].dtype != DType.STRING for A in FIVE
    ] + [
        Completeness(c) for c in table.column_names
        if table[c].dtype == DType.STRING
    ]


def _interleaved_suite(table):
    # c3 is read by a `where` op alone: its plane rows are skipped
    return [a for a in _routed_everywhere(table) if a.column != "c3"] + [
        Mean("c3", where="i0 > 0"), Size(),
    ]


def _mixed_suite(table):
    # a column that a predicate compares goes over the exact wide plane
    # (c3 here); the ops it filters read c0..c2 off the pair planes
    return _routed_everywhere(table) + [
        Mean("c0", where="c3 > 103"),
        Minimum("c0", where="c3 > 103"),
        StandardDeviation("c1", where="c3 < 103"),
        Compliance("c3 big", "c3 > 103"),
        Correlation("c0", "c1"),
        Size(),
    ]


# name -> (table, analyzers of that table, chunk or None for one chunk of
# exactly the table's rows, ops the planner must route)
CASES = {
    "nulls_1pct": (lambda: _table_plain(5000), _routed_everywhere, None, 24),
    "all_null_column": (_table_all_null, _routed_everywhere, None, 18),
    "null_free_no_mask_rows": (
        lambda: _table_plain(4096, nulls=0), _routed_everywhere, None, 24),
    "inf_and_nan": (_table_non_finite, _routed_everywhere, None, 24),
    "one_row": (lambda: _table_plain(1, nulls=0), _routed_everywhere, None, 24),
    "one_null_row": (lambda: _table_plain(1, nulls=1.0), _routed_everywhere, None, 24),
    "odd_rows": (lambda: _table_plain(1001), _routed_everywhere, None, 24),
    "rows_not_a_multiple_of_32": (
        lambda: _table_plain(4100), _routed_everywhere, None, 24),
    "padded_last_chunk": (
        lambda: _table_plain(3000), _routed_everywhere, 4096, 24),
    "padded_null_free": (
        lambda: _table_plain(777, nulls=0), _routed_everywhere, 1024, 24),
    "interleaved_planes": (_table_interleaved, _interleaved_suite, None, 24),
    "alternating_null_free": (
        lambda: ColumnarTable.from_columns([
            _fractional(f"c{i}", 2000, 40 + i, nulls=0.02 * (i % 2))
            for i in range(5)
        ]),
        _routed_everywhere, None, 30),
    "routed_beside_where_and_correlation": (
        lambda: _table_plain(5000), _mixed_suite, None, 18),
}


def _partials(ops, packer, args):
    step_fn, shape_fn, _ = _build_step_fns(
        ops, packer.unpack_view(), None, packer.chunk, ()
    )
    shapes = jax.eval_shape(shape_fn, *args, {})
    return _unflatten_partials(np.asarray(step_fn(*args, {})), shapes)


def _close(got, want, what):
    got, want = np.ravel(got), np.ravel(want)  # a gather leaf is (1,)
    if np.issubdtype(want.dtype, np.integer):
        assert np.array_equal(got, want), what
        return
    both_nan = np.isnan(got) & np.isnan(want)
    same = both_nan | (got == want)  # equal infinities included
    with np.errstate(invalid="ignore"):
        near = np.abs(got - want) <= REL * np.abs(want)
    assert np.all(same | near), (what, got, want)


def _reference(analyzer, table, n):
    """The analyzer's partial over the first ``n`` rows, numpy float64."""
    col = table[analyzer.column]
    x = np.asarray(col.values, dtype=np.float64)[:n]
    ok = np.asarray(col.mask)[:n]
    with np.errstate(invalid="ignore"):
        total = math.fsum(x[ok]) if np.isfinite(x[ok]).all() else x[ok].sum()
    count = int(ok.sum())
    if isinstance(analyzer, Completeness):
        return {"matches": count, "count": n}
    if isinstance(analyzer, Mean):
        return {"sum": total, "count": count}
    if isinstance(analyzer, Sum):
        return {"sum": total, "n": count}
    if isinstance(analyzer, StandardDeviation):
        mean = total / max(count, 1)
        with np.errstate(invalid="ignore"):
            m2 = math.fsum((x[ok] - mean) ** 2) if np.isfinite(
                x[ok]).all() else np.nan
        return {"n": count, "avg": mean, "m2": m2}
    red, ident = (np.min, np.inf) if isinstance(analyzer, Minimum) else (
        np.max, -np.inf)
    return {"value": red(x[ok]) if count else ident, "n": count}


@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_statistics_are_the_per_column_ones(case):
    make_table, make_suite, chunk, want_routed = CASES[case]
    table = make_table()
    analyzers = make_suite(table)
    ops, scannable, failures = AnalysisRunner._build_scan_ops(table, analyzers)
    assert not failures and len(scannable) == len(analyzers)
    n = table.num_rows
    packer = _ChunkPacker(
        {c: table[c] for c in table.column_names}, chunk or n
    )
    args = packer.pack(0, n)
    plan = plan_scan_ops(ops, packer, resident=False)
    assert plan.plane_ops == want_routed
    assert [op.tags for op in plan.ops] == [op.tags for op in ops]
    for op, planned in zip(ops, plan.ops):
        routed = planned.plane_route is not None
        # a routed program is never taken for the per-column one
        assert planned.cache_key == (
            ("plane", op.cache_key) if routed else op.cache_key)
        assert routed == (
            op.plane_update is not None
            and op.plane_column in packer.pair_names
        )

    batched = _partials(plan.ops, packer, args)
    per_column = _partials(ops, packer, args)
    for analyzer, got, want in zip(analyzers, batched, per_column):
        assert sorted(got) == sorted(want)
        for leaf in want:
            exact = leaf in ("value", "n", "count", "matches")
            if exact:
                assert np.array_equal(
                    got[leaf], want[leaf], equal_nan=True
                ), (analyzer, leaf, got[leaf], want[leaf])
                assert got[leaf].dtype == want[leaf].dtype
            else:
                _close(got[leaf], want[leaf], (analyzer, leaf))
    for analyzer, op, got in zip(analyzers, plan.ops, batched):
        if op.plane_route is None:
            continue
        want = _reference(analyzer, table, n)
        for leaf, value in want.items():
            if leaf == "m2" and np.isnan(value):
                continue  # a non-finite column: whatever IEEE gives
            _close(got[leaf], value, (analyzer, leaf, "numpy"))


def test_one_run_per_static_slice_of_the_planes():
    """Runs follow the layout: consecutive hi/lo rows whose mask rows are
    consecutive too (or absent throughout) are ONE slice."""
    table = _table_interleaved()
    ops, _, _ = AnalysisRunner._build_scan_ops(table, _interleaved_suite(table))
    packer = _ChunkPacker(
        {c: table[c] for c in table.column_names}, table.num_rows
    )
    assert packer.pair_names == ["c0", "c1", "c2", "c3", "c4"]
    assert packer.masked_names == ["c0", "i0", "c1", "w0", "c3", "c4"]
    route = plan_scan_ops(ops, packer).ops[0].plane_route
    assert [name for name, _ in route.columns] == ["c0", "c1", "c2", "c4"]
    stats = PlaneStats(packer, None, None, None, None, np)
    assert [(row, mrow, names) for row, mrow, names, _ in stats._runs(route)] == [
        (0, 0, ["c0"]), (1, 2, ["c1"]), (2, None, ["c2"]), (4, 5, ["c4"]),
    ]
    whole = _table_plain(2000, cols=6)
    ops, _, _ = AnalysisRunner._build_scan_ops(whole, _routed_everywhere(whole))
    packer = _ChunkPacker({c: whole[c] for c in whole.column_names}, 2000)
    route = plan_scan_ops(ops, packer).ops[0].plane_route
    assert [
        (row, mrow, len(names))
        for row, mrow, names, _ in PlaneStats(
            packer, None, None, None, None, np)._runs(route)
    ] == [(0, 0, 6)]


@pytest.mark.parametrize("chunk_rows", [None, 1 << 10])
def test_a_suite_counts_its_plane_ops_per_dispatch(chunk_rows):
    """Through the public runner: `plane_ops` is the routed ops of each
    dispatched plan (Size has no column and stays), and the metrics are
    numpy's."""
    from deequ_tpu.ops.scan_engine import run_scan

    table = _table_plain(3000)
    analyzers = _routed_everywhere(table) + [Size()]
    before = SCAN_STATS.plane_ops
    if chunk_rows is None:
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        dispatches = 1
        for analyzer in analyzers[:-1]:
            assert ctx.metric_map[analyzer].value.is_success
        x, ok = table["c1"].values, table["c1"].mask
        _close(ctx.metric_map[Mean("c1")].value.get(), x[ok].mean(), "mean")
        _close(ctx.metric_map[StandardDeviation("c1")].value.get(),
               x[ok].std(), "stddev")
        assert ctx.metric_map[Minimum("c1")].value.get() == x[ok].min()
        assert ctx.metric_map[Maximum("c1")].value.get() == x[ok].max()
    else:
        ops, _, _ = AnalysisRunner._build_scan_ops(table, analyzers)
        run_scan(table, ops, chunk_rows=chunk_rows)
        dispatches = 3
    assert SCAN_STATS.plane_ops - before == dispatches * (len(analyzers) - 1)
