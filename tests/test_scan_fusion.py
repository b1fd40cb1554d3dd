"""Scan-fusion assertions via pass accounting — the analogue of the
reference's SparkMonitor job-count tests (AnalysisRunnerTests.scala:51-120:
6 shareable analyzers fused = 1 job; grouping analyzers = 2 jobs)."""

import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    Compliance,
    CountDistinct,
    DataType,
    Maximum,
    Mean,
    Minimum,
    StandardDeviation,
    Sum,
    Size,
    Uniqueness,
    UniqueValueRatio,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.ops.scan_engine import SCAN_STATS


def test_six_scan_shareable_analyzers_fuse_into_one_pass(df_with_numeric_values):
    analyzers = [
        Size(),
        Completeness("att1"),
        Minimum("att1"),
        Maximum("att1"),
        Mean("att1"),
        StandardDeviation("att1"),
    ]
    ctx = AnalysisRunner.do_analysis_run(df_with_numeric_values, analyzers)
    assert all(m.value.is_success for m in ctx.all_metrics())
    assert SCAN_STATS.scan_passes == 1
    assert SCAN_STATS.grouping_passes == 0


def test_sketches_fuse_into_the_same_pass(df_with_numeric_values):
    analyzers = [
        Size(),
        Mean("att1"),
        ApproxCountDistinct("att1"),
        DataType("att1"),
        Compliance("c", "att1 > 3"),
        Sum("att2"),
    ]
    ctx = AnalysisRunner.do_analysis_run(df_with_numeric_values, analyzers)
    assert all(m.value.is_success for m in ctx.all_metrics())
    assert SCAN_STATS.scan_passes == 1


def test_grouping_analyzers_share_one_frequency_pass(df_with_unique_columns):
    analyzers = [
        Uniqueness(("nonUnique",)),
        UniqueValueRatio(("nonUnique",)),
        CountDistinct(("nonUnique",)),
    ]
    ctx = AnalysisRunner.do_analysis_run(df_with_unique_columns, analyzers)
    assert all(m.value.is_success for m in ctx.all_metrics())
    assert SCAN_STATS.grouping_passes == 1
    assert SCAN_STATS.scan_passes == 0


def test_different_groupings_get_separate_passes(df_with_unique_columns):
    analyzers = [
        Uniqueness(("unique",)),
        Uniqueness(("nonUnique",)),
        Uniqueness(("unique", "nonUnique")),
    ]
    AnalysisRunner.do_analysis_run(df_with_unique_columns, analyzers)
    assert SCAN_STATS.grouping_passes == 3


def test_mixed_workload_pass_accounting(df_with_unique_columns):
    analyzers = [
        Size(),
        Completeness("unique"),
        Uniqueness(("nonUnique",)),
        UniqueValueRatio(("nonUnique",)),
    ]
    AnalysisRunner.do_analysis_run(df_with_unique_columns, analyzers)
    assert SCAN_STATS.scan_passes == 1
    assert SCAN_STATS.grouping_passes == 1


def test_precondition_failures_do_not_trigger_passes(df_with_numeric_values):
    analyzers = [Completeness("missing_col"), Minimum("also_missing")]
    ctx = AnalysisRunner.do_analysis_run(df_with_numeric_values, analyzers)
    assert all(m.value.is_failure for m in ctx.all_metrics())
    assert SCAN_STATS.scan_passes == 0


def test_persisted_table_scans_from_hbm():
    """persist() ships the table once; subsequent scans move zero host
    bytes and produce identical metrics (the df.persist() analogue)."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(11)
    n = 4096
    mask = np.ones(n, dtype=np.bool_)
    mask[rng.integers(0, n, 40)] = False
    table = ColumnarTable([
        Column("a", DType.FRACTIONAL, values=rng.normal(5.0, 2.0, n), mask=mask),
        Column("b", DType.INTEGRAL, values=rng.integers(0, 1000, n)),
    ])
    analyzers = [
        Size(), Completeness("a"), Mean("a"), StandardDeviation("a"),
        Minimum("b"), Maximum("b"), Sum("b"),
    ]

    streamed = AnalysisRunner.do_analysis_run(table, analyzers)

    table.persist()
    assert table.is_persisted
    SCAN_STATS.reset()
    resident = AnalysisRunner.do_analysis_run(table, analyzers)
    assert SCAN_STATS.scan_passes == 1
    assert SCAN_STATS.resident_passes == 1
    assert SCAN_STATS.bytes_packed == 0  # nothing re-shipped
    table.unpersist()
    assert not table.is_persisted

    for a in analyzers:
        va = streamed.metric_map[a].value.get()
        vb = resident.metric_map[a].value.get()
        assert va == vb or abs(va - vb) < 1e-12, (a, va, vb)


def test_profiler_persists_across_passes():
    """The 3-pass profiler auto-persists: passes 2..N read from HBM."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.profiles.profiler import ColumnProfiler

    rng = np.random.default_rng(13)
    n = 2048
    table = ColumnarTable([
        Column("x", DType.FRACTIONAL, values=rng.normal(0.0, 1.0, n)),
        Column("y", DType.INTEGRAL, values=rng.integers(0, 50, n)),
    ])
    SCAN_STATS.reset()
    profiles = ColumnProfiler.profile(table)
    assert profiles.profiles["x"].completeness == 1.0
    # pass 1 streams (persist transfer), pass 2 reads from HBM
    assert SCAN_STATS.resident_passes >= 2
    assert not table.is_persisted  # auto-persist cleaned up


def test_repeated_runs_reuse_compiled_program():
    """N identical runs over a persisted table -> 1 traced/compiled
    program (the analogue of SparkMonitor job accounting guarding against
    recompiles; SURVEY §4)."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(17)
    n = 1024
    table = ColumnarTable([
        Column("a", DType.FRACTIONAL, values=rng.normal(size=n)),
        Column("b", DType.INTEGRAL, values=rng.integers(0, 9, n)),
    ]).persist()
    analyzers = [Size(), Mean("a"), Minimum("a"), Maximum("b"), Sum("b")]

    SCAN_STATS.reset()
    first = AnalysisRunner.do_analysis_run(table, analyzers)
    for _ in range(3):
        again = AnalysisRunner.do_analysis_run(table, analyzers)
    assert SCAN_STATS.programs_built == 1
    assert SCAN_STATS.programs_reused == 3
    for a in analyzers:
        assert first.metric_map[a].value.get() == again.metric_map[a].value.get()
    table.unpersist()


def test_high_cardinality_grouping_sorts_on_device():
    """Sparse (huge key-space) grouping runs the sort on device — no host
    np.unique — and numeric code-building also rides the device sort
    (BASELINE config #4 shape; SURVEY §2.14.2)."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.segment import DENSE_KEYSPACE_LIMIT, group_counts

    rng = np.random.default_rng(31)
    n = 20_000
    # two high-cardinality numeric columns: key space >> dense limit
    a = rng.integers(0, n, n).astype(np.int64)
    b = rng.integers(0, n, n).astype(np.int64)
    table = ColumnarTable([
        Column("a", DType.INTEGRAL, values=a),
        Column("b", DType.INTEGRAL, values=b),
    ])
    SCAN_STATS.reset()
    freqs, num_rows = group_counts(table, ["a", "b"])
    # 2 column-code device sorts + 1 matrix RLE device sort
    assert SCAN_STATS.device_sort_passes == 3
    assert num_rows == n
    # cross-check against a pure-host group-by
    import collections
    expected = collections.Counter(zip(a.tolist(), b.tolist()))
    assert len(freqs) == len(expected)
    for (ka, kb), cnt in list(expected.items())[:100]:
        assert freqs[(ka, kb)] == cnt


def test_numeric_grouping_collapses_nan_to_one_group():
    """NaN values (possible with user-supplied masks) form ONE distinct
    group, matching np.unique equal_nan semantics (review finding r2)."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.segment import column_key_codes, group_counts

    nan = float("nan")
    col = Column(
        "x", DType.FRACTIONAL,
        values=np.array([1.0, nan, nan, 2.0, nan]),
        mask=np.ones(5, dtype=bool),
    )
    codes, values = column_key_codes(col)
    assert len(values) == 3  # 1.0, 2.0, nan
    assert codes[1] == codes[2] == codes[4]

    table = ColumnarTable([col])
    freqs, num_rows = group_counts(table, ["x"])
    assert num_rows == 5
    nan_counts = [c for (v,), c in freqs.items() if v == v is False or (isinstance(v, float) and v != v)]
    assert nan_counts == [3]


def test_streaming_batches_reuse_global_program():
    """Incremental monitoring: the same suite over successive same-schema
    batches traces ONCE (global program cache). String ops qualify too —
    their dictionary LUTs enter the program as ARGUMENTS (ops/lut_cache),
    so per-batch dictionaries do not bake into the trace."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    def batch(seed):
        rng = np.random.default_rng(seed)
        return ColumnarTable([
            Column("v", DType.FRACTIONAL, values=rng.normal(size=512)),
        ])

    from deequ_tpu.ops.scan_engine import _GLOBAL_PROGRAMS

    _GLOBAL_PROGRAMS.clear()  # module-level cache: isolate from other tests
    analyzers = [Size(), Mean("v"), StandardDeviation("v"), Minimum("v")]
    SCAN_STATS.reset()
    results = []
    for seed in range(4):
        ctx = AnalysisRunner.do_analysis_run(batch(seed), analyzers)
        results.append(ctx.metric_map[Mean("v")].value.get())
    assert SCAN_STATS.programs_built == 1
    assert SCAN_STATS.programs_reused == 3
    # correctness: each batch got its OWN mean, not a cached value
    expected = [float(np.random.default_rng(s).normal(size=512).mean())
                for s in range(4)]
    assert np.allclose(results, expected)

    # string columns reuse too (LUTs are inputs, not trace constants) —
    # and each batch must still see ITS OWN dictionary, not a cached one
    from deequ_tpu.analyzers import PatternMatch

    SCAN_STATS.reset()
    matches = []
    for seed in range(3):
        rng = np.random.default_rng(seed)
        strings = [
            ("ok" if x else f"bad{seed}") for x in rng.integers(0, 2, 64)
        ]
        t = ColumnarTable.from_pydict({"s": strings})
        ctx = AnalysisRunner.do_analysis_run(
            t, [Completeness("s"), PatternMatch("s", "^ok$")]
        )
        expect = sum(1 for s in strings if s == "ok") / len(strings)
        got = ctx.metric_map[PatternMatch("s", "^ok$")].value.get()
        assert got == expect, (seed, got, expect)
        matches.append(got)
    assert SCAN_STATS.programs_built == 1
    assert SCAN_STATS.programs_reused == 2
    assert len(set(matches)) > 1  # genuinely different per-batch answers


def test_count_stats_fast_path_matches_full_path():
    """Without state persistence, grouping analyzers run from device count
    aggregates; with a state provider they take the full frequency-table
    path. Both agree."""
    import numpy as np

    from deequ_tpu.analyzers import (
        CountDistinct, Distinctness, Entropy, UniqueValueRatio, Uniqueness,
    )
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.states import InMemoryStateProvider

    rng = np.random.default_rng(37)
    n = 30_000
    table = ColumnarTable([
        Column("k", DType.INTEGRAL, values=rng.integers(0, n, n)),
    ])
    analyzers = [
        Uniqueness(("k",)), UniqueValueRatio(("k",)), Distinctness(("k",)),
        CountDistinct(("k",)), Entropy("k"),
    ]
    fast = AnalysisRunner.do_analysis_run(table, analyzers)
    full = AnalysisRunner.do_analysis_run(
        table, analyzers, save_states_with=InMemoryStateProvider()
    )
    for a in analyzers:
        vf = fast.metric_map[a].value.get()
        vz = full.metric_map[a].value.get()
        assert abs(vf - vz) < 1e-12, (a, vf, vz)


def test_columnar_frequency_state_matches_dict_semantics():
    """Round-4 columnar FrequenciesAndNumRows: vectorized merge and MI must
    agree exactly with the dict-based semantics on a mixed-type grouping
    with nulls, and the state provider path must match the fast path."""
    import numpy as np

    from deequ_tpu.analyzers import MutualInformation, Uniqueness
    from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.segment import group_counts_state
    from deequ_tpu.states import InMemoryStateProvider

    rng = np.random.default_rng(9)
    n = 20_000
    codes = rng.integers(-1, 500, n).astype(np.int32)  # -1 = null
    dictionary = np.array([f"k{i}" for i in range(500)], dtype=object)
    ints = rng.integers(0, 50, n)
    int_mask = rng.random(n) > 0.05
    table = ColumnarTable([
        Column("s", DType.STRING, codes=codes, dictionary=dictionary),
        Column("i", DType.INTEGRAL, values=ints, mask=int_mask),
    ])

    # columnar state == dict state
    state = group_counts_state(table, ["s", "i"])
    expect = {}
    for c, v, m in zip(codes.tolist(), ints.tolist(), int_mask.tolist()):
        key = (None if c < 0 else f"k{c}", v if m else None)
        if key == (None, None):
            continue
        expect[key] = expect.get(key, 0) + 1
    assert state.as_dict() == expect

    # vectorized merge == dict merge
    half = n // 2
    t1 = ColumnarTable([
        Column("s", DType.STRING, codes=codes[:half], dictionary=dictionary),
        Column("i", DType.INTEGRAL, values=ints[:half], mask=int_mask[:half]),
    ])
    t2 = ColumnarTable([
        Column("s", DType.STRING, codes=codes[half:], dictionary=dictionary),
        Column("i", DType.INTEGRAL, values=ints[half:], mask=int_mask[half:]),
    ])
    merged = group_counts_state(t1, ["s", "i"]).sum(group_counts_state(t2, ["s", "i"]))
    assert merged == state

    # stateful run == fast-path run
    a = Uniqueness(("s",))
    fast = AnalysisRunner.do_analysis_run(table, [a]).metric_map[a].value.get()
    stateful = AnalysisRunner.do_analysis_run(
        table, [a], save_states_with=InMemoryStateProvider()
    ).metric_map[a].value.get()
    assert fast == stateful

    # vectorized MI == dict-loop MI
    mi_an = MutualInformation("s", "i")
    mi = AnalysisRunner.do_analysis_run(table, [mi_an]).metric_map[mi_an].value.get()
    import math
    total = state.num_rows
    ma, mb = {}, {}
    for (va, vb), c in state.frequencies:
        ma[va] = ma.get(va, 0) + c
        mb[vb] = mb.get(vb, 0) + c
    ref = 0.0
    for (va, vb), c in state.frequencies:
        if va is None or vb is None:
            continue
        pxy = c / total
        ref += pxy * math.log(pxy / ((ma[va] / total) * (mb[vb] / total)))
    assert abs(mi - ref) < 1e-12


def test_pair_sum_inf_columns_keep_ieee_semantics():
    """Columns containing +/-inf stay on the pair path (pair_safe checks
    finite values only); sums must return the IEEE result (inf / NaN), not
    the NaN that TwoSum's inf - inf error channel produces."""
    import numpy as np

    from deequ_tpu.analyzers import Mean, Sum
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    base = [1.0, 2.0, 3.0] * 64
    pos_inf = ColumnarTable(
        [Column("x", DType.FRACTIONAL, values=np.array(base + [np.inf]))]
    )
    v = AnalysisRunner.do_analysis_run(pos_inf, [Sum("x")]).metric_map[
        Sum("x")
    ].value.get()
    assert v == np.inf
    mixed = ColumnarTable(
        [Column("x", DType.FRACTIONAL, values=np.array(base + [np.inf, -np.inf]))]
    )
    m = AnalysisRunner.do_analysis_run(mixed, [Mean("x")]).metric_map[
        Mean("x")
    ].value.get()
    assert np.isnan(m)


def test_frequency_merge_all_null_side_adopts_typed_keys():
    """Merging a legacy all-null-keys state (string-dtype default) with a
    typed int state must keep int keys, not stringify them; genuinely
    mismatched key types refuse loudly."""
    import pytest as _pytest

    from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows

    legacy = FrequenciesAndNumRows.from_dict(("g",), {(None,): 3}, 3)
    typed = FrequenciesAndNumRows.from_dict(("g",), {(5,): 2}, 2)
    merged = legacy.sum(typed)
    assert merged.as_dict() == {(None,): 3, (5,): 2}

    strs = FrequenciesAndNumRows.from_dict(("g",), {("a",): 1}, 1)
    with _pytest.raises(ValueError, match="mismatched group-key types"):
        typed.sum(strs)


def test_sparse_grouping_fetch_is_bounded_by_group_count():
    """The sparse (keyspace > 2^22) group-by must fetch O(k*G) bytes from
    device — group representatives + counts — never the O(k*n) sorted code
    matrix (r4 verdict: the scaling cliff between 16M and 100M rows).
    Reference analogue: the shuffle group-by's output is one row per group
    (GroupingAnalyzers.scala:66-78)."""
    import collections

    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.segment import (
        DENSE_KEYSPACE_LIMIT,
        SMALL_N_FETCH_LIMIT,
        _pad_group_count,
        group_count_stats,
        group_counts,
    )

    rng = np.random.default_rng(47)
    n = SMALL_N_FETCH_LIMIT + 8_192  # forces the two-phase O(G) fetch path
    card = 2_100  # 2100*2100 distinct pairs possible > 2^22 keyspace
    # draw pairs from a SMALL pool of distinct keys so G << n
    pool_a = rng.integers(0, card, 512)
    pool_b = rng.integers(0, card, 512)
    pick = rng.integers(0, 512, n)
    strs_a = np.array([f"a{v:05d}" for v in pool_a[pick]])
    strs_b = np.array([f"b{v:05d}" for v in pool_b[pick]])
    dict_a = np.unique(strs_a)
    dict_b = np.unique(strs_b)
    code_a = np.searchsorted(dict_a, strs_a).astype(np.int32)
    code_b = np.searchsorted(dict_b, strs_b).astype(np.int32)
    # pad dictionaries so the keyspace product exceeds the dense limit
    pad_a = np.array([f"za{i}" for i in range(card - len(dict_a))])
    pad_b = np.array([f"zb{i}" for i in range(card - len(dict_b))])
    table = ColumnarTable([
        Column("a", DType.STRING, codes=code_a,
               dictionary=np.concatenate([dict_a, pad_a])),
        Column("b", DType.STRING, codes=code_b,
               dictionary=np.concatenate([dict_b, pad_b])),
    ])
    assert card * card > DENSE_KEYSPACE_LIMIT

    SCAN_STATS.reset()
    freqs, num_rows = group_counts(table, ["a", "b"])
    expected = collections.Counter(zip(strs_a.tolist(), strs_b.tolist()))
    assert num_rows == n
    assert dict(freqs) == dict(expected)
    g_pad = _pad_group_count(len(expected))
    # fetched: (k=2, G_pad) reps + (G_pad,) counts, int64 -> 24*G_pad, plus
    # slack for scalar round trips; the O(k*n) alternative would be ~1.8MB
    bound = 24 * g_pad + 4096
    assert SCAN_STATS.bytes_fetched <= bound, (
        SCAN_STATS.bytes_fetched, bound)
    assert SCAN_STATS.bytes_fetched < 2 * n  # far under any O(n) fetch

    # count-stats flavor: four scalars only
    SCAN_STATS.reset()
    stats = group_count_stats(table, ["a", "b"])
    assert stats.num_groups == len(expected)
    assert stats.singletons == sum(1 for c in expected.values() if c == 1)
    p = np.array(sorted(expected.values()), dtype=np.float64) / n
    assert abs(stats.entropy - float(-(p * np.log(p)).sum())) < 1e-9
    assert SCAN_STATS.bytes_fetched <= 64


def test_numeric_unique_inverse_two_phase_large_n():
    """Above SMALL_N_FETCH_LIMIT the numeric code-builder gathers distinct
    values on device (O(U) fetch) instead of fetching the full sorted
    column; codes and uniques must match the small-n path exactly."""
    import numpy as np

    from deequ_tpu.ops.segment import SMALL_N_FETCH_LIMIT, _device_unique_inverse

    rng = np.random.default_rng(53)
    n = SMALL_N_FETCH_LIMIT + 1_000
    vals = rng.integers(0, 700, n).astype(np.float64)
    vals[::97] = np.nan  # NaNs collapse to one group
    mask = np.ones(n, dtype=bool)
    mask[::101] = False

    uniques, codes = _device_unique_inverse(vals, mask)
    # reference: numpy unique over the valid slice (equal_nan collapses)
    ref = np.unique(vals[mask])
    nan_ct = np.isnan(ref).sum()
    ref = np.concatenate([ref[: len(ref) - nan_ct], ref[len(ref) - nan_ct:][:1]])
    assert len(uniques) == len(ref)
    np.testing.assert_array_equal(np.sort(uniques[~np.isnan(uniques)]),
                                  ref[~np.isnan(ref)])
    # codes decode back to the original values on valid rows
    assert (codes[~mask] == 0).all()
    valid_codes = codes[mask]
    assert (valid_codes > 0).all()
    decoded = uniques[valid_codes - 1]
    vv = vals[mask]
    same = (decoded == vv) | (np.isnan(decoded) & np.isnan(vv))
    assert same.all()


def test_advice_r4_low_findings_regressions():
    """r4 advisor low findings: NaN dict-keys collapse like the columnar
    path; int64-min merge guard doesn't wrap; unsigned >= 2^63 keys refuse
    serde; histogram boundary ties break deterministically by key."""
    import numpy as np
    import pytest

    from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows, Histogram

    # two distinct float('nan') objects are distinct dict keys -> ONE group
    n1, n2 = float("nan"), float("nan")
    st = FrequenciesAndNumRows.from_dict(("x",), {(n1,): 2, (n2,): 3, (1.0,): 1}, 6)
    assert st.num_groups == 2
    assert sorted(st.counts.tolist()) == [1, 5]

    # int64 min in an int/float merge: abs() used to wrap negative and
    # skip the 2^53 collapse guard
    big = FrequenciesAndNumRows(
        ("x",), (np.array([np.iinfo(np.int64).min]),),
        (np.zeros(1, dtype=bool),), np.array([1]), 1)
    flt = FrequenciesAndNumRows(
        ("x",), (np.array([0.5]),), (np.zeros(1, dtype=bool),),
        np.array([1]), 1)
    with pytest.raises(ValueError, match="2\\^53"):
        big.sum(flt)

    # unsigned >= 2^63 keys: loud refusal, not silent wrap
    from deequ_tpu.states.serde import serialize_state
    ust = FrequenciesAndNumRows(
        ("x",), (np.array([2 ** 63], dtype=np.uint64),),
        (np.zeros(1, dtype=bool),), np.array([1]), 1)
    with pytest.raises(ValueError, match="unsigned"):
        serialize_state(ust)

    # histogram detail-bin boundary tie: selection is by stringified key,
    # stable regardless of group order in the state
    def hist_for(order):
        vals = np.array([f"k{i}" for i in order])
        counts = np.array([5] + [3] * (len(order) - 1))  # all but one tied
        st = FrequenciesAndNumRows(
            ("c",), (vals,), (np.zeros(len(order), dtype=bool),), counts, 14)
        m = Histogram("c", max_detail_bins=3).compute_metric_from(st)
        return set(m.value.get().values.keys())

    sel_a = hist_for([0, 1, 2, 3])
    sel_b = hist_for([0, 3, 2, 1])  # same data, different state order
    assert sel_a == sel_b


def test_histogram_fast_path_matches_state_path_at_boundary_tie():
    """Tie semantics at the max_detail_bins boundary: the device fast
    path breaks ties by rank order (reference top() parity) while the
    state path breaks them deterministically by stringified key; both
    must agree on every NON-tied bin and on all counts. (A fallback
    unifying them was reverted: high-cardinality columns are always
    boundary-tied, and it cost 10x on BASELINE config 4.)"""
    import numpy as np

    from deequ_tpu.analyzers.grouping import Histogram
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.states import InMemoryStateProvider

    # k9 x5, then k1,k2,k3 x3 each: bins=3 -> tie at the boundary
    raw = ["k9"] * 5 + ["k1", "k2", "k3"] * 3
    dic = np.unique(np.array(raw))
    codes = np.searchsorted(dic, np.array(raw)).astype(np.int32)
    t = ColumnarTable([Column("c", DType.STRING, codes=codes, dictionary=dic)])

    h = Histogram("c", max_detail_bins=3)
    fast = h.calculate(t).value.get()
    stateful = h.calculate(
        t, save_states_with=InMemoryStateProvider()
    ).value.get()
    assert fast.number_of_bins == stateful.number_of_bins == 4
    # the untied bin agrees; tied bins carry identical counts
    assert fast.values["k9"] == stateful.values["k9"]
    assert len(fast.values) == len(stateful.values) == 3
    assert {v.absolute for v in fast.values.values()} == {5, 3}
    assert {v.absolute for v in stateful.values.values()} == {5, 3}
    # state path is DETERMINISTIC: lowest stringified keys fill the ties
    assert set(stateful.values) == {"k9", "k1", "k2"}


def test_sparse_and_dense_grouping_agree_randomized(monkeypatch):
    """Property sweep over random shapes/dtypes/null patterns: the sparse
    (device RLE + O(G) gather) and dense (bincount) group-by paths must
    produce identical frequency states and count stats. Forces each path
    via DENSE_KEYSPACE_LIMIT."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops import segment

    rng = np.random.default_rng(2024)
    for case in range(6):
        n = int(rng.integers(200, 3000))
        card = int(rng.integers(2, 40))
        cols = []
        names = []
        for j in range(int(rng.integers(1, 3))):
            name = f"g{j}"
            kind = rng.integers(0, 3)
            if kind == 0:
                codes = rng.integers(0, card, n).astype(np.int32)
                null_rate = rng.random() * 0.2
                codes[rng.random(n) < null_rate] = -1
                dic = np.array([f"v{i}" for i in range(card)])
                cols.append(Column(name, DType.STRING, codes=codes,
                                   dictionary=dic))
            elif kind == 1:
                vals = rng.integers(-5, card, n).astype(np.int64)
                mask = rng.random(n) > 0.1
                cols.append(Column(name, DType.INTEGRAL, values=vals,
                                   mask=mask))
            else:
                vals = np.round(rng.normal(0, 2, n), 1)
                mask = rng.random(n) > 0.1
                cols.append(Column(name, DType.FRACTIONAL, values=vals,
                                   mask=mask))
            names.append(name)
        table = ColumnarTable(cols)

        # force the DEVICE paths (small inputs otherwise take the host
        # fast path below HOST_GROUP_LIMIT — covered separately below)
        monkeypatch.setattr(segment, "HOST_GROUP_LIMIT", 0)
        monkeypatch.setattr(segment, "DENSE_KEYSPACE_LIMIT", 1 << 22)
        dense_state = segment.group_counts_state(table, names)
        dense_stats = segment.group_count_stats(table, names)
        monkeypatch.setattr(segment, "DENSE_KEYSPACE_LIMIT", 0)  # force sparse
        before = SCAN_STATS.device_sort_passes
        sparse_state = segment.group_counts_state(table, names)
        sparse_stats = segment.group_count_stats(table, names)
        # the sparse branch uniquely runs device RLE sorts — prove the
        # forcing took (guards against the comparison silently becoming
        # dense-vs-dense after a refactor)
        assert SCAN_STATS.device_sort_passes >= before + 2, case

        assert dense_state.as_dict() == sparse_state.as_dict(), case
        assert dense_state.num_rows == sparse_state.num_rows
        assert dense_stats.num_groups == sparse_stats.num_groups, case
        assert dense_stats.singletons == sparse_stats.singletons, case
        if dense_stats.num_groups:
            assert abs(dense_stats.entropy - sparse_stats.entropy) < 1e-9

        # host fast path (small inputs skip the device entirely) must
        # agree with both device paths. Rebuild the table from FRESH
        # Column objects: the memoized _typed_distinct cache on the old
        # columns would otherwise hand the host run the device-derived
        # key arrays and mask any decoded-value drift (review catch).
        fresh = ColumnarTable([
            Column(c.name, c.dtype, values=getattr(c, "values", None),
                   mask=getattr(c, "mask", None), codes=getattr(c, "codes", None),
                   dictionary=getattr(c, "dictionary", None))
            if c.dtype == DType.STRING else
            Column(c.name, c.dtype, values=c.values.copy(), mask=c.mask.copy())
            for c in cols
        ])
        monkeypatch.setattr(segment, "HOST_GROUP_LIMIT", 1 << 14)
        host_state = segment.group_counts_state(fresh, names)
        host_stats = segment.group_count_stats(fresh, names)
        assert host_state.as_dict() == dense_state.as_dict(), case
        assert host_stats.num_groups == dense_stats.num_groups, case
        assert host_stats.singletons == dense_stats.singletons, case
        if dense_stats.num_groups:
            assert abs(host_stats.entropy - dense_stats.entropy) < 1e-9


def _fold_table(n=32_768, seed=3):
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(seed)
    mask = np.ones(n, dtype=bool)
    mask[rng.integers(0, n, n // 100)] = False
    return ColumnarTable([
        Column("a", DType.FRACTIONAL, values=rng.normal(5.0, 2.0, n),
               mask=mask),
        Column("b", DType.INTEGRAL, values=rng.integers(0, 1000, n)),
    ])


def _fold_analyzers():
    return [
        Size(), Completeness("a"), Mean("a"), StandardDeviation("a"),
        Minimum("a"), Maximum("b"), Sum("b"), ApproxCountDistinct("b"),
    ]


def _fold_ops(table, analyzers):
    ops = [a.scan_op(table) for a in analyzers]
    for op, a in zip(ops, analyzers):
        op.cache_key = a
    return ops


def test_multi_chunk_resident_scan_is_one_fetch():
    """The one-fetch-per-scan contract (ISSUE 4 tentpole): a >=8-chunk
    device-resident scan of device-foldable ops folds its chunk partials
    ON device and materializes exactly one device->host result."""
    from deequ_tpu.ops.scan_engine import persist_table

    table = _fold_table()
    persist_table(table, chunk_rows=4096)  # 32768/4096 = 8 chunks
    analyzers = _fold_analyzers()
    try:
        SCAN_STATS.reset()
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        assert all(m.value.is_success for m in ctx.all_metrics())
        assert SCAN_STATS.scan_passes == 1
        assert SCAN_STATS.resident_passes == 1
        assert SCAN_STATS.chunks_processed == 8
        assert SCAN_STATS.device_fetches == 1, SCAN_STATS.device_fetches
    finally:
        table.unpersist()


def test_resident_scan_bit_identical_to_host_packed_scan():
    """A persisted multi-chunk table takes the same per-chunk step and
    the same left-to-right device fold as the host-packed scan of the
    same rows at the same chunk_rows: every leaf of every op's state is
    BIT-identical (docs/numerics.md; no resident scan is excused)."""
    import jax
    import numpy as np

    from deequ_tpu.analyzers import Correlation
    from deequ_tpu.ops.scan_engine import persist_table, run_scan

    table = _fold_table()
    analyzers = _fold_analyzers() + [Correlation("a", "b")]
    ops = _fold_ops(table, analyzers)

    SCAN_STATS.reset()
    packed = run_scan(table, ops, chunk_rows=4096)
    assert SCAN_STATS.resident_passes == 0
    assert SCAN_STATS.bytes_packed > 0

    persist_table(table, chunk_rows=4096)
    try:
        SCAN_STATS.reset()
        resident = run_scan(table, ops)
        assert SCAN_STATS.resident_passes == 1
        assert SCAN_STATS.bytes_packed == 0
        assert SCAN_STATS.chunks_processed == 8
        assert SCAN_STATS.device_fetches == 1
    finally:
        table.unpersist()
    for i, (x, y) in enumerate(zip(packed, resident)):
        for ap, ar in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            ap, ar = np.asarray(ap), np.asarray(ar)
            assert ap.dtype == ar.dtype, (i, ap.dtype, ar.dtype)
            assert np.array_equal(ap, ar, equal_nan=True), (i, ap, ar)


@pytest.mark.parametrize("name,value", [
    ("DEEQU_TPU_DEVICE_FOLD", "0"),
    ("DEEQU_TPU_FUSED_RESIDENT", "0"),
    ("DEEQU_TPU_TRANSFER_F32", "1"),
    ("DEEQU_TPU_COMPUTE", "f64"),
])
def test_closed_hatches_steer_nothing(name, value, monkeypatch):
    """The four A/B switches of the scan path are gone from the registry,
    and exporting one changes neither the packer's layout, nor the fetch
    count, nor a bit of the result."""
    import jax
    import numpy as np

    from deequ_tpu.envcfg import registry_snapshot
    from deequ_tpu.ops.scan_engine import _ChunkPacker, persist_table, run_scan

    def scan():
        table = _fold_table()
        cols = {n: table[n] for n in table.column_names}
        layout = _ChunkPacker(cols, 4096).layout()
        ops = _fold_ops(table, _fold_analyzers())
        persist_table(table, chunk_rows=4096)
        try:
            SCAN_STATS.reset()
            result = run_scan(table, ops)
            counts = (SCAN_STATS.device_fetches, SCAN_STATS.chunks_processed,
                      SCAN_STATS.programs_built + SCAN_STATS.programs_reused)
        finally:
            table.unpersist()
        return layout, counts, [np.asarray(x) for x in jax.tree.leaves(result)]

    layout, counts, leaves = scan()
    assert counts == (1, 8, 1)
    assert layout["pair"] == ("a",) and layout["wide"] == ()
    monkeypatch.setenv(name, value)
    assert name not in registry_snapshot()
    layout_set, counts_set, leaves_set = scan()
    assert layout_set == layout and counts_set == counts
    assert all(x.tobytes() == y.tobytes() for x, y in zip(leaves, leaves_set))


def test_device_fold_bit_identical_to_host_fold(monkeypatch):
    """Device-folded partials (per-chunk merge + gather capacity) must be
    BIT-identical to the host fold at the same chunking — sum/min/max
    leaves merge with the same IEEE f64 ops in the same left-to-right
    order, gather leaves concatenate in the same chunk order."""
    import jax
    import numpy as np

    import deequ_tpu.ops.scan_engine as se
    from deequ_tpu.analyzers import Correlation
    from deequ_tpu.ops.scan_engine import run_scan

    table = _fold_table()
    analyzers = _fold_analyzers() + [Correlation("a", "b")]
    ops = _fold_ops(table, analyzers)

    with monkeypatch.context() as host_fold:
        host_fold.setattr(se, "_folds_on_device", lambda ops: False)
        SCAN_STATS.reset()
        host = run_scan(table, ops, chunk_rows=4096)
    host_fetches = SCAN_STATS.device_fetches
    assert host_fetches == 8  # one per chunk: what the fold removes

    SCAN_STATS.reset()
    folded = run_scan(table, ops, chunk_rows=4096)
    assert SCAN_STATS.device_fetches == 1
    assert SCAN_STATS.chunks_processed == 8
    for i, (x, y) in enumerate(zip(host, folded)):
        for ah, af in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            ah, af = np.asarray(ah), np.asarray(af)
            assert ah.dtype == af.dtype, (i, ah.dtype, af.dtype)
            assert np.array_equal(ah, af, equal_nan=True), (i, ah, af)


def test_compact_ops_keep_host_fold_path():
    """Ops with a compact() hook (KLL) are not device-foldable: the scan
    keeps the per-chunk host fold (and its per-chunk fetches) and stays
    correct — nothing regresses for them."""
    import numpy as np

    from deequ_tpu.analyzers import ApproxQuantile
    from deequ_tpu.ops.scan_engine import device_foldable

    table = _fold_table(n=16_384)
    analyzers = [Size(), Mean("a"), ApproxQuantile("a", 0.5)]
    ops = _fold_ops(table, analyzers)
    assert not all(device_foldable(op) for op in ops)

    SCAN_STATS.reset()
    from deequ_tpu.ops.scan_engine import run_scan

    results = run_scan(table, ops, chunk_rows=4096)
    assert SCAN_STATS.device_fetches == 4  # host fold: one per chunk
    median = analyzers[2].state_from_scan_result(results[2])
    assert median is not None
    # sanity: the sketch median lands near the true one
    vals = np.sort(table["a"].values[table["a"].mask])
    assert abs(median.sketch.quantile(0.5) - vals[len(vals) // 2]) < 0.2


def test_scan_window_validation_and_env(monkeypatch):
    """DEEQU_TPU_SCAN_WINDOW / run_scan(window=...) configure the
    pipelined-dispatch window; invalid values refuse loudly."""
    import pytest

    from deequ_tpu.ops.scan_engine import (
        DEFAULT_SCAN_WINDOW,
        _resolve_scan_window,
        run_scan,
    )

    assert _resolve_scan_window() == DEFAULT_SCAN_WINDOW == 3
    assert _resolve_scan_window(7) == 7
    monkeypatch.setenv("DEEQU_TPU_SCAN_WINDOW", "5")
    assert _resolve_scan_window() == 5
    assert _resolve_scan_window(2) == 2  # explicit argument wins
    monkeypatch.setenv("DEEQU_TPU_SCAN_WINDOW", "0")
    with pytest.raises(ValueError, match=">= 1"):
        _resolve_scan_window()
    monkeypatch.setenv("DEEQU_TPU_SCAN_WINDOW", "soon")
    with pytest.raises(ValueError, match="integer"):
        _resolve_scan_window()
    monkeypatch.delenv("DEEQU_TPU_SCAN_WINDOW")

    table = _fold_table(n=8192)
    ops = _fold_ops(table, [Size(), Mean("a")])
    with pytest.raises(ValueError, match=">= 1"):
        run_scan(table, ops, window=0)
    # a tight window still computes the right thing (throttle path)
    one = run_scan(table, ops, chunk_rows=1024, window=1)
    three = run_scan(table, ops, chunk_rows=1024, window=3)
    assert float(one[0]["n"]) == float(three[0]["n"]) == 8192


def test_fetch_deferred_isolates_one_scans_fold_failure():
    """One deferred scan's fold raising marks only THAT scan failed at
    result(); sibling scans drained in the same batched fetch succeed."""
    import pytest

    from deequ_tpu.ops.scan_engine import fetch_deferred, run_scan

    table = _fold_table(n=8192)
    analyzers = _fold_analyzers()
    good = run_scan(table, _fold_ops(table, analyzers), defer=True,
                    chunk_rows=4096)
    bad = run_scan(table, _fold_ops(table, analyzers), defer=True,
                   chunk_rows=2048)

    boom = RuntimeError("injected fold failure")

    def exploding_drain(device_result):
        raise boom

    bad._folder.drain = exploding_drain
    fetch_deferred([good, bad])

    results = good.result()  # sibling unaffected
    assert float(results[0]["n"]) == 8192
    with pytest.raises(RuntimeError, match="injected fold failure"):
        bad.result()
    # non-retryable: a second result() must re-raise, never half-refold
    with pytest.raises(RuntimeError, match="injected fold failure"):
        bad.result()


def test_fetch_deferred_keyboard_interrupt_marks_scan_failed():
    """A KeyboardInterrupt mid-drain propagates out of fetch_deferred
    AND leaves the interrupted scan marked failed (non-retryable): a
    retry would double-fold the half-drained accumulator."""
    import pytest

    from deequ_tpu.ops.scan_engine import fetch_deferred, run_scan

    table = _fold_table(n=8192)
    analyzers = _fold_analyzers()
    scan = run_scan(table, _fold_ops(table, analyzers), defer=True,
                    chunk_rows=4096)

    def interrupted_drain(device_result):
        raise KeyboardInterrupt()

    scan._folder.drain = interrupted_drain
    with pytest.raises(KeyboardInterrupt):
        fetch_deferred([scan])
    assert scan._done
    with pytest.raises(KeyboardInterrupt):
        scan.result()


def test_streaming_scan_fetches_once(monkeypatch):
    """The fused streaming pass device-folds across batches: a many-batch
    stream of device-foldable ops drains once (vs once per chunk), and
    metrics match the host-folded stream bit-for-bit (same chunking)."""
    import deequ_tpu.ops.scan_engine as se
    from deequ_tpu.data.streaming import stream_table

    table = _fold_table()
    analyzers = _fold_analyzers()
    with monkeypatch.context() as host_fold:
        host_fold.setattr(se, "_folds_on_device", lambda ops: False)
        SCAN_STATS.reset()
        ref = AnalysisRunner.do_analysis_run(
            stream_table(table, 4096), analyzers)
    assert SCAN_STATS.device_fetches == 8  # host fold: one per chunk

    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(stream_table(table, 4096), analyzers)
    assert SCAN_STATS.chunks_processed == 8
    assert SCAN_STATS.device_fetches == 1
    for a in analyzers:
        assert ctx.metric_map[a].value.get() == ref.metric_map[a].value.get(), a


def test_stream_fold_capacity_overflow_drains_and_continues(monkeypatch):
    """A stream longer than the device gather capacity drains mid-flight
    and keeps folding — gather-leaf analyzers (StdDev) stay EXACT, fetches
    stay O(chunks/capacity)."""
    import deequ_tpu.ops.scan_engine as se
    from deequ_tpu.data.streaming import stream_table

    table = _fold_table()
    analyzers = _fold_analyzers()
    with monkeypatch.context() as host_fold:
        host_fold.setattr(se, "_folds_on_device", lambda ops: False)
        ref = AnalysisRunner.do_analysis_run(
            stream_table(table, 4096), analyzers)

    monkeypatch.setattr(se, "STREAM_FOLD_CAPACITY", 3)
    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(stream_table(table, 4096), analyzers)
    assert SCAN_STATS.chunks_processed == 8
    assert SCAN_STATS.device_fetches == 3  # ceil(8/3)
    for a in analyzers:
        va = ref.metric_map[a].value.get()
        vb = ctx.metric_map[a].value.get()
        # counts/extrema/gathered moments exact; f64 sum leaves may
        # regroup at the capacity restart (docs/numerics.md) — ulp only
        assert va == vb or abs(va - vb) <= 1e-12 * max(abs(va), 1.0), (
            a, va, vb)


def test_sparse_gather_falls_back_when_groups_near_rows(monkeypatch):
    """Nearly-all-distinct data: the pow2-padded O(G) gather would fetch
    up to 2n slots, more than the sorted matrix itself — the sparse path
    then takes the single-phase fetch and must stay correct."""
    import collections

    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops import segment

    n = segment.SMALL_N_FETCH_LIMIT + 5_000
    rng = np.random.default_rng(77)
    a = rng.permutation(n).astype(np.int64)   # all distinct
    b = rng.integers(0, 3, n).astype(np.int64)
    table = ColumnarTable([
        Column("a", DType.INTEGRAL, values=a),
        Column("b", DType.INTEGRAL, values=b),
    ])
    monkeypatch.setattr(segment, "DENSE_KEYSPACE_LIMIT", 0)  # force sparse
    state = segment.group_counts_state(table, ["a", "b"])
    expected = collections.Counter(zip(a.tolist(), b.tolist()))
    assert state.num_groups == len(expected) == n
    got = state.as_dict()
    for key, cnt in list(expected.items())[:50]:
        assert got[key] == cnt
