"""The HLL registers of a resident dictionary column from the entries
PRESENT in the counts its Histogram already has (PR 33).

A where-free ``ApproxCountDistinct`` over a string column of a persist()ed
table whose ``Histogram`` is in the same run leaves the fused scan: its
registers are folded, inside the Histograms' one dispatch, out of the K
dictionary entries some row holds (``segment.resident_top_k``,
``hll.registers_from_present``). The rider route must give the state and
the metric of the scan route, register for register; everything else (a
``where``, a state provider, no partner, no residency, a failed batch)
stays on the scan, which still answers. Nothing here is a chip run."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from deequ_tpu.analyzers import ApproxCountDistinct, Histogram
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.analyzers.sketches import ApproxCountDistinctState
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.ops import device_policy, hll, scan_engine, segment
from deequ_tpu.ops.scan_engine import SCAN_STATS
from deequ_tpu.parallel.mesh import ROW_AXIS, use_mesh
from deequ_tpu.states import InMemoryStateProvider

P = hll.precision_from_relative_sd()
COUNTERS = ("hll_folds", "hll_presence_folds", "device_fetches",
            "grouping_passes", "hist_onehot_dispatches",
            "hist_scatter_dispatches", "seam_grouping_count")


def counted(fn):
    before = SCAN_STATS.snapshot()
    out = fn()
    after = SCAN_STATS.snapshot()
    return out, {k: after[k] - before[k] for k in COUNTERS}


def strings(name, codes, dictionary):
    return Column(name, DType.STRING, codes=np.asarray(codes, np.int32),
                  dictionary=np.array(dictionary, dtype=object))


def reference_registers(col, rows=True):
    """numpy alone: the per-row fold of the column's valid rows (of those
    that ``rows`` keeps)."""
    lut = hll.string_idx_rank_lut(col.dictionary, P)
    codes = np.asarray(col.codes)
    packed = lut[np.maximum(codes, 0)]
    return np.asarray(hll.registers_from_idx_rank(
        packed >> 6, packed & 0x3F, (codes >= 0) & rows, P, np))


def zipf_codes(rng, rows, card, null_share=0.0):
    weights = 1.0 / np.arange(1, card + 1)
    codes = rng.choice(card, size=rows, p=weights / weights.sum())
    codes[rng.random(rows) < null_share] = -1
    return codes


def _nulls_1pct(rng):
    return [strings("s", zipf_codes(rng, 20_000, 3_000, 0.01),
                    [f"v{j}" for j in range(3_000)])]


def _null_value_held_and_nulls(rng):
    d = [f"v{j}" for j in range(400)]
    d[7] = "NullValue"
    return [strings("s", zipf_codes(rng, 9_000, 400, 0.05), d)]


def _null_value_unheld_and_nulls(rng):
    """The trap: the Histogram folds the nulls into the literal entry's
    slot; no row holds the entry, so its hash must not reach a register."""
    d = [f"v{j}" for j in range(400)] + ["NullValue"]
    return [strings("s", zipf_codes(rng, 9_000, 400, 0.05), d)]


def _null_value_held_no_nulls(rng):
    d = [f"v{j}" for j in range(400)]
    d[0] = "NullValue"
    return [strings("s", zipf_codes(rng, 9_000, 400), d)]


def _entries_no_row_uses(rng):
    codes = zipf_codes(rng, 12_000, 2_500, 0.01)
    return [strings("s", np.where(codes >= 0, 2 * codes, -1),
                    [f"v{j}" for j in range(5_000)])]


def _dictionary_larger_than_the_rows(rng):
    return [strings("s", rng.integers(-1, 4_000, 500),
                    [f"v{j}" for j in range(4_000)])]


def _all_null(rng):
    return [strings("s", np.full(3_000, -1), ["a", "b", "c"])]


def _four_columns(rng):
    return [strings(f"s{i}", zipf_codes(rng, 15_000, card, 0.01),
                    [f"s{i}_{j}" for j in range(card)])
            for i, card in enumerate((20, 700, 2_500, 70_000))]


TABLES = {
    "1% nulls": _nulls_1pct,
    "a NullValue entry rows hold, and nulls": _null_value_held_and_nulls,
    "a NullValue entry no row holds, and nulls": _null_value_unheld_and_nulls,
    "a NullValue entry rows hold, no nulls": _null_value_held_no_nulls,
    "entries no row uses": _entries_no_row_uses,
    "a dictionary larger than the row count": _dictionary_larger_than_the_rows,
    "every row null": _all_null,
    "four columns, 20 to 70,000 entries": _four_columns,
}
# (table, mesh devices, one-hot cap on the CPU, resident chunk rows)
CASES = {name: (name, 1, None, None) for name in TABLES}
CASES.update({
    "the one-hot class": ("1% nulls", 1, 1 << 20, None),
    "the scatter class": ("1% nulls", 1, 64, None),
    "several resident chunks": ("four columns, 20 to 70,000 entries", 1,
                                None, 4_000),
    "a four-device mesh": ("four columns, 20 to 70,000 entries", 4, None,
                           None),
    "a four-device mesh, several chunks, a NullValue entry": (
        "a NullValue entry no row holds, and nulls", 4, None, 2_048),
})


def mesh_of(n):
    return None if n == 1 else Mesh(np.array(jax.devices()[:n]), (ROW_AXIS,))


@pytest.fixture
def low_floors(monkeypatch):
    """The device tiers at the tests' sizes."""
    monkeypatch.setattr(device_policy, "HIST_MIN_ROWS", 0)


@pytest.mark.parametrize("case", list(CASES))
def test_the_rider_route_gives_the_scan_routes_state_and_metric(
        case, low_floors, monkeypatch):
    name, devices, onehot_cap, chunk_rows = CASES[case]
    if onehot_cap is not None:
        monkeypatch.setattr(
            device_policy, "HIST_ONEHOT_CPU_MAX_SEGMENTS", onehot_cap)
    if chunk_rows is not None:
        monkeypatch.setattr(scan_engine, "MAX_RESIDENT_CHUNK_ROWS", chunk_rows)
    table = ColumnarTable(TABLES[name](np.random.default_rng(len(case))))
    columns = table.column_names
    distinct = [ApproxCountDistinct(c) for c in columns]
    histograms = [Histogram(c) for c in columns]
    with use_mesh(mesh_of(devices)):
        table.persist()
        try:
            cache = table._device_cache
            assert cache.device_count == devices
            chunks = len(cache.device_chunks)
            assert (chunks > 1) is (chunk_rows is not None)
            # the scan route: no partner; its states through a provider
            scan_states = InMemoryStateProvider()
            scan_ctx, scan_delta = counted(lambda: AnalysisRunner.do_analysis_run(
                table, distinct, save_states_with=scan_states))
            alone_ctx = AnalysisRunner.do_analysis_run(table, histograms)
            # the rider route
            ctx, delta = counted(lambda: AnalysisRunner.do_analysis_run(
                table, distinct + histograms))
            _, registers = segment.resident_top_k(
                table, [(c, 1000) for c in columns], registers_of=columns)
        finally:
            table.unpersist()
    assert scan_delta["hll_folds"] == len(columns) * chunks
    assert scan_delta["hll_presence_folds"] == 0
    assert delta["hll_presence_folds"] == len(columns)
    assert delta["hll_folds"] == 0
    assert delta["device_fetches"] == 1 and delta["seam_grouping_count"] == 1
    assert delta["grouping_passes"] == len(columns)
    if onehot_cap is not None:
        variant = "onehot" if onehot_cap > 64 else "scatter"
        assert delta[f"hist_{variant}_dispatches"] == chunks
    assert list(ctx.metric_map) == distinct + histograms
    for a, h in zip(distinct, histograms):
        want = scan_states.load(a)
        got = a.state_from_present_registers(registers[a.column])
        assert isinstance(want, ApproxCountDistinctState)
        assert got == want and got.hash_version == want.hash_version == 1
        assert np.array_equal(got.registers, reference_registers(table[a.column]))
        assert ctx.metric(a).value.get() == scan_ctx.metric(a).value.get()
        assert ctx.metric(a).value.get() == hll.estimate_cardinality(
            reference_registers(table[a.column]))
        # the Histogram beside it is the Histogram without it
        alone, beside = alone_ctx.metric(h).value.get(), ctx.metric(h).value.get()
        assert beside.number_of_bins == alone.number_of_bins
        assert beside.values == alone.values


def test_the_unheld_null_value_entry_would_change_the_registers():
    """The trap is a trap: were ``present`` read after the null merge, the
    literal entry's hash would reach a register no row fills."""
    col = _null_value_unheld_and_nulls(np.random.default_rng(3))[0]
    lut = hll.string_idx_rank_lut(col.dictionary, P)
    present = np.bincount(np.asarray(col.codes) + 1, minlength=402)[1:] > 0
    assert not present[400]
    right = np.asarray(hll.registers_from_present(lut, present, P, np))
    assert np.array_equal(right, reference_registers(col))
    present[400] = True
    wrong = np.asarray(hll.registers_from_present(lut, present, P, np))
    assert not np.array_equal(wrong, right)


def test_two_histograms_of_one_column_bring_its_registers_once(low_floors):
    table = ColumnarTable(_nulls_1pct(np.random.default_rng(5)))
    suite = [ApproxCountDistinct("s"), Histogram("s"),
             Histogram("s", max_detail_bins=5)]
    with use_mesh(None):
        table.persist()
        try:
            ctx, delta = counted(
                lambda: AnalysisRunner.do_analysis_run(table, suite))
        finally:
            table.unpersist()
    assert delta["hll_presence_folds"] == 1 and delta["hll_folds"] == 0
    assert delta["device_fetches"] == 1 and delta["grouping_passes"] == 2
    assert ctx.metric(suite[0]).value.get() == hll.estimate_cardinality(
        reference_registers(table["s"]))
    assert len(ctx.metric(suite[2]).value.get().values) == 5


def _with_a_number(rng):
    cols = _nulls_1pct(rng)
    return ColumnarTable(cols + [
        Column("x", DType.FRACTIONAL, values=rng.normal(0.0, 1.0, 20_000)),
        Column("k", DType.INTEGRAL, values=rng.integers(0, 50, 20_000)),
    ])


def _raising(monkeypatch):
    real = segment.resident_top_k

    def batch_fails(table, requests, mesh=None, registers_of=()):
        if registers_of:
            raise RuntimeError("the batch is lost")
        return real(table, requests, mesh)

    monkeypatch.setattr(segment, "resident_top_k", batch_fails)


# name -> (analyzers, run arguments, persisted, set-up, hll folds expected)
STAYS = {
    "a where": (
        [ApproxCountDistinct("s", where="x > 0"), Histogram("s")], {}, True,
        None),
    "aggregate_with": (
        [ApproxCountDistinct("s"), Histogram("s")],
        {"aggregate_with": InMemoryStateProvider}, True, None),
    "save_states_with": (
        [ApproxCountDistinct("s"), Histogram("s")],
        {"save_states_with": InMemoryStateProvider}, True, None),
    "no Histogram partner": ([ApproxCountDistinct("s")], {}, True, None),
    "a partner of another column": (
        [ApproxCountDistinct("s"), Histogram("k")], {}, True, None),
    "a numeric column": (
        [ApproxCountDistinct("k"), Histogram("k")], {}, True, None),
    "a binned partner": (
        [ApproxCountDistinct("s"),
         Histogram("s", binning_udf=lambda v: v[:2])], {}, True, None),
    "an unpersisted table": (
        [ApproxCountDistinct("s"), Histogram("s")], {}, False, None),
    "resident_top_k raises": (
        [ApproxCountDistinct("s"), Histogram("s")], {}, True, _raising),
}


@pytest.mark.filterwarnings("ignore:column 'x' is compared at a predicate")
@pytest.mark.parametrize("case", list(STAYS))
def test_the_scan_route_still_serves(case, low_floors, monkeypatch):
    suite, arguments, persisted, set_up = STAYS[case]
    table = _with_a_number(np.random.default_rng(11))
    arguments = {k: make() for k, make in arguments.items()}
    if set_up is not None:
        set_up(monkeypatch)
    with use_mesh(None):
        if persisted:
            table.persist()
        try:
            ctx, delta = counted(lambda: AnalysisRunner.do_analysis_run(
                table, suite, **arguments))
        finally:
            table.unpersist()
    assert delta["hll_presence_folds"] == 0
    assert delta["hll_folds"] >= 1
    assert not SCAN_STATS.degradation_events
    for a in suite:
        assert ctx.metric(a).value.is_success, (a, ctx.metric(a).value)
    distinct = suite[0]
    col = table[distinct.column]
    if distinct.column == "s":
        rows = (np.asarray(table["x"].values) > 0) if distinct.where else True
        assert ctx.metric(distinct).value.get() == hll.estimate_cardinality(
            reference_registers(col, rows))
    else:
        assert abs(ctx.metric(distinct).value.get() - 50) <= 5
    if "save_states_with" in arguments:
        state = arguments["save_states_with"].load(distinct)
        assert np.array_equal(state.registers, reference_registers(col))
        assert state.hash_version == 1


def test_a_suite_of_riders_alone_dispatches_no_scan(low_floors):
    """Every scanning analyzer rode: ``scanning`` is left empty and no scan
    attempt is made; one that cannot ride keeps the scan for itself."""
    table = _with_a_number(np.random.default_rng(13))
    seams = ("scan_passes", "hll_folds", "hll_presence_folds")

    def run(suite):
        before = SCAN_STATS.snapshot()
        ctx = AnalysisRunner.do_analysis_run(table, suite)
        after = SCAN_STATS.snapshot()
        assert all(m.value.is_success for m in ctx.metric_map.values())
        return [after[k] - before[k] for k in seams]

    with use_mesh(None):
        table.persist()
        try:
            alone = run([ApproxCountDistinct("s"), Histogram("s")])
            mixed = run([ApproxCountDistinct("s"), Histogram("s"),
                         ApproxCountDistinct("k")])
        finally:
            table.unpersist()
    assert alone == [0, 0, 1]
    assert mixed == [1, 1, 1]
