"""Selection-kernel suite (ops/select_device.py + ops/scan_plan.py): the
batched histogram multi-rank selection that replaces the device sort for
resident quantiles.

Pins, against the sort path as the reference:

- exact-rank agreement of selected strata on adversarial inputs
  (all-equal columns, < bucket-count distinct values, duplicate-heavy
  ranks, NaN/null-heavy validity masks, inf endpoints, tiny chunks) —
  bit-identical summaries wherever the data carries no sub-ulp(f32)
  hi-plane collisions, and the documented <= 1 ulp(f32) lo-rider bound
  where it does (docs/numerics.md, selection-kernel determinism);
- KLL merge algebra parity: selection-built sketches merge with host- and
  sort-built sketches;
- planner routing: resident scans run zero sort passes, streaming /
  non-resident / disabled-kernel scans keep the sort path bit-identically;
- the DEEQU_TPU_SELECT_KERNEL / run_scan(select_kernel=...) opt-out and
  its validation;
- fault-ladder composition: an OOM injected during a selection pass
  bisects onto the sort path without corrupting the accumulator;
- ApproxQuantile(s) up-front argument validation (typed, at
  construction).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deequ_tpu.analyzers import (
    ApproxQuantile,
    ApproxQuantiles,
    KLLSketch,
    Mean,
    Size,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.analyzers.sketches import KLLState, _sketch_column
from deequ_tpu.data.streaming import stream_table
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import IllegalAnalyzerParameterException
from deequ_tpu.ops.df32 import split_pair_np
from deequ_tpu.ops.kll import KLLSketchState
from deequ_tpu.ops.kll_device import chunk_summary, fold_summaries
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    install_scan_fault_hook,
    run_scan,
)
from deequ_tpu.ops.scan_plan import plan_scan_ops, select_kernel_enabled
from deequ_tpu.ops.select_device import (
    chunk_summary_select,
    inverse_monotone_u32,
    monotone_u32,
)
from deequ_tpu.ops.device_policy import DEVICE_HEALTH
from deequ_tpu.resilience import FaultInjectingScanHook

pytestmark = pytest.mark.quantile


def _summaries(values, mask, k):
    """(sort_summary, select_summary) for one chunk, both jitted."""
    n = len(values)
    hi, lo = split_pair_np(np.asarray(values, dtype=np.float64))
    f_sort = jax.jit(
        lambda x, v, l: chunk_summary(x, v, k, n, jnp, lo=l)
    )
    f_sel = jax.jit(
        lambda x, v, l: chunk_summary_select(x, v, k, n, jnp, lo=l)
    )
    a = {key: np.asarray(v) for key, v in f_sort(hi, mask, lo).items()}
    b = {key: np.asarray(v) for key, v in f_sel(hi, mask, lo).items()}
    return a, b


def _assert_summary_equal(a, b, k):
    for key in ("count", "min", "max"):
        av, bv = float(a[key]), float(b[key])
        assert av == bv or (np.isnan(av) and np.isnan(bv)), key
    assert np.array_equal(a["weights"], b["weights"])
    # strata region: per-slot identical (each slot is one exact rank;
    # equal_nan — a rank resolving to a valid NaN is NaN on both paths)
    assert np.array_equal(a["items"][:k], b["items"][:k], equal_nan=True)
    # remainder region: identical as a multiset (the summary is
    # order-insensitive; fold_summaries sorts per level)
    assert np.array_equal(
        np.sort(a["items"][k:]), np.sort(b["items"][k:]), equal_nan=True
    )


# f32-grid values: f64 == f32 exactly, so lo == 0 and any hi-plane tie is
# an EXACT duplicate — selection must match the sort path bit for bit
def _grid(values):
    return np.asarray(values, dtype=np.float64).astype(np.float32).astype(
        np.float64
    )


_RNG = np.random.default_rng(1234)
_ADVERSARIAL = {
    # all-equal column: every histogram pass collapses into one bucket
    "all_equal": (_grid(np.full(5000, 3.25)), None),
    # fewer distinct values than histogram buckets
    "three_distinct": (
        _grid(_RNG.choice([1.5, -2.0, 7.0], 5000)), None,
    ),
    # duplicate-heavy: every rank lands inside a fat tie group
    "dup_heavy": (_grid(np.round(_RNG.normal(0, 2, 5000), 1)), None),
    # null-heavy validity mask (sentinel keys must stay out of ranks)
    "null_heavy": (
        _grid(_RNG.normal(0, 1, 5000)), _RNG.random(5000) > 0.85,
    ),
    "all_null": (_grid(_RNG.normal(0, 1, 300)), np.zeros(300, bool)),
    # inf endpoints: valid +/-inf values are real rank candidates
    "inf_endpoints": (
        _grid(
            np.where(
                _RNG.random(5000) < 0.02,
                np.where(_RNG.random(5000) < 0.5, np.inf, -np.inf),
                _RNG.normal(0, 1, 5000),
            )
        ),
        None,
    ),
    # masked NaNs (nulls arriving as NaN payloads under a validity mask)
    "nan_masked": (
        np.where(
            (_nan_r := _RNG.random(2000)) < 0.4,
            np.nan,
            _grid(_RNG.normal(0, 1, 2000)),
        ),
        _nan_r >= 0.4,
    ),
    "tiny": (_grid(_RNG.normal(0, 1, 7)), None),
    "single": (np.array([42.0]), None),
    "huge_magnitude": (_grid(_RNG.normal(0, 1e30, 3000)), None),
    # VALID NaNs (not masked), both sign bits: numpy sort order puts all
    # NaNs last regardless of sign — the selection key must agree
    # (review catch: the plain sign-flip bijection ordered -NaN below
    # -inf and shifted every rank)
    "valid_nan_both_signs": (
        np.where(
            np.arange(3000) % 7 == 0,
            np.where(np.arange(3000) % 14 == 0, -np.nan, np.nan),
            _grid(_RNG.normal(0, 1, 3000)),
        ),
        None,
    ),
    # valid NaNs AND nulls together: the sort path pads invalid rows
    # with +inf, which then interleaves BELOW the valid NaNs — top
    # ranks/remainder legitimately resolve to padding +inf and the
    # selection must reproduce exactly that
    "valid_nan_plus_nulls": (
        np.where(
            np.arange(2000) % 11 == 0, -np.nan,
            _grid(_RNG.normal(0, 1, 2000)),
        ),
        _RNG.random(2000) > 0.3,
    ),
    # pass 1's whole digit range in one column: keys whose top 8 bits are
    # 0x00 (-inf, the largest negatives) and 0xFF (+inf, every NaN), the
    # denormals on both sides of the sign flip (0x7F / 0x80) between them
    "top_byte_00_and_ff": (
        np.concatenate([
            np.full(40, -np.nan), np.full(40, np.nan),
            np.full(60, -np.inf), np.full(60, np.inf),
            _grid(-_RNG.uniform(1e38, 3.4e38, 400)),
            _grid(_RNG.uniform(1e38, 3.4e38, 400)),
            _grid(_RNG.integers(-300, 300, 500) * 1.401298464324817e-45),
            _grid(_RNG.normal(0, 1, 1500)),
        ])[_RNG.permutation(3000)],
        None,
    ),
    # every row under ONE 16-bit prefix: pass 1 fills a single cell of
    # its 256 x 256 histogram and passes 2-3 do all the narrowing
    "one_prefix16": (
        1.0 + _RNG.integers(0, 1 << 16, 4000) * 2.0 ** -23, None,
    ),
    # fewer rows than a radix digit has values (the block's floor)
    "under_256_rows": (_grid(_RNG.normal(0, 1, 200)), None),
    # one row over a whole block: the second block is one row and padding
    "one_over_a_block": (
        _grid(_RNG.normal(100, 5, (1 << 16) + 1)),
        _RNG.random((1 << 16) + 1) > 0.01,
    ),
}


@pytest.mark.parametrize("case", sorted(_ADVERSARIAL))
@pytest.mark.parametrize("k", [64, 256])
def test_select_matches_sort_reference_adversarial(case, k):
    values, mask = _ADVERSARIAL[case]
    # recompute the mask AFTER gridding: nan_masked builds it inline
    if mask is None:
        mask = np.ones(len(values), bool)
    a, b = _summaries(values, mask, k)
    _assert_summary_equal(a, b, k)
    # and the folded sketches are identical level by level
    sa = fold_summaries(a["items"], a["weights"], k, 0.64)
    sb = fold_summaries(b["items"], b["weights"], k, 0.64)
    if sa is None:
        assert sb is None
    else:
        assert sa.count == sb.count
        for la, lb in zip(sa.compactors, sb.compactors):
            assert np.array_equal(la, lb, equal_nan=True)


def test_pass1_cases_span_the_leading_digit_range():
    """What the pass-1 cases above claim of their keys: both ends of the
    top byte in one column, and one 16-bit prefix over a whole column."""
    keys = lambda case: np.asarray(monotone_u32(  # noqa: E731
        jnp.asarray(split_pair_np(_ADVERSARIAL[case][0])[0]), jnp))
    top = keys("top_byte_00_and_ff") >> 24
    assert {0x00, 0x7F, 0x80, 0xFF} <= set(top.tolist())
    assert np.unique(keys("one_prefix16") >> 16).size == 1
    assert np.unique(keys("one_prefix16")).size > 256


def test_valid_negative_nan_column_end_to_end_parity():
    """Review repro: a column with VALID negative-NaN values must give
    the same quantile on the resident selection path as on the
    non-resident sort path (the original key map ordered -NaN below
    -inf and shifted every rank by the NaN count)."""
    values = np.arange(8192, dtype=np.float64)
    values[::7] = -np.nan
    cols = lambda: ColumnarTable(  # noqa: E731
        [Column("c", DType.FRACTIONAL, values=values.copy())]
    )
    a = ApproxQuantile("c", 0.5)
    v_sort = AnalysisRunner.do_analysis_run(cols(), [a]).metric_map[a].value
    SCAN_STATS.reset()
    v_sel = AnalysisRunner.do_analysis_run(
        cols().persist(), [a]
    ).metric_map[a].value
    assert SCAN_STATS.device_select_passes > 0
    assert v_sort.is_success and v_sel.is_success
    assert v_sort.get() == v_sel.get()


def test_select_exact_ranks_vs_numpy_reference():
    """Strata items equal the numpy-sorted column at the documented
    midpoint ranks — an independent reference, not just the sort kernel."""
    k = 64
    values = _grid(_RNG.normal(100, 10, 3000))
    mask = np.ones(len(values), bool)
    _, b = _summaries(values, mask, k)
    sv = np.sort(values)
    m = len(values)
    w = int(b["weights"][0])
    n_strata = int((b["weights"][:k] > 0).sum())
    assert n_strata == m // w
    for i in range(n_strata):
        assert b["items"][i] == sv[i * w + w // 2], i
    # remainder = the exact top (m - n_strata*w) values
    n_rem = m - n_strata * w
    got = np.sort(b["items"][k:][b["weights"][k:] > 0])
    assert np.array_equal(got, sv[m - n_rem:]) if n_rem else got.size == 0


def test_sub_ulp_hi_collisions_stay_within_tie_budget():
    """Distinct f64 values colliding on one f32 hi value: the selected
    item may carry a different tie's lo rider, bounded by 1 ulp(f32) —
    the documented divergence; the hi plane itself stays exact."""
    k = 64
    base = _RNG.normal(1.0, 0.25, 2000)
    # perturb sub-ulp(f32): distinct f64s, identical f32 hi
    values = base + _RNG.uniform(0, 1e-8, 2000)
    mask = np.ones(len(values), bool)
    a, b = _summaries(values, mask, k)
    assert np.array_equal(a["weights"], b["weights"])
    hs = a["items"][:k].astype(np.float32)
    hl = b["items"][:k].astype(np.float32)
    assert np.array_equal(hs, hl)  # exact on the hi plane
    d = np.abs(a["items"][:k] - b["items"][:k])
    assert np.all(d <= np.spacing(np.abs(hs)).astype(np.float64))


def test_monotone_u32_roundtrip_total_order():
    vals = np.array(
        [-np.inf, -1e30, -1.5, -0.0, 0.0, 1e-30, 2.5, np.inf],
        dtype=np.float32,
    )
    u = np.asarray(jax.jit(lambda x: monotone_u32(x, jnp))(vals))
    assert np.all(np.diff(u.astype(np.int64)) > 0)  # strictly ordered
    back = np.asarray(
        jax.jit(lambda b: inverse_monotone_u32(b, jnp))(u)
    )
    assert np.array_equal(back.view(np.uint32), vals.view(np.uint32))


# -- the matmul formulation (what an accelerator plan resolves to) ---------


def _select_under(variant, values, mask, k, batched=False):
    """``chunk_summary_select`` traced under one histogram variant."""
    from deequ_tpu.ops.histogram_device import active_hist_variant

    n = values.shape[-1]
    hi, lo = split_pair_np(np.asarray(values, dtype=np.float64).ravel())
    hi, lo = hi.reshape(values.shape), lo.reshape(values.shape)

    def summary(x, v, l):
        with active_hist_variant(variant):
            one = lambda xc, vc, lc: chunk_summary_select(  # noqa: E731
                xc, vc, k, n, jnp, lo=lc
            )
            return jax.vmap(one)(x, v, l) if batched else one(x, v, l)

    return {key: np.asarray(v) for key, v in jax.jit(summary)(
        hi, mask, lo).items()}


@pytest.mark.parametrize("case", sorted(_ADVERSARIAL))
def test_matmul_formulation_matches_sort_reference_adversarial(case):
    """The one-hot variant's passes are blocked matmuls with no LUT, no
    scatter-min: the same summary as the sort path on every adversarial
    input, and as the LUT formulation bit for bit."""
    values, mask = _ADVERSARIAL[case]
    if mask is None:
        mask = np.ones(len(values), bool)
    k = 64
    a, lut = _summaries(values, mask, k)
    b = _select_under("onehot", values, mask, k)
    _assert_summary_equal(a, b, k)
    for key in lut:
        assert np.array_equal(lut[key], b[key], equal_nan=True), key


@pytest.mark.parametrize("k", [256, 1024])
def test_matmul_formulation_over_several_blocks(k):
    """More rows than a block holds and no block size dividing them, fat
    tie groups across block boundaries, nulls: bit-equal to the LUT
    formulation (k = 1024 shrinks the block: the membership plane grows
    with the sketch)."""
    rng = np.random.default_rng(77)
    n = 150_001
    values = rng.normal(100.0, 5.0, n)
    values[rng.integers(0, n, 4000)] = values[7]  # one fat tie group
    values[::5] = np.round(values[::5], 1)        # many small ones
    mask = rng.random(n) > 0.01
    lut = _select_under("scatter", values, mask, k)
    mm = _select_under("onehot", values, mask, k)
    for key in lut:
        assert np.array_equal(lut[key], mm[key], equal_nan=True), key
    assert int(mm["count"]) == int(mask.sum())


def test_matmul_formulation_under_vmap_maps_its_members():
    """``vmap`` of the one-hot formulation runs the unbatched program per
    member (``map_under_vmap``: the batched one-hot matmul is the one
    XLA:TPU miscompiles): equal to the members run alone."""
    rng = np.random.default_rng(78)
    values = rng.normal(0.0, 3.0, (3, 9_001))
    mask = rng.random((3, 9_001)) > 0.05
    batched = _select_under("onehot", values, mask, 64, batched=True)
    for j in range(3):
        alone = _select_under("onehot", values[j], mask[j], 64)
        for key in alone:
            assert np.array_equal(batched[key][j], alone[key],
                                  equal_nan=True), (j, key)


def test_pass1_leading_digits_stay_opaque_in_the_lowered_program():
    """Pass 1 compares its rows against the 256 leading digits as a
    RUN-TIME array, like the prefixes of passes 2-3, padded off a
    multiple of 128 rows: an ``optimization_barrier`` keeps the compiler
    from folding them back into an iota on both sides of the matmul (on
    the v5e that fusion ran at a quarter of the speed of passes 2-3,
    PERF.md section 6, PR 31). The barrier is in the lowered program,
    once, and under pass 1's scope, so a clean-up cannot drop it unseen."""
    from deequ_tpu.ops.select_device import (
        _PASS1_ROWS,
        _multirank_onehot_body,
    )

    lowered = jax.jit(_multirank_onehot_body).lower(
        jax.ShapeDtypeStruct((70_001,), jnp.uint32),
        jax.ShapeDtypeStruct((66,), jnp.int32),
    )
    text = lowered.as_text(debug_info=True)
    barriers = [
        line for line in text.splitlines()
        if "stablehlo.optimization_barrier" in line
    ]
    assert len(barriers) == 1, barriers
    assert _PASS1_ROWS > 256 and _PASS1_ROWS % 128
    assert f"tensor<{_PASS1_ROWS}xi32>" in barriers[0]
    assert "deequ.select.pass1/optimization_barrier" in text


# -- the remainder's extraction by counting (PR 35) ----------------------


def _mask_of(n, set_at):
    mask = np.zeros(n, bool)
    mask[np.asarray(set_at, dtype=np.int64)] = True
    return mask


_MASKS = {
    "empty": _mask_of(1000, []),
    "full": np.ones(1000, bool),
    "full_whole_rows": np.ones(1024, bool),
    # every set element in one group of 32, and in its last lanes
    "one_group": _mask_of(1000, range(530, 544)),
    "last_element_only": _mask_of(1000, [999]),
    "first_element_only": _mask_of(1000, [0]),
    # n no multiple of a group, and smaller than one
    "ragged": _RNG.random(777) < 0.3,
    "under_one_group": _mask_of(7, [1, 2, 6]),
    "single_row": _mask_of(1, [0]),
    # clustered in the last groups / the first, sparse between
    "clustered": _mask_of(
        5001, [*range(0, 70), 2500, *range(4900, 5001)]
    ),
    "several_rows_sparse": _RNG.random(20_001) < 0.002,
}


@pytest.mark.parametrize("case", sorted(_MASKS))
def test_nth_set_element_matches_numpy(case):
    """The primitive of the extraction, the index of the q-th set element
    of a 0/1 plane by counting over its packed groups, against
    ``np.flatnonzero(mask)[q - 1]``: at q = 1, q = total, q past the total
    (the not-found value is n) and in between; and all the first slots at
    once (``_first_set``), under both histogram variants of its
    inverting bincount."""
    from deequ_tpu.ops.histogram_device import active_hist_variant
    from deequ_tpu.ops.select_device import (
        _first_set,
        _group_words,
        _nth_set,
    )

    mask = _MASKS[case]
    n = len(mask)
    where = np.flatnonzero(mask)
    total = len(where)
    words = _group_words(jnp.asarray(mask))
    assert words.dtype == jnp.uint32 and words.shape == (4, -(-n // 128))
    nth = jax.jit(lambda w, q: _nth_set(w, q, n))
    for q in sorted({1, 2, max(total // 2, 1), max(total, 1), total + 1,
                     total + 40, n + 1}):
        want = where[q - 1] if q <= total else n
        assert int(nth(words, jnp.int32(q))) == want, q
    for count in sorted({1, max(total, 1), total + 3, 64}):
        want = np.full(count, n)
        want[:min(count, total)] = where[:count]
        for variant in ("scatter", "onehot"):
            with active_hist_variant(variant):
                got = _first_set(words, count, n, jnp)
            assert np.array_equal(np.asarray(got), want), (count, variant)


def _searched_source(u, bounds, tie_ranks, has_rem, W):
    """The extraction as it stood until PR 35, the reference of the one
    that counts: the ties at the two bounding keys numbered by an n-long
    running count each, slot s the element at which the running count of
    remainder elements first reaches s + 1, by W binary searches."""
    v_b, v_t = bounds[0], bounds[1]
    j0, j1 = tie_ranks[0], tie_ranks[1]
    tie_b = u == v_b
    tie_t = u == v_t
    pos_b, pos_t = jnp.cumsum(
        jnp.stack([tie_b, tie_t]).astype(jnp.int32), axis=1
    ) - 1
    above = (u > v_b) | (tie_b & (pos_b >= j0))
    below = (u < v_t) | (tie_t & (pos_t <= j1))
    rem = has_rem & above & below
    return jnp.minimum(
        jnp.searchsorted(
            jnp.cumsum(rem.astype(jnp.int32)),
            jnp.arange(1, W + 1, dtype=jnp.int32),
            side="left", method="scan",
        ),
        u.shape[0] - 1,
    )


# the remainder all in the last groups / the first / one tie group
# spanning the plane, with invalid rows and +inf / NaN present
_ORDERED_BASE = np.where(
    (_ord_r := _RNG.random(5000)) < 0.03,
    np.where(_ord_r < 0.015, np.inf, np.nan),
    _grid(np.round(_RNG.normal(0, 3, 5000), 2)),
)
_ORDERED = {
    "sorted_ascending": (np.sort(_ORDERED_BASE), _RNG.random(5000) > 0.1),
    "sorted_descending": (
        np.sort(_ORDERED_BASE)[::-1].copy(), _RNG.random(5000) > 0.1,
    ),
    "constant": (np.full(5000, 3.25), _RNG.random(5000) > 0.1),
    "constant_inf": (np.full(3000, np.inf), _RNG.random(3000) > 0.4),
}
_EXTRACTION_CASES = {**_ADVERSARIAL, **_ORDERED}


@pytest.mark.parametrize("case", sorted(_EXTRACTION_CASES))
@pytest.mark.parametrize("k", [64, 256])
def test_extraction_source_matches_searched_reference(case, k, monkeypatch):
    """``source``, the index each remainder slot reads, equal to the
    searched formulation's slot for slot (so ``items`` and ``weights``
    are): the arguments are the ones ``chunk_summary_select`` itself hands
    its extraction on that column."""
    from deequ_tpu.ops import select_device

    values, mask = _EXTRACTION_CASES[case]
    if mask is None:
        mask = np.ones(len(values), bool)
    n = len(values)
    hi, lo = split_pair_np(np.asarray(values, dtype=np.float64))
    W = select_device.strata_capacity(n, k)
    counted = select_device._remainder_source
    past = jnp.asarray([n + 5, n + 5], dtype=jnp.int32)

    def spy(u, bounds, tie_ranks, has_rem, slots, xp):
        assert slots == W
        pairs.extend(
            (counted(u, bounds, ranks, has_rem, W, xp),
             _searched_source(u, bounds, ranks, has_rem, W))
            # the column's own tie numbers; and those a clipped padding
            # target can ask for, past the last tie: none joins at the
            # bottom, all join at the top
            for ranks in (tie_ranks, past)
        )
        return pairs[0][0]

    def both(x, valid, low):
        chunk_summary_select(x, valid, k, n, jnp, lo=low)
        return tuple(pairs)

    pairs = []
    monkeypatch.setattr(select_device, "_remainder_source", spy)
    for got, want in jax.jit(both)(hi, mask, lo):
        assert got.shape == (W,)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# -- KLL merge algebra --------------------------------------------------


def test_selection_sketch_merges_with_host_built_sketch():
    values = _grid(_RNG.normal(50, 10, 20_000))
    table = ColumnarTable(
        [Column("x", DType.FRACTIONAL, values=values)]
    ).persist()
    a = ApproxQuantile("x", 0.5)
    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(table, [a], save_states_with=None)
    assert SCAN_STATS.device_select_passes > 0
    assert SCAN_STATS.device_sort_passes == 0

    # state built through the selection path
    _, b = _summaries(values, np.ones(len(values), bool), 256)
    sel_sketch = fold_summaries(b["items"], b["weights"], 256, 0.64)

    host = KLLSketchState(256, 0.64)
    other = _grid(_RNG.normal(60, 5, 10_000))
    host.update_batch(other)
    merged = sel_sketch.merge(host)
    assert merged.count == len(values) + len(other)
    both = np.concatenate([values, other])
    est = merged.quantile(0.5)
    lo_q, hi_q = np.quantile(both, [0.4, 0.6])
    assert lo_q <= est <= hi_q

    # and the KLLState algebra (selection + host partition sketch)
    host_state = _sketch_column(
        ColumnarTable([Column("x", DType.FRACTIONAL, values=other)]),
        "x", 256, 0.64,
    )
    sel_state = KLLState(sel_sketch, float(values.min()), float(values.max()))
    summed = sel_state.sum(host_state)
    assert summed.sketch.count == merged.count
    assert summed.global_min == min(values.min(), other.min())
    assert summed.global_max == max(values.max(), other.max())


# -- planner routing ----------------------------------------------------


def _quantile_analyzers():
    return [
        Size(),
        Mean("c0"),
        ApproxQuantile("c0", 0.5),
        ApproxQuantile("c1", 0.25),
        ApproxQuantiles("c1", (0.1, 0.9)),
        KLLSketch("c0"),
    ]


def _two_col_table(n=8_000):
    rng = np.random.default_rng(7)
    return ColumnarTable(
        [
            Column("c0", DType.FRACTIONAL, values=_grid(rng.normal(5, 2, n))),
            Column("c1", DType.FRACTIONAL, values=_grid(rng.normal(-3, 1, n))),
        ]
    )


def test_resident_scan_routes_selection_with_zero_sort_passes():
    analyzers = _quantile_analyzers()
    plain = _two_col_table()
    SCAN_STATS.reset()
    ctx_sort = AnalysisRunner.do_analysis_run(plain, analyzers)
    assert SCAN_STATS.device_sort_passes > 0
    assert SCAN_STATS.device_select_passes == 0

    resident = _two_col_table().persist()
    SCAN_STATS.reset()
    ctx_sel = AnalysisRunner.do_analysis_run(resident, analyzers)
    # the config-3 contract: a resident selection-path scan sorts NOTHING
    assert SCAN_STATS.device_sort_passes == 0
    assert SCAN_STATS.device_select_passes > 0

    # f32-grid data: the two kernels must agree bit for bit
    for a in analyzers:
        va, vb = ctx_sort.metric_map[a].value, ctx_sel.metric_map[a].value
        assert va.is_success and vb.is_success
        if isinstance(a, KLLSketch):
            assert va.get().buckets == vb.get().buckets
        else:
            assert va.get() == vb.get(), a


def test_streaming_scan_keeps_sort_path():
    table = _two_col_table()
    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(
        stream_table(table, batch_rows=2_000), _quantile_analyzers()
    )
    assert SCAN_STATS.device_select_passes == 0
    assert SCAN_STATS.device_sort_passes > 0
    for a, m in ctx.metric_map.items():
        assert m.value.is_success, (a, m.value)


def test_select_kernel_env_opt_out(monkeypatch):
    resident = _two_col_table().persist()
    analyzers = _quantile_analyzers()
    monkeypatch.setenv("DEEQU_TPU_SELECT_KERNEL", "0")
    SCAN_STATS.reset()
    ctx_off = AnalysisRunner.do_analysis_run(resident, analyzers)
    assert SCAN_STATS.device_select_passes == 0
    assert SCAN_STATS.device_sort_passes > 0
    monkeypatch.delenv("DEEQU_TPU_SELECT_KERNEL")
    # sort fallback must be bit-identical to the plain sort path
    ctx_sort = AnalysisRunner.do_analysis_run(_two_col_table(), analyzers)
    for a in analyzers:
        va, vb = ctx_off.metric_map[a].value, ctx_sort.metric_map[a].value
        if isinstance(a, KLLSketch):
            assert va.get().buckets == vb.get().buckets
        else:
            assert va.get() == vb.get(), a


def test_run_scan_select_kernel_param_overrides_env(monkeypatch):
    table = _two_col_table()
    table.persist()
    op = ApproxQuantile("c0", 0.5).scan_op(table)
    op.cache_key = ("t", "q")
    SCAN_STATS.reset()
    run_scan(table, [op], select_kernel=False)
    assert SCAN_STATS.device_select_passes == 0
    assert SCAN_STATS.device_sort_passes > 0
    # param=True wins over env=0
    monkeypatch.setenv("DEEQU_TPU_SELECT_KERNEL", "0")
    SCAN_STATS.reset()
    run_scan(table, [op], select_kernel=True)
    assert SCAN_STATS.device_select_passes > 0
    assert SCAN_STATS.device_sort_passes == 0


def test_select_kernel_validation():
    table = _two_col_table()
    op = ApproxQuantile("c0", 0.5).scan_op(table)
    with pytest.raises(ValueError, match="select_kernel"):
        run_scan(table, [op], select_kernel="yes")
    with pytest.raises(ValueError, match="select_kernel"):
        select_kernel_enabled(2)
    with pytest.raises(ValueError, match="DEEQU_TPU_SELECT_KERNEL"):
        import os

        os.environ["DEEQU_TPU_SELECT_KERNEL"] = "maybe"
        try:
            select_kernel_enabled(None)
        finally:
            del os.environ["DEEQU_TPU_SELECT_KERNEL"]


def test_planner_keeps_sort_for_wide_f64_columns():
    """One value above f32_max routes its column onto the wide plane — no
    u32 key domain, so the planner must keep the sort path even when
    resident."""
    table = _two_col_table()
    table["c0"].values[0] = 1e39
    table.persist()
    assert table._device_cache.packer.wide_names == ["c0"]
    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(table, [ApproxQuantile("c0", 0.5)])
    assert SCAN_STATS.device_select_passes == 0
    assert SCAN_STATS.device_sort_passes > 0
    assert all(m.value.is_success for m in ctx.all_metrics())


def test_huge_sketch_sizes_keep_sort_path():
    """Extreme relative_error requests (k > MAX_SELECT_SKETCH_SIZE)
    attach no selection variant: the pass-2/3 histograms scale O(k*256)
    per column — an allocation chunk bisection cannot shrink — so such
    ops stay on the O(n)-footprint sort kernel even when resident."""
    from deequ_tpu.ops.select_device import MAX_SELECT_SKETCH_SIZE
    from deequ_tpu.analyzers.sketches import _sketch_size_for_error

    table = _two_col_table()
    table.persist()
    a = ApproxQuantile("c0", 0.5, relative_error=1e-4)
    assert _sketch_size_for_error(1e-4) > MAX_SELECT_SKETCH_SIZE
    assert a.scan_op(table).select_update is None
    SCAN_STATS.reset()
    ctx = AnalysisRunner.do_analysis_run(table, [a])
    assert SCAN_STATS.device_select_passes == 0
    assert SCAN_STATS.device_sort_passes > 0
    assert ctx.metric_map[a].value.is_success


def test_plan_scan_ops_census():
    table = _two_col_table()
    from deequ_tpu.ops.scan_engine import _ChunkPacker

    cols = {n: table[n] for n in table.column_names}
    packer = _ChunkPacker(cols, table.num_rows)
    ops = [
        ApproxQuantile("c0", 0.5).scan_op(table),
        Mean("c0").scan_op(table),
        Mean("c0", where="c1 > 0").scan_op(table),
    ]
    plan = plan_scan_ops(ops, packer, resident=True, select_kernel=True)
    assert (plan.select_ops, plan.sort_ops, plan.plane_ops) == (1, 0, 1)
    assert plan.ops[0].update is not ops[0].update
    # a where-free statistic of a pair-plane column reads the batched
    # plane statistics (resident or not); a filtered one keeps its update
    assert plan.ops[1].plane_route is not None
    assert plan.ops[2].update is ops[2].update
    off = plan_scan_ops(ops, packer, resident=True, select_kernel=False)
    assert (off.select_ops, off.sort_ops) == (0, 1)
    assert off.ops[0].update is ops[0].update
    nonres = plan_scan_ops(ops, packer, resident=False, select_kernel=True)
    assert (nonres.select_ops, nonres.sort_ops, nonres.plane_ops) == (0, 1, 1)


# -- fault-ladder composition -------------------------------------------


def test_oom_during_selection_pass_bisects_to_sort_without_corruption():
    """A device OOM injected while the resident selection path is running
    evicts residency and bisects; the re-planned attempt lands on the
    sort path (residency is gone) and the run completes. Exact-monoid
    metrics (Size/Mean) must be bit-identical to a fault-free run — a
    corrupted (half-folded) accumulator would break them loudly; the
    quantiles land within the KLL rank-error envelope (the bisected
    retry runs SMALLER chunks, which legitimately re-chunks the sketch —
    same as any chunk-size change)."""
    analyzers = _quantile_analyzers()
    clean = AnalysisRunner.do_analysis_run(
        _two_col_table().persist(), analyzers
    )

    table = _two_col_table().persist()
    DEVICE_HEALTH.reset()
    hook = FaultInjectingScanHook(faults={0: ("oom", 1)})
    prev = install_scan_fault_hook(hook)
    SCAN_STATS.reset()
    try:
        faulted = AnalysisRunner.do_analysis_run(table, analyzers)
    finally:
        install_scan_fault_hook(prev)
        DEVICE_HEALTH.reset()
    assert hook.injected, "fault hook never fired"
    assert SCAN_STATS.oom_bisections >= 1
    # the bisected retry re-planned onto the sort path (residency gone)
    assert SCAN_STATS.device_sort_passes > 0
    for a in analyzers:
        va, vb = clean.metric_map[a].value, faulted.metric_map[a].value
        assert va.is_success and vb.is_success, a
        if isinstance(a, (Size, Mean)):
            assert va.get() == vb.get(), a
        elif isinstance(a, ApproxQuantile):
            # w/2 rank error at n=8000, k=256 => well under 0.05 here
            assert abs(va.get() - vb.get()) < 0.05, a


def test_device_loss_during_selection_falls_back_bit_identically():
    """A persistent device loss with on_device_error='fallback' re-runs
    the scan on the CPU backend: same chunk rows, single device, sort
    path, residency evicted. The fallback result must be bit-identical
    to a clean run of exactly that shape (single-device, non-resident,
    sort) — the strongest no-corruption statement the ladder allows,
    since states are backend-agnostic monoids."""
    from deequ_tpu.parallel.mesh import use_mesh

    analyzers = _quantile_analyzers()
    # reference: what the fallback attempt computes (single device,
    # non-resident pack path, sort kernel)
    with use_mesh(None):
        clean = AnalysisRunner.do_analysis_run(_two_col_table(), analyzers)

    table = _two_col_table().persist()
    DEVICE_HEALTH.reset()
    hook = FaultInjectingScanHook(faults={0: ("lost", 99)})
    prev = install_scan_fault_hook(hook)
    SCAN_STATS.reset()
    try:
        faulted = AnalysisRunner.do_analysis_run(
            table, analyzers, on_device_error="fallback"
        )
    finally:
        install_scan_fault_hook(prev)
        DEVICE_HEALTH.reset()
    assert hook.injected, "fault hook never fired"
    assert SCAN_STATS.fallback_scans >= 1
    for a in analyzers:
        va, vb = clean.metric_map[a].value, faulted.metric_map[a].value
        assert va.is_success and vb.is_success, a
        if isinstance(a, KLLSketch):
            assert va.get().buckets == vb.get().buckets
        else:
            assert va.get() == vb.get(), a


# -- argument validation ------------------------------------------------


@pytest.mark.parametrize("bad", [float("nan"), "0.5", None, True])
def test_approx_quantile_rejects_untypable_quantile_at_construction(bad):
    """Non-numeric / NaN quantiles would crash the trace opaquely —
    rejected typed at CONSTRUCTION."""
    with pytest.raises(IllegalAnalyzerParameterException):
        ApproxQuantile("x", bad)
    with pytest.raises(IllegalAnalyzerParameterException):
        ApproxQuantiles("x", (0.5, bad))


@pytest.mark.parametrize("bad", [0.0, 1.0, -0.1, 1.5])
def test_out_of_range_quantile_fails_typed_at_preconditions(bad):
    """Out-of-range q constructs (persisted results from the historic
    closed-interval era must stay deserializable) but fails its RUN with
    a typed per-analyzer metric, before any kernel work."""
    t = ColumnarTable([Column("x", DType.FRACTIONAL, values=np.arange(10.0))])
    a = ApproxQuantile("x", bad)
    m = a.calculate(t)
    assert m.value.is_failure
    assert "open interval" in str(m.value.exception)
    ks = ApproxQuantiles("x", (0.5, bad)).calculate(t)
    assert ks.value.is_failure


def test_approx_quantiles_validation():
    # empty list: constructs (deserialization safety), fails typed at
    # preconditions
    m = ApproxQuantiles("x", ()).calculate(
        ColumnarTable([Column("x", DType.FRACTIONAL, values=np.arange(4.0))])
    )
    assert m.value.is_failure
    assert "non-empty" in str(m.value.exception)
    # duplicates dedupe, order preserved; equal specs stay equal keys
    a = ApproxQuantiles("x", (0.5, 0.25, 0.5))
    assert a.quantiles == (0.5, 0.25)
    assert a == ApproxQuantiles("x", (0.5, 0.25))


def test_valid_quantiles_still_accepted():
    a = ApproxQuantile("x", 0.5)
    assert a.quantile == 0.5
    b = ApproxQuantiles("x", (0.01, 0.99))
    assert b.quantiles == (0.01, 0.99)
