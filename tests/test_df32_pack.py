"""The host-side f64 -> (hi, lo) split and its range check walk a column
block by block through a small scratch (ops/df32.py). The formulas they
replaced stay here as the oracle: the planes must be the same bits, and
the check the same answer, for every input."""

import numpy as np
import pytest

from deequ_tpu.data.table import Column, DType
from deequ_tpu.ops import df32
from deequ_tpu.ops.df32 import (
    F32_MAX,
    PAIR_SAFE_MAX,
    pair_safe_np,
    split_pair_np,
)
from deequ_tpu.ops.scan_engine import _ChunkPacker

BLOCK = df32._HOST_BLOCK
LENGTHS = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK + 7]


def oracle_split(x):
    canonical = x + 0.0
    with np.errstate(over="ignore", invalid="ignore"):
        hi = canonical.astype(np.float32)
        diff = canonical - hi.astype(np.float64)
        lo = np.where(np.isfinite(diff), diff, 0.0).astype(np.float32)
    return hi, lo


def oracle_safe(values):
    if len(values) == 0:
        return True
    finite = values[np.isfinite(values)]
    if len(finite) == 0:
        return True
    return float(np.max(np.abs(finite))) <= PAIR_SAFE_MAX


def _normal(n, rng):
    return rng.normal(100.0, 5.0, n)


def _zeros(n, rng):
    return np.where(rng.random(n) < 0.5, 0.0, -0.0)


def _specials(n, rng):
    pool = np.array([np.inf, -np.inf, np.nan, 0.0, -0.0, 1.5, -2.25e30])
    return pool[rng.integers(0, len(pool), n)]


def _sparse_specials(n, rng):
    # one non-finite value in the LAST block only: the other blocks take
    # the path that never looks for one
    x = rng.normal(0.0, 1e6, n)
    x[-1:] = np.inf
    return x


def _beyond_f32(n, rng):
    x = rng.normal(0.0, 1.0, n) * 1e300
    x[::3] = F32_MAX * 1.0000001
    x[1::3] = -F32_MAX * 2.0
    return x


def _subnormals(n, rng):
    x = rng.normal(0.0, 1.0, n) * 1e-310
    x[::4] = np.float64(np.finfo(np.float32).smallest_subnormal) * 0.75
    x[1::4] = 5e-324
    return x


def _mixed_magnitudes(n, rng):
    return rng.normal(0.0, 1.0, n) * 10.0 ** rng.integers(-40, 40, n)


KINDS = {
    "normal": _normal,
    "signed_zeros": _zeros,
    "inf_nan": _specials,
    "one_inf_in_last_block": _sparse_specials,
    "beyond_f32": _beyond_f32,
    "subnormals": _subnormals,
    "mixed_magnitudes": _mixed_magnitudes,
}


def _column(make, n, layout):
    """``n`` values of ``make`` as a contiguous array, a strided view or a
    slice out of the middle of a longer array."""
    rng = np.random.default_rng(n)
    if layout == "contiguous":
        return make(n, rng)
    if layout == "strided":
        return make(2 * n, rng)[::2]
    return make(n + 11, rng)[5:n + 5]


def _same_bits(a, b):
    assert a.dtype == b.dtype == np.float32 and a.shape == b.shape
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


@pytest.mark.parametrize("layout", ["contiguous", "strided", "sliced"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_split_is_the_old_formula_bit_for_bit(kind, n, layout):
    x = _column(KINDS[kind], n, layout)
    before = x.copy()
    want_hi, want_lo = oracle_split(x)

    hi, lo = split_pair_np(x)
    _same_bits(hi, want_hi)
    _same_bits(lo, want_lo)

    # the packer's form: rows of a wider plane, the padded tail left alone
    hi_plane = np.full((3, n + 9), 7.0, dtype=np.float32)
    lo_plane = np.full((2, n + 9), 7.0, dtype=np.float32)
    got = split_pair_np(x, hi_plane[1, :n], lo_plane[0, :n])
    assert got[0].base is hi_plane and got[1].base is lo_plane
    _same_bits(hi_plane[1, :n], want_hi)
    _same_bits(lo_plane[0, :n], want_lo)
    for plane, row in ((hi_plane, 1), (lo_plane, 0)):
        assert (plane[row, n:] == 7.0).all()
        assert (np.delete(plane, row, axis=0) == 7.0).all()
    np.testing.assert_array_equal(
        x.view(np.uint64), before.view(np.uint64)
    )  # the input is read, never folded in place


def test_split_of_signed_zeros_and_non_finites_reads_as_documented():
    x = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e39, -1e39])
    hi, lo = split_pair_np(x)
    assert not np.signbit(hi[:2]).any() and not np.signbit(lo[:2]).any()
    np.testing.assert_array_equal(
        hi, np.array([0, 0, np.inf, -np.inf, np.nan, np.inf, -np.inf],
                     dtype=np.float32))
    assert (lo == 0.0).all()


def _unsafe_in_last_block(n, rng):
    x = rng.normal(0.0, 1e6, n)
    x[-1:] = -PAIR_SAFE_MAX * 2.0
    return x


def _at_the_ceiling(n, rng):
    x = rng.normal(0.0, 1e6, n)
    x[n // 2:n // 2 + 1] = PAIR_SAFE_MAX
    return x


def _only_non_finite(n, rng):
    return np.array([np.inf, -np.inf, np.nan])[rng.integers(0, 3, n)]


SAFE_KINDS = dict(
    KINDS,
    unsafe_in_last_block=_unsafe_in_last_block,
    at_the_ceiling=_at_the_ceiling,
    only_non_finite=_only_non_finite,
)


@pytest.mark.parametrize("layout", ["contiguous", "strided"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", sorted(SAFE_KINDS))
def test_pair_safe_is_the_old_answer(kind, n, layout):
    x = _column(SAFE_KINDS[kind], n, layout)
    got = pair_safe_np(x)
    assert isinstance(got, bool) and got == oracle_safe(x)


def test_pair_safe_answers_cover_both_sides():
    rng = np.random.default_rng(3)
    n = 3 * BLOCK + 7
    assert pair_safe_np(_at_the_ceiling(n, rng))
    assert pair_safe_np(_only_non_finite(n, rng))
    assert not pair_safe_np(_unsafe_in_last_block(n, rng))
    assert not pair_safe_np(_beyond_f32(n, rng))


def _mixed_columns(n):
    rng = np.random.default_rng(11)
    some = rng.random(n) < 0.9
    # pair-safe: the non-finite values do not count against the ceiling
    frac = np.array([np.inf, -np.inf, np.nan, -0.0, 2.5e17])[
        rng.integers(0, 5, n)]
    frac[::2] = rng.normal(100.0, 5.0, len(frac[::2]))
    dense = rng.normal(0.0, 1.0, n) * 10.0 ** rng.integers(-40, 17, n)
    return {
        "frac": Column("frac", DType.FRACTIONAL, frac, some),
        "dense": Column("dense", DType.FRACTIONAL, dense),
        "huge": Column("huge", DType.FRACTIONAL, _beyond_f32(n, rng), ~some),
        "small_int": Column("small_int", DType.INTEGRAL,
                            rng.integers(-2 ** 31 + 1, 2 ** 31, n)),
        "big_int": Column("big_int", DType.INTEGRAL,
                          rng.integers(-2 ** 62, 2 ** 62, n), some),
        "flag": Column("flag", DType.BOOLEAN, rng.random(n) < 0.5),
        "word": Column("word", DType.STRING,
                       codes=rng.integers(-1, 3, n).astype(np.int32),
                       dictionary=np.array(["a", "b", "c"], dtype=object)),
    }


def test_chunk_packer_planes_are_the_parents():
    """A mixed table, two chunks of which the second has a padded tail:
    every plane equals the one the parent's ``pack`` made, rebuilt here
    from the oracle."""
    n, chunk = BLOCK + 1000, BLOCK + 64
    cols = _mixed_columns(n)
    packer = _ChunkPacker(cols, chunk)
    assert packer.pair_names == ["frac", "dense"]
    assert packer.wide_names == ["huge", "big_int"]
    assert packer.narrow_i32 == ["small_int", "flag"]
    assert packer.masked_names == ["frac", "huge", "big_int"]

    for start, stop in ((0, chunk), (chunk, n)):
        m = stop - start

        def plane(names, dtype, fill, rows_of):
            out = np.full((len(names), chunk), fill, dtype=dtype)
            for i, name in enumerate(names):
                out[i, :m] = rows_of(name)[start:stop]
            return out

        with np.errstate(over="ignore", invalid="ignore"):
            want_hi = plane(packer.pair_names, np.float32, 0.0,
                            lambda c: oracle_split(cols[c].values)[0])
            want_lo = plane(packer.pair_names, np.float32, 0.0,
                            lambda c: oracle_split(cols[c].values)[1])
        want = (
            plane(packer.wide_names, np.float64, 0.0,
                  lambda c: cols[c].values),
            want_hi,
            want_lo,
            plane(packer.narrow_i32, np.int32, 0, lambda c: cols[c].values),
            plane(packer.masked_names, np.bool_, False,
                  lambda c: cols[c].mask),
            plane(["word"], np.int32, -1, lambda c: cols[c].codes),
            np.arange(chunk) < m,
            np.empty((0, chunk), dtype=np.int16),
        )
        got = packer.pack(start, stop)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.tobytes() == w.tobytes()
