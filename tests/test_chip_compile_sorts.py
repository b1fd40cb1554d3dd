"""Compile guards of the grouping sorts and their gathers: the device
unique/inverse sort and the host-keyed run-length stats over a 64-bit
key, the gathers at the group starts, and the resident key sort of the
SF10 LINEITEM primary key — compiled (or lowered) for a described v5e
(tests/chip_compile.py says how)."""

import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — one_chip, topo: fixtures
    LINEITEM_KEY_SPECS, LINEITEM_ROWS, SMOKE_ROWS, _aval,
    _lineitem_chunk_avals, _scatter_results, _sort_results, one_chip, topo,
)


def test_grouping_sort_kernels_compile_unique_inverse(one_chip):
    """Uniqueness over a 64-bit key: the device unique/inverse sort keeps
    its index payload 32-bit, ``(pred, u32, u32, s32)`` with the key in
    two u32 halves; an s64 payload sorts six operands, XLA:TPU's stability
    iota among them, and multiplies the compile time. At 2^13 rows: the
    operands do not depend on the row count, but from 2^15 rows on this
    sort compiles in 2.5 to 5 minutes on a CPU host (150 s at 2^15, 310 s
    at the smoke's 10M rows), against 5 s at 2^13."""
    from deequ_tpu.ops import segment

    n = 1 << 13
    compiled = segment._unique_inverse_kernel.lower(
        _aval((n,), np.int64, one_chip), _aval((n,), np.bool_, one_chip),
    ).compile()
    assert _sort_results(compiled.as_text()) == [
        f"(pred[{n}], u32[{n}], u32[{n}], s32[{n}])"]


def test_grouping_sort_kernels_compile_rle_stats(one_chip):
    """The sparse run-length stats over the smoke's near-unique 64-bit
    key, as the host-keyed grouping path sorts it."""
    from deequ_tpu.ops import segment

    n = SMOKE_ROWS
    assert segment._rle_stats_kernel.lower(
        _aval((1, n), np.int64, one_chip), _aval((n,), np.bool_, one_chip),
    ).compile()


@pytest.mark.parametrize("size", [64, 1 << 19])
def test_grouping_sort_kernels_compile_gathers_at_starts(one_chip, size):
    """The gathers at the group starts, at 64 distinct values (an integral
    Histogram's) and at 2^19 (a near-unique column's), over the smoke's
    rows: no scatter in the search, and no 64-bit one in the run-length
    gather either (``jnp.nonzero``'s sized form was an s64 scatter-add of
    every row, split in u32 halves on the v5e: a tuple-valued scatter)."""
    from deequ_tpu.ops import segment

    n = SMOKE_ROWS
    valid = _aval((n,), np.bool_, one_chip)
    texts = [
        segment._gather_at_starts_kernel.lower(
            _aval((n,), np.int32, one_chip), _aval((n,), np.int32, one_chip),
            size=size,
        ).compile().as_text(),
        segment._rle_gather_kernel.lower(
            _aval((1, n), np.int32, one_chip), valid,
            _aval((), np.int64, one_chip), size=size,
        ).compile().as_text(),
    ]
    for text in texts:
        for result in _scatter_results(text):
            assert not result.startswith("(") and "64[" not in result
    assert "scatter" not in texts[0]


def test_resident_key_sort_has_only_32_bit_operands_at_sf10(one_chip):
    """The composite primary key (``l_orderkey``, ``l_linenumber``) of the
    whole SF10 LINEITEM table, counted from the resident narrow-i32 planes
    of its chunks as ``persist()`` sizes them, lowered for the described
    chip: the rows' sort keys on the exclusion flag and two int32 keys, the
    run positions' sort on int32, no 64-bit operand in either (one
    multiplies XLA:TPU's compile time), and neither asks for stability
    (XLA:TPU would add an s32 iota operand to break ties that no count
    reads). Lowered, not compiled: the compile takes ~110 s here (1.1 GB of
    temporaries beside the 4.8 GB table; docs/kernels.md)."""
    from deequ_tpu.data.table import DType
    from deequ_tpu.ops import scan_engine, segment

    dtypes = ([DType.INTEGRAL] * 7 + [DType.FRACTIONAL] * 4
              + [DType.STRING] * 5)
    chunk = scan_engine._auto_chunk_rows_from_dtypes(
        dtypes, scan_engine.RESIDENT_CHUNK_BYTES,
        scan_engine.MAX_RESIDENT_CHUNK_ROWS)
    n_chunks = -(-LINEITEM_ROWS // chunk)
    assert n_chunks == 3
    fn = segment._resident_group_stats_fn(LINEITEM_KEY_SPECS, n_chunks, True)
    text = fn.lower(*_lineitem_chunk_avals(chunk, n_chunks, one_chip)).as_text(
        dialect="hlo")
    n = n_chunks * chunk
    assert sorted(_sort_results(text)) == sorted([
        f"(pred[{n}], s32[{n}], s32[{n}])", f"s32[{n}]"])
    sort_lines = [line for line in text.splitlines() if " sort(" in line]
    assert len(sort_lines) == 2
    assert not any("is_stable=true" in line for line in sort_lines)


def test_compiled_grouping_sorts_carry_no_iota_operand(one_chip):
    """Compiled for the described chip at 2^12 rows: the resident program
    of the SF10 key set and the host-keyed ``_rle_stats_kernel`` sort
    exactly their keys, ``(pred, s32, s32)`` and ``s32``; a stable sort
    compiles to ``(pred, s32, s32, s32)`` and ``(s32, s32)``, the last
    operand an iota that breaks ties. The operands do not depend on the
    row count (the iota shows at 2^12 as at 2^20), but the compile time
    does: ~4 s here against ~140 s at 2^20."""
    from deequ_tpu.ops import segment

    n = 1 << 12
    fn = segment._resident_group_stats_fn(LINEITEM_KEY_SPECS, 1, True)
    resident = fn.lower(*_lineitem_chunk_avals(n, 1, one_chip)).compile()
    host_keyed = segment._rle_stats_kernel.lower(
        _aval((2, n), np.int32, one_chip), _aval((n,), np.bool_, one_chip),
    ).compile()
    for compiled in (resident, host_keyed):
        assert sorted(_sort_results(compiled.as_text())) == sorted([
            f"(pred[{n}], s32[{n}], s32[{n}])", f"s32[{n}]"])
