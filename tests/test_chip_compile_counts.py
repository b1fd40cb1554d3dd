"""Compile guards of the resident count programs: ``resident_top_k`` of
the four string classes (with and without the HLL registers), the counts
with types of ``mixed12m100.profile``, the one-hot and Pallas bincounts,
the sharded grouping kernels, the fused grouping pass, and the string
HLL step of ``strings12m.sscan`` — compiled for a described v5e
(tests/chip_compile.py says how)."""

import re

import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — topo, one_chip, as_tpu: fixtures
    SMOKE_ROWS, STRING_CLASSES, STRING_ROWS, _aval, _chunk_avals, _compile,
    _emitters, as_tpu, chip_smoke, one_chip, topo,
)


@pytest.mark.parametrize("segments", [1 << 16, 1 << 17])
def test_bincount_onehot_bf16_planes_compile(as_tpu, one_chip, segments):
    import jax.numpy as jnp

    from deequ_tpu.ops.histogram_device import _plane_dtype, bincount_onehot

    assert _plane_dtype(jnp) == jnp.bfloat16
    compiled = _compile(
        lambda seg: bincount_onehot(seg, segments, jnp),
        _aval((1 << 22,), np.int32, one_chip),
    )
    text = compiled.as_text()
    assert "convolution" in text and "scatter" not in text


@pytest.mark.parametrize(
    "keyspaces", [(22, 41), (22, chip_smoke.WIDE_CARD + 1)],
    ids=["narrow-onehot", "wide-scatter"],
)
def test_fused_grouping_pass_compiles(as_tpu, one_chip, keyspaces):
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.segment import _bincount_fn

    n = 2 * (SMOKE_ROWS // 2)  # two sub-passes' keys, concatenated
    total = sum(keyspaces)
    variant = resolve_hist_variant((total + 1,), rows=n)
    assert variant == ("onehot" if total < (1 << 17) else "scatter")
    fn = _bincount_fn(total, None, variant)
    assert fn.lower(_aval((n,), np.int64, one_chip)).compile() is not None


def test_sharded_grouping_kernels_compile(as_tpu, topo):
    """The persisted string column's bincount under the 4-chip mesh (the
    --chips 4 run's Histogram/Entropy path): per-shard counts + i64 psum."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.segment import _bincount_fn, _resident_bincount_fn
    from deequ_tpu.parallel.mesh import ROW_AXIS

    mesh = Mesh(np.array(topo.devices), (ROW_AXIS,))
    n = SMOKE_ROWS
    card = chip_smoke.N_CATS
    variant = resolve_hist_variant((card + 2,), rows=n)
    assert variant == "onehot"
    fn = _resident_bincount_fn(card + 1, 1, 0, True, mesh, variant)
    compiled = fn.lower(
        _aval((2, n), np.int32, NamedSharding(mesh, P(None, ROW_AXIS))),
        _aval((n,), np.bool_, NamedSharding(mesh, P(ROW_AXIS))),
    ).compile()
    assert "all-reduce" in compiled.as_text()
    wide = _bincount_fn(chip_smoke.WIDE_CARD + 1, mesh, "scatter")
    assert wide.lower(
        _aval((n,), np.int64, NamedSharding(mesh, P(ROW_AXIS)))
    ).compile()


def test_resident_top_k_of_the_four_string_classes_compiles(as_tpu, one_chip):
    """The Histogram pass of ``strings12m.sscan`` (PR 32), one column of
    each dictionary class in ONE program over the resident code plane: the
    class under the one-hot cap counts by matmul, the three past it by an
    int32 scatter-add. No 64-bit operand anywhere: the int64 scatter the
    grouping kernels ran before is emulated on the v5e, 8x the int32 one
    on the chip (PERF.md section 6, PR 32)."""
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.segment import _resident_topk_fn

    n = STRING_ROWS
    specs = tuple(
        (row, card + 1, 1000, -1, resolve_hist_variant((card + 2,), rows=n))
        for row, card in enumerate(STRING_CLASSES)
    )
    assert [spec[4] for spec in specs] == [
        "onehot", "scatter", "scatter", "scatter"]
    compiled = _resident_topk_fn(specs, 1, None, False).lower(
        _aval((20, n), np.int32, one_chip), _aval((n,), np.bool_, one_chip)
    ).compile()
    text = compiled.as_text()
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert sorted(s.split("{")[0] for s in scatters) == sorted(
        f"s32[{card + 2}]" for card in STRING_CLASSES[1:])
    assert not re.search(r"\b[su]64\[", text)
    # the narrow class rides the MXU, built by the emitter the selection
    # passes share since PR 31
    assert _emitters(text, "deequ.bincount.onehot") == {
        "EmitAllBatchInSublanes"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_resident_top_k_with_registers_folds_the_entries_present(
        as_tpu, one_chip):
    """The ONE pass of ``strings12m.sscan`` since PR 33: the same four
    classes with the HLL registers asked of every column, the packed
    (idx, rank) LUTs as run-time arguments at their padded widths. The
    registers come from the K entries present in the counts: no gather
    over the rows, no scatter but the three wide counts, the K-entry fold
    on the MXU at every class (the 50k one too, under the per-row fold's
    row floor), no 64-bit operand."""
    from deequ_tpu.ops import hll
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.segment import _resident_topk_fn

    n = STRING_ROWS
    p = hll.precision_from_relative_sd()
    specs = tuple(
        (row, card + 1, 1000, -1, resolve_hist_variant((card + 2,), rows=n))
        for row, card in enumerate(STRING_CLASSES)
    )
    compiled = _resident_topk_fn(specs, 1, None, False, (p,) * 4).lower(
        _aval((20, n), np.int32, one_chip), _aval((n,), np.bool_, one_chip),
        *[_aval((1 << (card - 1).bit_length(),), np.int32, one_chip)
          for card in STRING_CLASSES],
    ).compile()
    text = compiled.as_text()
    assert not re.search(r"\bgather\(", text)
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert sorted(s.split("{")[0] for s in scatters) == sorted(
        f"s32[{card + 2}]" for card in STRING_CLASSES[1:])
    assert not re.search(r"\b[su]64\[", text)
    assert _emitters(text, "deequ.bincount.onehot") == {
        "EmitAllBatchInSublanes"}
    assert _emitters(text, "deequ.hll.present") == {"EmitInputBatchInLanes"}
    assert not _emitters(text, "deequ.hll.fold")
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert f"s32[{4 * (2001 + (1 << p))}]" in text  # ONE output vector


def test_resident_counts_with_types_compile_without_a_gather(
        as_tpu, one_chip):
    """Pass 1 of ``mixed12m100.profile`` since PR 41: a string column's
    ApproxCountDistinct and DataType from ONE count of its codes, no
    Histogram beside them, over the cell's four resident chunks. Each
    column asks for no ``top_k``: its registers from the entries present
    and its five type counts, five masked sums over the K entries of the
    class LUT (a run-time argument like the (idx, rank) LUT). No gather
    over the rows, no sort, no scatter but the wide counts, no 64-bit
    operand."""
    from deequ_tpu.ops import hll
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.lut_cache import padded_length
    from deequ_tpu.ops.segment import _counted_entries, _resident_topk_fn

    n, chunks = 3_381_864, 4
    p = hll.precision_from_relative_sd()
    counted = [_counted_entries(card) for card in STRING_CLASSES]
    assert counted == [57_344, 262_144, 1_048_576, 3_145_728]
    specs = tuple(
        (row, k + 1, None, -1, resolve_hist_variant((k + 2,), rows=n * chunks))
        for row, k in enumerate(counted)
    )
    luts = [_aval((padded_length(card),), np.int32, one_chip)
            for card in STRING_CLASSES for _ in range(2)]
    compiled = _resident_topk_fn(
        specs, chunks, None, False, (p,) * 4, (True,) * 4,
    ).lower(
        *[_aval((100, n), np.int32, one_chip), _aval((n,), np.bool_, one_chip)]
        * chunks, *luts,
    ).compile()
    text = compiled.as_text()
    assert not re.search(r"\bgather\(", text)
    assert not re.search(r"\bsort\(", text)
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert sorted(s.split("{")[0] for s in scatters) == sorted(
        f"s32[{k + 2}]" for k in counted[1:] for _ in range(chunks))
    assert not re.search(r"\b[su]64\[", text)
    assert _emitters(text, "deequ.hll.present") == {"EmitInputBatchInLanes"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert f"s32[{4 * (5 + (1 << p))}]" in text  # ONE output vector


def test_string_hll_step_compiles_with_a_gather_and_an_mxu_fold(
        as_tpu, one_chip):
    """The scan pass of ``strings12m.sscan``: ApproxCountDistinct over one
    string column of each dictionary class at the cell's 12.5M rows, the
    (idx, rank) LUTs as run-time arguments at their padded widths: per
    column one gather by code and the one-hot register fold, no scatter."""
    from chipbench import suite_build
    from chipbench.generators import string_table
    from deequ_tpu.analyzers import ApproxCountDistinct
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.scan_engine import _build_step_fns, _ChunkPacker
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    n = STRING_ROWS
    params = {"n_string": 4, "dictionary_sizes": [5, 6, 7, 8],
              "zipf_exponent": 1.0, "null_share": 0.01}
    table = suite_build.table_of(string_table.generate(64, 3, params))
    ops, scannable, failures = AnalysisRunner._build_scan_ops(
        table, [ApproxCountDistinct(f"s{i}") for i in range(4)])
    assert not failures and len(scannable) == 4
    packer = _ChunkPacker({c: table[c] for c in table.column_names}, n)
    plan = plan_scan_ops(ops, packer, resident=True, rows=n)
    assert plan.hll_folds == 4
    luts = {
        col + "\x00" + kind: _aval(
            (1 << (card - 1).bit_length(),), np.int32, one_chip)
        for op, card in zip(plan.ops, STRING_CLASSES)
        for col, kind, _ in op.luts
    }
    step_fn, _, _ = _build_step_fns(
        plan.ops, packer.unpack_view(), None, n, tuple(sorted(luts)))
    compiled = step_fn.lower(
        *_chunk_avals(packer, n, one_chip, one_chip), luts).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\bgather\(", text)) == 4
    assert not re.search(r"\bscatter\(", text)
    assert _emitters(text, "deequ.hll.fold") == {"EmitInputBatchInLanes"}
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("weighted", [False, True], ids=["count", "weighted"])
def test_bincount_pallas_compiles_on_mosaic(one_chip, weighted):
    import jax.numpy as jnp

    from deequ_tpu.ops.histogram_device import bincount_pallas

    n, segments = 1 << 20, (1 << 18) + 3
    avals = [_aval((n,), np.int32, one_chip)]
    if weighted:
        avals.append(_aval((n,), np.int32, one_chip))

    def kernel(seg, w=None):
        return bincount_pallas(seg, segments, jnp, weights=w, interpret=False)

    compiled = _compile(kernel, *avals)
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_refusal_surfaces_as_device_compile_exception(one_chip):
    """A kernel Mosaic refuses (a (1, 1024) block over a (1024, 1024)
    array — the shape bincount_pallas had before it met the compiler) must
    classify as a typed compile failure, never reroute."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from deequ_tpu.exceptions import (
        DeviceCompileException,
        classify_device_error,
    )

    def misaligned(x):
        def body(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        return pl.pallas_call(
            body, grid=(1024,),
            in_specs=[pl.BlockSpec((1, 1024), lambda i: (i, np.int32(0)))],
            out_specs=pl.BlockSpec((1, 1024), lambda i: (i, np.int32(0))),
            out_shape=jax.ShapeDtypeStruct((1024, 1024), jnp.int32),
        )(x)

    with pytest.raises(ValueError) as refused:
        _compile(misaligned, _aval((1024, 1024), np.int32, one_chip))
    assert "divisible by 8 and 128" in str(refused.value)
    typed = classify_device_error(refused.value, "execute")
    assert isinstance(typed, DeviceCompileException)
