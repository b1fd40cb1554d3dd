"""Compile guards of the fused scan steps: chip_smoke's resident suite on
one chip and row-sharded over four, the profiler's plane route, the fold
merge with donation, and the cast view's pass 2 of ``mixed12m100.profile``
— compiled for a described v5e (tests/chip_compile.py says how)."""

import copy
import re

import numpy as np
import pytest

from chip_compile import (  # noqa: F401 — topo, one_chip, as_tpu: fixtures
    SMOKE_ROWS, _aval, _chunk_avals, _smoke_step, as_tpu, chip_smoke,
    one_chip, topo,
)


def test_resident_fused_step_compiles_at_smoke_shape(as_tpu, one_chip):
    step_fn, avals, plan = _smoke_step(
        SMOKE_ROWS, None, one_chip, one_chip, one_chip
    )
    # the chip's branches: selection kernel routed over the one-hot tier
    assert plan.variant == "select" and plan.hist_variant == "onehot"
    lowered = step_fn.lower(*avals)
    # traced as the chip traces it: bf16 one-hot planes (HLL MXU fold and
    # the selection kernel's histogram passes)
    assert "bf16" in lowered.as_text()
    compiled = lowered.compile()
    # ...which XLA:TPU turns into MXU convolutions with the one-hot
    # compare fused into the operands
    assert "convolution" in compiled.as_text()
    mem = compiled.memory_analysis()
    # one program must fit beside the resident table in 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 14 << 30


def test_row_sharded_step_compiles_with_all_reduce(as_tpu, topo):
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from deequ_tpu.parallel.mesh import ROW_AXIS

    mesh = Mesh(np.array(topo.devices), (ROW_AXIS,))
    step_fn, avals, _ = _smoke_step(
        SMOKE_ROWS, mesh,
        NamedSharding(mesh, P(None, ROW_AXIS)),
        NamedSharding(mesh, P(ROW_AXIS)),
        NamedSharding(mesh, P()),
    )
    compiled = step_fn.lower(*avals).compile()
    assert "all-reduce" in compiled.as_text()
    # each device holds a quarter of every packed plane
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize for a in avals[:8]
    )
    # (plus the (8, 128) tile padding of 20-row planes)
    assert per_device < whole / 4 * 1.3


def _profile_step(chunk, mesh, plane, rows):
    """The fused step of the `profile10m` / `profile80m` cells: 20 pair
    columns with nulls, the source's five analyzers on each, planned the
    way ``_run_scan_once`` plans it. Returns (jitted step, abstract args,
    resolved plan)."""
    from deequ_tpu.analyzers import (
        Completeness, Maximum, Mean, Minimum, StandardDeviation,
    )
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.scan_engine import _build_step_fns, _ChunkPacker
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    rng = np.random.default_rng(3)
    table = ColumnarTable.from_columns([
        Column(f"c{i}", DType.FRACTIONAL, values=rng.normal(100.0 + i, 5.0, 512),
               mask=rng.random(512) >= 0.1)
        for i in range(20)
    ])
    analyzers = [
        a(c) for c in table.column_names
        for a in (Completeness, Mean, StandardDeviation, Minimum, Maximum)
    ]
    ops, scannable, failures = AnalysisRunner._build_scan_ops(table, analyzers)
    assert not failures and len(scannable) == 100
    exec_ops, _ = AnalysisRunner._coalesce_scan_ops(ops)
    packer = _ChunkPacker({c: table[c] for c in table.column_names}, chunk)
    assert len(packer.pair_names) == len(packer.masked_names) == 20
    plan = plan_scan_ops(exec_ops, packer, resident=True, rows=chunk)
    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    step_fn, _, _ = _build_step_fns(
        plan.ops, packer.unpack_view(), mesh, chunk // n_dev, ()
    )
    return step_fn, _chunk_avals(packer, chunk, plane, rows) + ({},), plan


def _row_sized_fusions(compiled, local_n):
    """(fusions of the entry computation, those that read or write an
    array with a row axis, the planes' parameter names)."""
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    lines = [line for line in entry.splitlines() if " fusion(" in line]
    # the halving tree's levels stop at local_n / 32 columns
    widths = {str(local_n >> k) for k in range(6)}
    shaped = re.compile(r"\[(?:\d+,)?(\d+)\]")
    row_sized = [
        line for line in lines
        if widths & set(shaped.findall(line.split(", kind=")[0]))
    ]
    planes = set(re.findall(
        rf"%(\S+) = \w+\[20,{local_n}\]\S* parameter\(", entry
    ))
    return lines, row_sized, planes


@pytest.mark.parametrize("chips", [1, 4])
def test_profile_step_reduces_the_planes_where_they_lie(as_tpu, topo, chips):
    """The guard of the plane route (PR 27): all 100 ops of the profiler
    suite read their scalars out of the batched plane statistics, and the
    compiled step pulls no column out of a plane: no fusion writes a 1-D
    f32[n] / pred[n] copy of a plane parameter (the per-column program
    held forty such re-layout copies, ~24 of its 46 ms a suite)."""
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from deequ_tpu.parallel.mesh import ROW_AXIS

    chunk = 1 << 20
    if chips == 1:
        mesh = None
        plane = rows = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices), (ROW_AXIS,))
        plane = NamedSharding(mesh, P(None, ROW_AXIS))
        rows = NamedSharding(mesh, P(ROW_AXIS))
    step_fn, avals, plan = _profile_step(chunk, mesh, plane, rows)
    assert plan.plane_ops == 100
    assert all(op.plane_route is plan.ops[0].plane_route for op in plan.ops)
    compiled = step_fn.lower(*avals).compile()
    local_n = chunk // chips
    fusions, row_sized, planes = _row_sized_fusions(compiled, local_n)
    assert len(planes) == 3  # hi, lo, masks
    # one row of a plane written out on its own, as f32[n] or f32[1,n]:
    # the per-column program wrote one per column and plane (reading
    # the plane itself, or a prefetched copy of it)
    column = re.compile(rf"(?:f32|pred)\[(?:1,)?{local_n}\]")
    for line in row_sized:
        assert not column.search(line.split(" fusion(")[0]), line
    assert 5 <= len(row_sized) <= 40
    if chips == 1:
        # (on a mesh each of the 220 state leaves adds a fusion of four
        # elements around its collective, as the per-column step did)
        assert len(fusions) <= 40
        assert "all-reduce" not in compiled.as_text()
    else:
        assert "all-reduce" in compiled.as_text()


def test_profile_step_fits_beside_the_table_at_10m_rows(as_tpu, one_chip):
    step_fn, avals, _ = _profile_step(10_000_000, None, one_chip, one_chip)
    mem = step_fn.lower(*avals).compile().memory_analysis()
    # the batched sweeps keep more alive than the per-column program did
    # (2.9 GB of temporaries against 0.8): still one program beside the
    # resident table in 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 14 << 30


def test_device_fold_merge_compiles_with_donation(as_tpu, one_chip):
    """Buffer donation is on only off-CPU (scan_engine._fold_plan_for):
    the donated multi-chunk merge must compile for the chip."""
    import jax

    from deequ_tpu.analyzers import Maximum, Mean, Minimum, Size
    from deequ_tpu.ops.scan_engine import _fold_plan_for

    table = chip_smoke.build_table(64, seed=1, n_numeric=2)
    ops = [a.scan_op(table) for a in (Size(), Mean("c0"), Minimum("c0"),
                                      Maximum("c1"))]
    shapes = [
        jax.tree.map(
            lambda tag: jax.ShapeDtypeStruct((), np.float64), op.tags
        )
        for op in ops
    ]
    plan = _fold_plan_for(ops, shapes, capacity=4)
    flat = sum(len(jax.tree.leaves(op.tags)) for op in ops)
    lowered = plan._merge_jit.lower(
        _aval((plan.acc_size,), np.float64, one_chip),
        _aval((flat,), np.float64, one_chip),
    )
    assert "donat" in lowered.as_text() or "alias" in lowered.as_text()
    assert lowered.compile() is not None


def test_profile_pass2_on_a_cast_view_compiles_at_the_cells_chunk(
        as_tpu, one_chip):
    """Pass 2 of ``mixed12m100.profile`` (PR 37) on the resident table's
    cast view, at the cell's resident chunk: the 45 fractional columns'
    statistics out of the plane sweeps, the 10 integral ones per column,
    and the 10 numbers-as-text columns read off the codes plane through
    their parsed-value LUTs (run-time arguments at their padded widths):
    one gather a LUT, under ``deequ.cast.<column>``, no scatter, and
    temporaries that fit beside the 8.6 GB table."""
    from chipbench import suite_build
    from chipbench.generators import mixed_table
    from deequ_tpu.analyzers import (
        Maximum, Mean, Minimum, StandardDeviation, Sum,
    )
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import DType
    from deequ_tpu.ops import scan_engine
    from deequ_tpu.ops.scan_engine import _build_step_fns, _ChunkPacker
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    from chipbench import cells

    config = cells.load_cell("mixed12m100.profile")["config"]
    params = copy.deepcopy(config["generator_params"])
    params["strings"]["dictionary_sizes"] = [8]
    table = suite_build.table_of(mixed_table.generate(512, 5, params))
    cols = {c: table[c] for c in table.column_names}
    chunk = min(scan_engine._auto_chunk_rows(
        cols, target_bytes=scan_engine.RESIDENT_CHUNK_BYTES,
        max_rows=scan_engine.MAX_RESIDENT_CHUNK_ROWS), config["rows"])
    casts = {f"t{i}": DType.INTEGRAL if i < 5 else DType.FRACTIONAL
             for i in range(10)}
    view = table.with_casts(casts)
    packer = _ChunkPacker(cols, chunk)
    view_packer = packer.cast_view(view.columns)
    assert view_packer.cast_names == list(casts)
    numeric = [f"c{i}" for i in range(45)] + [f"i{i}" for i in range(10)]
    analyzers = [a(c) for c in numeric + list(casts)
                 for a in (Minimum, Maximum, Mean, StandardDeviation, Sum)]
    ops, scannable, failures = AnalysisRunner._build_scan_ops(view, analyzers)
    assert not failures and len(scannable) == 325
    exec_ops, _ = AnalysisRunner._coalesce_scan_ops(ops)
    plan = plan_scan_ops(exec_ops, view_packer, resident=True, rows=chunk)
    assert plan.plane_ops == 225  # the pair columns; casts go per column
    widths = {"_cast_i32": 10_000, "_cast_hi": 8_192, "_cast_lo": 8_192}
    dtypes = {"_cast_i32": np.int32, "_cast_hi": np.float32,
              "_cast_lo": np.float32}
    luts = {}
    for name in casts:
        kinds = ("_cast_i32",) if casts[name] == DType.INTEGRAL else (
            "_cast_hi", "_cast_lo")
        for kind in kinds:
            luts[name + "\x00" + kind] = _aval(
                (1 << (widths[kind] - 1).bit_length(),), dtypes[kind],
                one_chip)
    step_fn, _, _ = _build_step_fns(
        plan.ops, view_packer.unpack_view(), None, chunk, tuple(sorted(luts)))
    compiled = step_fn.lower(
        *_chunk_avals(packer, chunk, one_chip, one_chip), luts).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\bgather\(", text)) == 15
    assert not re.search(r"\bscatter\(", text)
    assert "deequ.cast.t9" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30
