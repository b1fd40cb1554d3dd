"""The main path's device programs, compiled for a DESCRIBED TPU v5e at the
shapes chip_smoke.py runs (on-chip-measurement guide §2, rehearsal 3).

The sandbox has no chip, but the TPU compiler is installed and compiles for
a topology that is described, not attached — so what Mosaic or XLA:TPU
would refuse on the machine with the chip is refused here, at no chip
time. Nothing runs: these tests say nothing about results or speed.

Code that asks ``jax.default_backend()`` / ``jax.devices()`` still sees the
CPU here and would take its CPU branch (f32 one-hot planes, scatter HLL
fold, no donation); the ``as_tpu`` fixture steers it inside the test.

Every chip-related call lives in a fixture or a test body — never at
import time, in conftest.py, in a ``skipif`` or in ``parametrize`` — because
only one process may load the TPU library and every xdist worker imports
this file. Keep these tests in ONE file (one worker owns the library).
"""

import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TOPOLOGY = "v5e:2x2"
SMOKE_ROWS = chip_smoke.RESIDENT_ROWS


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) 4-chip v5e host, with the persistent
    compile cache off around every compile of this module: an entry
    written for a described chip cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name=TOPOLOGY
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no {TOPOLOGY} topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch, topo):
    """Steer the code that asks which backend it runs on: inside the test
    the default backend reads "tpu" and ``jax.devices()`` lists the
    described chips."""
    import jax

    real_devices = jax.devices

    def devices(backend=None):
        return list(topo.devices) if backend is None else real_devices(backend)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", devices)


def _aval(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *avals):
    import jax

    return jax.jit(fn).lower(*avals).compile()


def _chunk_avals(packer, chunk, plane, rows):
    """Abstract (values, hi, lo, narrow_i, masks, codes, row_valid, enc)
    for one packed chunk — the shapes ``_ChunkPacker.pack`` would emit."""
    return (
        _aval((len(packer.wide_names), chunk), np.float64, plane),
        _aval((len(packer.pair_names), chunk), np.float32, plane),
        _aval((len(packer.pair_names), chunk), np.float32, plane),
        _aval((len(packer.narrow_i32), chunk), np.int32, plane),
        _aval((len(packer.masked_names), chunk), np.bool_, plane),
        _aval((len(packer.string_names), chunk), np.int32, plane),
        _aval((chunk,), np.bool_, rows),
        _aval((len(packer.enc_names), chunk), np.int16, plane),
    )


def _smoke_step(chunk, mesh, plane, rows, replicated, resident=True):
    """The fused step of chip_smoke's resident suite, built the way
    ``scan_engine._run_scan_once`` builds it, at ``chunk`` abstract rows.
    Returns (jitted step, abstract args, resolved plan)."""
    from deequ_tpu.analyzers.base import ScanShareableAnalyzer
    from deequ_tpu.analyzers.runner import AnalysisRunner, _is_grouping_shared
    from deequ_tpu.ops.lut_cache import dictionary_lut
    from deequ_tpu.ops.scan_engine import _build_step_fns, _ChunkPacker
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    table = chip_smoke.build_table(4096, seed=21)
    scanning = [
        a for a in chip_smoke.suite_analyzers()
        if isinstance(a, ScanShareableAnalyzer) and not _is_grouping_shared(a)
    ]
    ops, scannable, failures = AnalysisRunner._build_scan_ops(table, scanning)
    assert not failures and len(scannable) == len(scanning)
    exec_ops, _ = AnalysisRunner._coalesce_scan_ops(ops)
    packer = _ChunkPacker(
        {n: table[n] for n in table.column_names}, chunk, encode_ingest=True
    )
    plan = plan_scan_ops(
        exec_ops, packer, resident=resident, select_kernel=True, rows=chunk
    )
    luts = {}
    for op in plan.ops:
        for col, kind, builder in op.luts:
            host = dictionary_lut(table[col].dictionary, kind, builder)
            # the smoke's wide dictionary holds WIDE_CARD entries: the LUT
            # is a runtime argument, so only its pow2-padded length matters
            width = 1 << (chip_smoke.WIDE_CARD - 1).bit_length() \
                if col == "ustr" else 1 << max(len(host) - 1, 0).bit_length()
            luts[col + "\x00" + kind] = _aval((width,), host.dtype, replicated)
    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    step_fn, _, _ = _build_step_fns(
        plan.ops, packer.unpack_view(), mesh, chunk // n_dev,
        tuple(sorted(luts)),
    )
    return step_fn, _chunk_avals(packer, chunk, plane, rows) + (luts,), plan


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


def test_hll_mxu_fold_compiles(as_tpu, one_chip):
    import jax.numpy as jnp

    from deequ_tpu.ops import hll

    def fold(idx, rank, valid):
        return hll.registers_from_idx_rank(idx, rank, valid, 9, jnp)

    import jax

    lowered = jax.jit(fold).lower(
        _aval((SMOKE_ROWS,), np.int32, one_chip),
        _aval((SMOKE_ROWS,), np.int32, one_chip),
        _aval((SMOKE_ROWS,), np.bool_, one_chip),
    )
    # the MXU branch (one-hot bf16 matmul), not the scatter segment_max
    assert "bf16" in lowered.as_text()
    text = lowered.compile().as_text()
    assert "convolution" in text and "scatter" not in text


def test_vmapped_hll_fold_compiles_without_a_batched_convolution(
    as_tpu, one_chip
):
    """The tenant-axis form of the fold (serving, 8 members): it must lower
    to the UNBATCHED one-hot convolution inside a loop — XLA:TPU returned
    zeros for half of a batch-8 convolution on the chip (PR 21)."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops import hll

    K, n = 8, chip_smoke.SERVE_ROWS
    compiled = _compile(
        jax.vmap(lambda i, r: hll._registers_mxu_fold(i, r, 512, jnp)),
        _aval((K, n), np.int32, one_chip),
        _aval((K, n), np.int32, one_chip),
    )
    text = compiled.as_text()
    assert "convolution" in text and "while" in text


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


def test_selection_kernel_compiles_at_config3_width(as_tpu, one_chip):
    """BASELINE config 3: 50 quantile columns (k=256) over one resident
    chunk of the benchmark's cell ``quantiles12m50.qscan`` (2 GiB over 450
    bytes a row: a row count no block divides), every histogram pass on
    the one-hot tier as the chip routes, and the step's temporaries small
    enough to stand beside the cell's 5.63 GB resident table."""
    import jax.numpy as jnp

    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.histogram_device import active_hist_variant
    from deequ_tpu.ops.select_device import chunk_summary_select_batched

    K, n, k = 50, (2 << 30) // 450, 256
    variant = resolve_hist_variant((1 << 16, (k + 2) * 256 + 1), rows=n)
    assert variant == "onehot"

    def summaries(x, valid, lo):
        with active_hist_variant(variant):
            return chunk_summary_select_batched(x, valid, k, n, jnp, lo=lo)

    compiled = _compile(
        summaries,
        _aval((K, n), np.float32, one_chip),
        _aval((K, n), np.bool_, one_chip),
        _aval((K, n), np.float32, one_chip),
    )
    # a sort INSTRUCTION, not the word: the module's text also lists the
    # call stack of whoever first traced the cached bincount program (a
    # test named "..._sort_reference_..." when it shares this worker)
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    # no walk over the n elements: the passes are matmuls, the compaction
    # gathers W slots
    assert "convolution" in text and not re.search(r"\bscatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    # the remainder's slots by COUNTING (PERF.md section 6, PR 35): no
    # running count over the n elements anywhere in the step (the longest
    # is over the 4 x 37,283 words of a plane's 32-element groups) and no
    # loop of gathers in the extraction (a binary search a slot was 23
    # dependent gathers, 5.4 ms a column on the chip)
    windows = [
        line for line in text.splitlines() if re.search(r"\breduce-window\(", line)
    ]
    assert windows
    for line in windows:
        shape = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
        assert np.prod([int(d) for d in shape.split(",") if d]) <= n // 16, line
    assert not [
        line for line in text.splitlines()
        if re.search(r"\bwhile\(", line) and "deequ.select.extract" in line
    ]
    # pass 1 is the program passes 2-3 are: its matmul fusion reads the
    # leading digits as an operand (the barrier held through the TPU
    # pipeline) and the compiler builds all three with one emitter. At 256
    # rows against an iota on both sides it picked another, four times
    # slower on the chip (PERF.md section 6, PR 31)
    computations, emitters = text.split("\n}\n"), {}
    for scope in ("pass1", "pass2", "pass3"):
        (computation,) = [
            c for c in computations
            if re.search(rf"convolution\(.*deequ\.select\.{scope}/", c)
        ]
        name = re.match(r"(%\S+) \(", computation.lstrip()).group(1)
        (call,) = [
            line for line in text.splitlines() if f"calls={name}," in line
        ]
        emitters[scope] = re.search(r'"emitter":"(\w+)"', call).group(1)
        if scope == "pass1":
            assert re.search(r"= s32\[\d+\]\S* parameter\(", computation)
    assert emitters["pass1"] == emitters["pass2"] == emitters["pass3"], emitters


def test_coalesced_tenant_step_compiles(topo, one_chip, monkeypatch):
    """The vmapped packed program of chip_smoke's serving phase: the plan
    is captured from the real service on a CPU run of tiny tenants, then
    its program is rebuilt and compiled at the smoke's (submits, rows)."""
    import dataclasses

    import jax

    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve import VerificationService, executor

    captured = []
    real_build = executor._build_packed_program

    def capture(plan, lut_keys, op_order=None):
        captured.append((plan, lut_keys, op_order))
        return real_build(plan, lut_keys, op_order=op_order)

    rows, tenants = 4096, 4
    with monkeypatch.context() as capture_run, use_mesh(None):
        capture_run.setattr(executor, "_build_packed_program", capture)
        service = VerificationService(max_batch=tenants)
        try:
            futures = [
                service.submit(
                    chip_smoke._tenant_table(rows, 100 + t),
                    [chip_smoke._tenant_check(rows)], tenant=f"t{t}",
                )
                for t in range(tenants)
            ]
            for f in futures:
                f.result(timeout=120)
        finally:
            service.stop()
    assert captured, "the service never built a coalesced program"
    plan, lut_keys, op_order = captured[-1]
    assert not lut_keys  # numeric tenants: no dictionary LUT arguments
    layout = plan.layout
    # the satisfies predicate routed x over the exact wide-f64 plane
    assert "x" in layout["wide"]

    # the same plan at the smoke's row count, traced as the chip would
    K, n = chip_smoke.SERVE_SUBMITS, chip_smoke.SERVE_ROWS
    big = dataclasses.replace(
        plan, key=dataclasses.replace(plan.key, chunk=n)
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: list(topo.devices))
    _tree, _flat, vstep = real_build(big, lut_keys, op_order=op_order)
    avals = (
        _aval((K, len(layout["wide"]), n), np.float64, one_chip),
        _aval((K, len(layout["pair"]), n), np.float32, one_chip),
        _aval((K, len(layout["pair"]), n), np.float32, one_chip),
        _aval((K, len(layout["narrow_i32"]), n), np.int32, one_chip),
        _aval((K, len(layout["masked"]), n), np.bool_, one_chip),
        _aval((K, 0, n), np.int32, one_chip),
        _aval((K, n), np.bool_, one_chip),
        _aval((K, len(layout.get("enc", ())), n), np.int16, one_chip),
        {},
    )
    assert vstep.lower(*avals).compile() is not None


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


def test_grouping_sort_kernels_compile(one_chip):
    """Uniqueness over the smoke's near-unique int64 key: the device
    unique/inverse sort and the sparse run-length stats, 64-bit keys."""
    from deequ_tpu.ops import segment

    n = SMOKE_ROWS
    values = _aval((n,), np.int64, one_chip)
    valid = _aval((n,), np.bool_, one_chip)
    assert segment._unique_inverse_kernel.lower(values, valid).compile()
    codes = _aval((1, n), np.int64, one_chip)
    assert segment._rle_stats_kernel.lower(codes, valid).compile()


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


STRING_ROWS = 12_500_000  # the cell strings12m.sscan: one resident chunk
STRING_CLASSES = (50_000, 250_000, 1_000_000, 3_000_000)


def _emitters(text, scope):
    """The emitter XLA:TPU chose for each matmul fusion whose convolution
    carries ``scope`` in its op name (the fusion call's
    ``convolution_algorithm_config``)."""
    found = set()
    for computation in text.split("\n}\n"):
        if not re.search(rf"convolution\(.*{re.escape(scope)}", computation):
            continue
        name = re.match(r"(%\S+) \(", computation.lstrip()).group(1)
        for line in text.splitlines():
            if f"calls={name}," in line:
                found.add(re.search(r'"emitter":"(\w+)"', line).group(1))
    return found


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


def test_pane_step_compiles_in_f64(one_chip):
    from deequ_tpu.analyzers import (
        Completeness, Maximum, Mean, Minimum, Size, Sum,
    )
    from deequ_tpu.windows.engine import (
        _data_columns, _make_step, pane_signature,
    )

    sig = pane_signature([
        Size(), Completeness("v"), Mean("v"), Minimum("v"), Maximum("v"),
        Sum("v"),
    ])
    data_cols = _data_columns(sig)
    n, panes = chip_smoke.WINDOW_BATCH_ROWS, 4
    compiled = _compile(
        _make_step(sig, 20.0, data_cols),
        _aval((n,), np.float64, one_chip),       # event times
        _aval((panes,), np.float64, one_chip),   # pane starts
        _aval((), np.float64, one_chip),         # watermark fence
        *[_aval((n,), np.float64, one_chip) for _ in data_cols],
        *[_aval((n,), np.bool_, one_chip) for _ in data_cols],
    )
    assert compiled is not None


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
