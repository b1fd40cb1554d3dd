"""Shared fixtures and helpers of the compile guards, the
``test_chip_compile_*.py`` files: the main path's device programs,
compiled for a DESCRIBED TPU v5e at the shapes chip_smoke.py and the
benchmark's cells run.

A host without a chip still has the TPU compiler, which compiles for a
topology that is described, not attached — so what Mosaic or XLA:TPU
would refuse on a machine with the chip is refused on the CPU host, at no
chip time. Nothing runs: these tests say nothing about results or speed.

Code that asks ``jax.default_backend()`` / ``jax.devices()`` still sees the
CPU here and would take its CPU branch (f32 one-hot planes, scatter HLL
fold, no donation); the ``as_tpu`` fixture steers it inside the test.

Every chip-related call lives in a fixture or a test body — never at
import time, in conftest.py, in a ``skipif`` or in ``parametrize`` —
because every xdist worker imports every test file. The guards are split
by kernel family so that the workers compile them side by side: each
worker given a file describes the chip and loads the TPU library, which
holds a lock file unless the environment sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, as the tier-1 command does (without
it the second worker's guards skip). Give a new guard the file of its
family, and keep each file within about 250 s of the tier-1 run.
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
STRING_ROWS = 12_500_000  # the cell strings12m.sscan: one resident chunk
STRING_CLASSES = (50_000, 250_000, 1_000_000, 3_000_000)
LINEITEM_ROWS = 59_986_052  # the cell lineitem-sf10.lcheck: TPC-H SF10
# the SF10 key set (l_orderkey, l_linenumber) on the narrow-i32 plane
LINEITEM_KEY_SPECS = (("narrow", 0, -1), ("narrow", 3, -1))


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) 4-chip v5e host, with the persistent
    compile cache off around every compile of the module: an entry
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


def _lineitem_chunk_avals(chunk, n_chunks, sharding):
    """Abstract ``(narrow_i, masks, codes, row_valid)`` of each resident
    chunk of the SF10 LINEITEM table: 7 narrow-i32 columns, no masks, 5
    string code rows."""
    args = []
    for _ in range(n_chunks):
        args += [_aval((7, chunk), np.int32, sharding),
                 _aval((0, chunk), np.bool_, sharding),
                 _aval((5, chunk), np.int32, sharding),
                 _aval((chunk,), np.bool_, sharding)]
    return args


def _sort_results(text):
    """The result type of every sort in HLO text, layouts dropped."""
    return [re.sub(r"\{[^}]*\}", "", s)
            for s in re.findall(r"= (\(.*?\)|\S+) sort\(", text)]


def _scatter_results(text):
    """The result type of every scatter in compiled HLO text."""
    return re.findall(r"= (\(.*?\)|\S+) scatter\(", text)


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
