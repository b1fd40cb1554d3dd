"""Pluggable filesystem (DfsUtils analogue) + multi-host helper tests."""

import numpy as np
import pytest

from deequ_tpu.data.fs import (
    InMemoryFileSystem,
    LocalFileSystem,
    filesystem_for,
    register_filesystem,
    strip_scheme,
)


def test_local_resolution():
    assert filesystem_for("/tmp/x") is filesystem_for("/var/y")
    assert isinstance(filesystem_for("/tmp/x"), LocalFileSystem)
    assert isinstance(filesystem_for("file:///tmp/x"), LocalFileSystem)
    assert strip_scheme("file:///tmp/x") == "/tmp/x"
    assert strip_scheme("/tmp/x") == "/tmp/x"
    assert strip_scheme("mem://bucket/x") == "mem://bucket/x"


def test_registered_scheme_backs_state_provider():
    """FileSystemStateProvider works against any registered filesystem —
    the storage-agnostic contract of HdfsStateProvider (StateProvider.scala
    via io/DfsUtils.scala)."""
    from deequ_tpu.analyzers import Mean
    from deequ_tpu.analyzers.states import MeanState
    from deequ_tpu.states import FileSystemStateProvider

    mem = InMemoryFileSystem()
    register_filesystem("mem", lambda path: mem)

    provider = FileSystemStateProvider("mem://bucket/states")
    provider.persist(Mean("x"), MeanState(10.0, 4))
    assert any(k.startswith("mem://bucket/states/") for k in mem.files)
    assert provider.load(Mean("x")) == MeanState(10.0, 4)
    assert provider.load(Mean("other")) is None


def test_registered_scheme_backs_metrics_repository():
    from deequ_tpu.analyzers import Size
    from deequ_tpu.analyzers.runner import AnalyzerContext
    from deequ_tpu.metrics import DoubleMetric, Entity
    from deequ_tpu.repository import AnalysisResult, ResultKey
    from deequ_tpu.repository.fs import FileSystemMetricsRepository
    from deequ_tpu.tryresult import Success

    mem = InMemoryFileSystem()
    register_filesystem("mem", lambda path: mem)

    repo = FileSystemMetricsRepository("mem://bucket/metrics.json")
    key = ResultKey(1000, {"env": "test"})
    ctx = AnalyzerContext(
        {Size(): DoubleMetric(Entity.DATASET, "Size", "*", Success(5.0))}
    )
    repo.save(AnalysisResult(key, ctx))
    assert "mem://bucket/metrics.json" in mem.files

    loaded = repo.load_by_key(key)
    assert loaded is not None
    assert loaded.analyzer_context.metric_map[Size()].value.get() == 5.0


def test_host_row_range_balanced(monkeypatch):
    """Edge cases from the round-1 review (#10): 0 rows, n_proc > rows, balance."""
    import jax

    from deequ_tpu.parallel.distributed import host_row_range

    def patch(n_proc, pid):
        monkeypatch.setattr(jax, "process_count", lambda: n_proc)
        monkeypatch.setattr(jax, "process_index", lambda: pid)

    # balanced split, union covers everything exactly once
    for total, n_proc in [(10, 3), (8, 8), (0, 4), (3, 8), (100, 1)]:
        seen = []
        for pid in range(n_proc):
            patch(n_proc, pid)
            start, stop = host_row_range(total)
            assert 0 <= start <= stop <= total
            seen.extend(range(start, stop))
        assert seen == list(range(total)), (total, n_proc)

    # single process owns the whole table
    patch(1, 0)
    assert host_row_range(7) == (0, 7)


def test_multihost_cross_process_state_merge():
    """Execute the multi-host (DCN) path end to end: two real OS processes
    under jax.distributed, per-host shard ingestion via host_row_range,
    per-host fused-scan states over the local mesh, cross-process
    all_gather exchange over the global mesh, and monoid fold — merged
    metrics must equal a single-host full-table run (SURVEY.md §2.15)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    try:
        g.dryrun_multihost(2, devices_per_process=2)
    except RuntimeError as e:
        # some jax builds ship a CPU backend without multiprocess
        # collectives: the cross-process all_gather (the one DCN-tier
        # exchange this test exists to execute) raises INVALID_ARGUMENT
        # in every worker. That is a missing-capability condition of the
        # build, not a regression in the merge path — skip with the
        # detected signature so a REAL merge failure still fails loudly.
        if "Multiprocess computations aren't implemented" in str(e):
            pytest.skip(
                "this jax build lacks CPU multiprocess collectives "
                "(cross-process all_gather raises INVALID_ARGUMENT: "
                "'Multiprocess computations aren't implemented on the "
                "CPU backend'); the multi-host exchange needs a real "
                "multi-host backend"
            )
        raise
