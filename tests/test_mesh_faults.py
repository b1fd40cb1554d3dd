"""Mesh-fault tolerance: degraded-mesh re-sharding, per-chip fault
attribution, shard-level straggler deadlines, multi-host peer loss, and
partial-result semantics (exceptions.py + ops/device_policy.py:MeshHealth
+ ops/scan_engine.py:run_scan + parallel/distributed.py).

Runs on the 8 forced host-platform CPU devices (conftest) via the
deterministic scan-fault hook — the chip losses are scripted, the
recovery machinery (mesh rebuild, shard re-pack, re-dispatch, monoid
refold) is real. The acceptance pair is the flagship: a scripted
DeviceLost on one mesh position mid-scan completes on the surviving 7
devices with metrics bit-identical to a healthy 7-device run, the
reshard lands on ``VerificationResult.mesh_events``, and NO path falls
back to the CPU while a healthy accelerator subset remains.
"""

import math

import numpy as np
import pytest

from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data.streaming import StreamingTable, stream_table
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import (
    DeviceHangException,
    DeviceLostException,
    DeviceOOMException,
    MeshDegradedException,
    PeerLostException,
    classify_device_error,
    implicated_devices,
)
from deequ_tpu.ops.device_policy import (
    DEVICE_HEALTH,
    MESH_HEALTH,
    MeshHealth,
)
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    install_scan_fault_hook,
    persist_table,
    run_scan,
    resident_bytes_per_device,
    total_resident_bytes,
)
from deequ_tpu.parallel.mesh import (
    current_mesh,
    mesh_device_ids,
    mesh_excluding,
    use_mesh,
)
from deequ_tpu.resilience import (
    FaultInjectingScanHook,
    FaultSchedule,
)
from deequ_tpu.verification import VerificationSuite

pytestmark = pytest.mark.meshfault


@pytest.fixture(autouse=True)
def _clean_mesh_state():
    """Each test starts with a healthy backend/mesh and no installed
    hook."""
    DEVICE_HEALTH.reset()
    MESH_HEALTH.reset()
    prev = install_scan_fault_hook(None)
    yield
    install_scan_fault_hook(prev)
    DEVICE_HEALTH.reset()
    MESH_HEALTH.reset()


@pytest.fixture
def mesh8():
    mesh = current_mesh()
    if mesh is None or math.prod(mesh.devices.shape) < 8:
        pytest.skip("needs the 8 forced host-platform devices")
    return mesh


def scan_faults(hook):
    from contextlib import contextmanager

    @contextmanager
    def cm():
        prev = install_scan_fault_hook(hook)
        try:
            yield hook
        finally:
            install_scan_fault_hook(prev)

    return cm()


def int_table(n=2000, seed=0):
    """Integer-valued columns: every partial-state sum is exact in f64,
    so 'bit-identical across mesh shapes' is a fair assertion (a reshard
    changes the per-device reduction association)."""
    rng = np.random.default_rng(seed)
    return ColumnarTable(
        [
            Column(
                "x", DType.FRACTIONAL,
                values=rng.integers(0, 100, n).astype(np.float64),
            ),
            Column(
                "g", DType.INTEGRAL,
                values=rng.integers(0, 7, n).astype(np.int64),
            ),
        ]
    )


def basic_analyzers():
    from deequ_tpu.analyzers import (
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
    )

    return [Size(), Completeness("x"), Mean("x"), Minimum("x"), Maximum("x")]


def scan_ops(table):
    ops = []
    for a in basic_analyzers():
        op = a.scan_op(table)
        op.cache_key = a
        ops.append(op)
    return ops


def checks_for(n):
    return (
        Check(CheckLevel.ERROR, "meshfault")
        .is_complete("x")
        .has_size(lambda s: s == n)
        .has_mean("x", lambda v: v > 0)
        .has_min("x", lambda v: v >= 0)
    )


def metric_values(result):
    return {
        repr(a): m.value.get()
        for a, m in result.metrics.items()
        if m.value.is_success
    }


def assert_results_equal(got, want):
    import jax

    for g, w in zip(got, want):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- taxonomy: attribution ---------------------------------------------------


@pytest.mark.parametrize(
    "message,expected_ids",
    [
        ("UNAVAILABLE: injected device halt; device 3 is lost", (3,)),
        ("INTERNAL: TPU_2 halted during all-reduce", (2,)),
        ("ABORTED: collective timed out on chip #5", (5,)),
        ("UNAVAILABLE: device is lost; halting execution", ()),
        (
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "8589934592 bytes.",
            (),
        ),
        # device ENUMERATIONS name the set, not a culprit — a
        # whole-backend loss listing its devices must not be pinned on
        # the first chip in the list
        ("INTERNAL: no visible devices: 0,1", ()),
        ("UNAVAILABLE: backend lost; visible devices: 0,1,2,3", ()),
    ],
)
def test_implicated_devices_extraction(message, expected_ids):
    assert implicated_devices(RuntimeError(message)) == expected_ids


def test_attributed_loss_classifies_as_mesh_degraded():
    """A loss the message pins on a chip is a MESH fault (the rest of the
    mesh is presumed healthy); an unattributed loss stays whole-backend."""
    typed = classify_device_error(
        RuntimeError("UNAVAILABLE: device 3 is lost"), "execute"
    )
    assert isinstance(typed, MeshDegradedException)
    assert typed.device_ids == (3,)
    # MeshDegraded IS a DeviceException — every existing policy that
    # catches the family still sees it
    assert isinstance(typed, DeviceLostException) is False
    untyped = classify_device_error(
        RuntimeError("UNAVAILABLE: device is lost"), "execute"
    )
    assert isinstance(untyped, DeviceLostException)
    assert untyped.device_ids == ()


def test_attributed_oom_keeps_oom_type_with_device_ids():
    typed = classify_device_error(
        RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "1024 bytes on device 5."
        ),
        "execute",
    )
    assert isinstance(typed, DeviceOOMException)
    assert typed.device_ids == (5,)


# -- MeshHealth --------------------------------------------------------------


def test_mesh_health_quarantine_and_half_open_probe():
    mh = MeshHealth(threshold=2, probe_interval=4)
    # a lost chip quarantines immediately
    mh.record_fault(MeshDegradedException("d3 gone", device_ids=(3,)))
    assert mh.quarantined() == frozenset({3})
    # a per-chip OOM counts one step toward the threshold
    mh.record_fault(DeviceOOMException("oom on 5", device_ids=(5,)))
    assert 5 not in mh.quarantined()
    mh.record_fault(DeviceOOMException("oom on 5", device_ids=(5,)))
    assert mh.quarantined() == frozenset({3, 5})

    ids = list(range(8))
    decisions = [mh.healthy_subset(ids) for _ in range(8)]
    # every probe_interval-th exclusion decision readmits for a probe
    probes = [d for d in decisions if not d[1]]
    assert len(probes) == 2
    excludes = [d for d in decisions if d[1]]
    for healthy, excluded in excludes:
        assert sorted(excluded) == [3, 5]
        assert sorted(healthy) == [0, 1, 2, 4, 6, 7]
    # one successful pass over the probed chips forgives
    mh.record_success(ids)
    assert mh.quarantined() == frozenset()
    assert mh.healthy_subset(ids) == (ids, [])


def test_mesh_health_unattributable_fault_is_noop():
    mh = MeshHealth()
    mh.record_fault(DeviceLostException("whole backend gone"))
    assert mh.quarantined() == frozenset()
    assert mh.consecutive_faults == {}


# -- ACCEPTANCE: chip loss mid-scan -> reshard, bit-identical ----------------


def test_chip_loss_reshards_bit_identical_to_healthy_7dev_run(mesh8):
    """ACCEPTANCE: a scripted DeviceLost on mesh position 3 mid-scan
    completes on the 7 survivors with metrics bit-identical to a healthy
    7-device run; the reshard is recorded; the CPU fallback is never
    touched while a healthy accelerator subset remains."""
    table = int_table(4096, seed=1)
    lost_id = mesh_device_ids(mesh8)[3]

    with use_mesh(mesh_excluding(mesh8, {lost_id})):
        healthy7 = run_scan(table, scan_ops(table))

    SCAN_STATS.reset()
    hook = FaultInjectingScanHook(
        faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
    )
    with scan_faults(hook):
        # on_device_error="fallback" armed ON PURPOSE: the assertion is
        # that resharding wins BEFORE the fallback ladder even though the
        # fallback is available
        degraded = run_scan(
            table, scan_ops(table), on_device_error="fallback"
        )

    assert hook.injected == [("lost", 0, 0, lost_id)]
    assert SCAN_STATS.mesh_reshards == 1
    assert SCAN_STATS.fallback_scans == 0, "fell back with 7 healthy chips"
    (event,) = [
        e for e in SCAN_STATS.degradation_events if e["kind"] == "mesh_reshard"
    ]
    assert event["lost_devices"] == [lost_id]
    assert event["mesh_from"] == 8 and event["mesh_to"] == 7
    assert_results_equal(degraded, healthy7)
    # the dead chip is quarantined for future scans
    assert lost_id in MESH_HEALTH.quarantined()


def test_chip_loss_acceptance_through_verification_suite(mesh8):
    """The same acceptance through the flagship entry point: the reshard
    lands on VerificationResult.mesh_events / .resharded and the checks
    pass with metrics equal to the healthy 7-device run's."""
    n = 2000
    table = int_table(n, seed=2)
    check = checks_for(n)
    lost_id = mesh_device_ids(mesh8)[3]

    with use_mesh(mesh_excluding(mesh8, {lost_id})):
        ref = VerificationSuite.on_data(table).add_check(check).run()
    assert ref.status == CheckStatus.SUCCESS

    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
        )
    ):
        result = VerificationSuite.on_data(table).add_check(check).run()

    assert result.status == CheckStatus.SUCCESS
    assert result.resharded
    assert any(e["kind"] == "mesh_reshard" for e in result.mesh_events)
    assert result.fallback_backend is None
    assert result.unverified_row_ranges == []
    assert metric_values(result) == metric_values(ref)
    # the clean reference run did not reshard
    assert ref.resharded is False and ref.mesh_events == []


def test_two_chip_loss_reshards_twice(mesh8):
    """Losing two chips (sequentially attributed) shrinks 8 -> 7 -> 6 and
    still completes on the accelerator subset."""
    table = int_table(2048, seed=3)
    ids = mesh_device_ids(mesh8)
    with use_mesh(mesh_excluding(mesh8, {ids[1], ids[6]})):
        healthy6 = run_scan(table, scan_ops(table))

    SCAN_STATS.reset()
    hook = FaultInjectingScanHook(
        faults={0: ("lost", FaultSchedule.PERMANENT, ids[1])}
    )
    # device ids[6] dies too, scripted as a second hook entry keyed on the
    # same scan via a wrapper: ids[1] faults while present, then ids[6]
    second = FaultInjectingScanHook(
        faults={0: ("lost", FaultSchedule.PERMANENT, ids[6])}
    )

    def both(boundary, ctx):
        hook(boundary, ctx)
        second(boundary, ctx)

    with scan_faults(both):
        degraded = run_scan(table, scan_ops(table))
    assert SCAN_STATS.mesh_reshards == 2
    assert SCAN_STATS.fallback_scans == 0
    assert_results_equal(degraded, healthy6)


def test_quarantined_chip_excluded_up_front(mesh8):
    """After a reshard quarantines a chip, the NEXT scan builds its mesh
    without it immediately (mesh_quarantine event) instead of re-failing
    into the dead member first."""
    table = int_table(1024, seed=4)
    lost_id = mesh_device_ids(mesh8)[2]
    # the chip is dead for EVERY scan — any dispatch to it would fault
    hook = FaultInjectingScanHook(
        faults={
            i: ("lost", FaultSchedule.PERMANENT, lost_id) for i in range(8)
        }
    )
    with scan_faults(hook):
        run_scan(table, scan_ops(table))
        assert lost_id in MESH_HEALTH.quarantined()
        SCAN_STATS.reset()
        n_injected = len(hook.injected)
        run_scan(table, scan_ops(table))
    # no new injection: the dead chip was never dispatched to again
    assert len(hook.injected) == n_injected
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "mesh_quarantine" in kinds and "mesh_reshard" not in kinds


def test_reshard_composes_with_oom_bisection(mesh8):
    """A chip loss (reshard) and a transient whole-mesh OOM (bisection)
    in the same logical scan both degrade gracefully; metrics stay
    bit-identical to the healthy 7-device run."""
    table = int_table(4096, seed=5)
    lost_id = mesh_device_ids(mesh8)[3]
    with use_mesh(mesh_excluding(mesh8, {lost_id})):
        healthy7 = run_scan(table, scan_ops(table), chunk_rows=1024)

    SCAN_STATS.reset()
    lost_hook = FaultInjectingScanHook(
        faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
    )
    # untargeted transient OOM that fires on the post-reshard attempt
    oom_hook = FaultInjectingScanHook(faults={0: ("oom", 2)})

    def both(boundary, ctx):
        lost_hook(boundary, ctx)
        oom_hook(boundary, ctx)

    with scan_faults(both):
        degraded = run_scan(table, scan_ops(table), chunk_rows=1024)
    assert SCAN_STATS.mesh_reshards == 1
    assert SCAN_STATS.oom_bisections >= 1
    assert SCAN_STATS.fallback_scans == 0
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "mesh_reshard" in kinds and "oom_bisect" in kinds
    # chunk geometry differs after bisection, but the monoid fold keeps
    # the METRICS identical (integer-valued data: exact f64 sums)
    import jax

    for g, w in zip(degraded, healthy7):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float64),
                np.asarray(b, dtype=np.float64),
            )


def test_per_chip_oom_at_floor_sheds_chip_before_cpu(mesh8):
    """An OOM the message pins on ONE chip, persisting through bisection
    to the floor, sheds that chip (reshard) instead of abandoning all
    eight to the CPU."""
    table = int_table(512, seed=6)
    sick_id = mesh_device_ids(mesh8)[5]
    with use_mesh(mesh_excluding(mesh8, {sick_id})):
        healthy7 = run_scan(table, scan_ops(table))

    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("oom", FaultSchedule.PERMANENT, sick_id)}
        )
    ):
        degraded = run_scan(
            table, scan_ops(table), on_device_error="fallback"
        )
    assert SCAN_STATS.mesh_reshards == 1
    assert SCAN_STATS.fallback_scans == 0
    assert_results_equal(degraded, healthy7)


def test_reshard_restores_chunk_size_after_floor_bisection(mesh8):
    """A per-chip OOM that bisected to the floor must NOT pin the
    post-reshard scan at floor-sized (~64-row) dispatches: the pressure
    left with the chip, so the retry on the healthy mesh restarts at the
    caller's chunk size."""
    table = int_table(4096, seed=18)
    sick_id = mesh_device_ids(mesh8)[5]
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("oom", FaultSchedule.PERMANENT, sick_id)}
        )
    ):
        run_scan(table, scan_ops(table), chunk_rows=1024)
    assert SCAN_STATS.mesh_reshards == 1
    # 4096 rows at the caller's chunk (1024 -> 1029 rounded to 7 devices)
    # is 4 chunks; a floor-pinned retry would have processed ~65
    assert SCAN_STATS.chunks_processed == 4, SCAN_STATS.chunks_processed


def test_all_chips_lost_falls_through_to_cpu_fallback(mesh8):
    """Only when NO accelerator subset remains does the run take the CPU
    fallback — the ladder's last rung, not its first."""
    table = int_table(512, seed=7)
    ids = mesh_device_ids(mesh8)
    hooks = [
        FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, d)}
        )
        for d in ids
    ]

    def all_dead(boundary, ctx):
        for h in hooks:
            h(boundary, ctx)

    clean = run_scan(table, scan_ops(table))
    SCAN_STATS.reset()
    with scan_faults(all_dead):
        result = run_scan(
            table, scan_ops(table), on_device_error="fallback"
        )
    assert SCAN_STATS.fallback_scans == 1
    assert SCAN_STATS.mesh_reshards >= 1  # it kept shrinking first
    assert_results_equal(result, clean)


def test_all_chips_lost_without_fallback_raises_typed(mesh8):
    table = int_table(256, seed=8)
    ids = mesh_device_ids(mesh8)
    hooks = [
        FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, d)}
        )
        for d in ids
    ]

    def all_dead(boundary, ctx):
        for h in hooks:
            h(boundary, ctx)

    with scan_faults(all_dead):
        with pytest.raises(MeshDegradedException):
            run_scan(table, scan_ops(table))


# -- straggler deadline ------------------------------------------------------


def test_shard_deadline_converts_straggler_to_typed_failure(mesh8):
    """A chip stalling a mesh dispatch past the shard deadline raises a
    typed DeviceHangException recorded as a mesh_straggler event."""
    table = int_table(512, seed=9)
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("hang", math.inf)}, hang_seconds=5.0
        )
    ):
        with pytest.raises(DeviceHangException):
            run_scan(table, scan_ops(table), shard_deadline=0.2)
    assert SCAN_STATS.mesh_stragglers >= 1
    (event,) = [
        e
        for e in SCAN_STATS.degradation_events
        if e["kind"] == "mesh_straggler"
    ]
    assert event["deadline"] == 0.2
    assert event["mesh_size"] == 8


def test_shard_deadline_feeds_fallback_policy(mesh8):
    """A transient straggler under on_device_error='fallback' completes
    (CPU rung: the hang is unattributable, no chip to shed)."""
    table = int_table(512, seed=10)
    clean = run_scan(table, scan_ops(table))
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("hang", 1)}, hang_seconds=5.0)
    ):
        result = run_scan(
            table, scan_ops(table),
            on_device_error="fallback", shard_deadline=0.2,
        )
    assert SCAN_STATS.mesh_stragglers == 1
    assert_results_equal(result, clean)


def test_tighter_device_deadline_is_not_labeled_straggler(mesh8):
    """A hang tripping a device_deadline TIGHTER than the shard deadline
    is a general watchdog timeout, not a straggling collective — the
    telemetry must attribute it to the deadline that actually bound."""
    table = int_table(256, seed=30)
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("hang", 1)}, hang_seconds=5.0)
    ):
        with pytest.raises(DeviceHangException):
            run_scan(
                table, scan_ops(table),
                device_deadline=0.2, shard_deadline=60.0,
            )
    assert SCAN_STATS.mesh_stragglers == 0
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "watchdog_timeout" in kinds and "mesh_straggler" not in kinds


def test_shard_deadline_armed_on_plain_streaming_path(mesh8):
    """The straggler deadline covers RAW streaming scans too (no
    checkpoint/quarantine): a stalled mesh collective becomes a typed
    DeviceHangException failure metric, never a frozen run."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(800, seed=31)
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("hang", math.inf)}, hang_seconds=5.0
        )
    ):
        ctx = AnalysisRunner.do_analysis_run(
            stream_table(table, 200), basic_analyzers(),
            shard_deadline=0.2,
        )
    failures = [m for m in ctx.all_metrics() if m.value.is_failure]
    assert failures
    for m in failures:
        assert isinstance(m.value.exception, DeviceHangException)


def test_shard_deadline_ignored_on_single_device():
    """The straggler watchdog is a MESH feature: single-device scans pay
    zero watchdog machinery for it."""
    table = int_table(256, seed=11)
    with use_mesh(None):
        with scan_faults(
            FaultInjectingScanHook(
                faults={0: ("hang", 1)}, hang_seconds=0.05
            )
        ):
            run_scan(table, scan_ops(table), shard_deadline=0.2)
    assert SCAN_STATS.mesh_stragglers == 0


# -- streaming + kill-and-resume through a reshard ---------------------------


def test_streaming_chip_loss_resilient_loop_reshards(mesh8):
    """A chip lost at batch 2 of a resilient streaming run reshards that
    batch's scan; every later batch runs on the pre-shrunken mesh; the
    metrics match a fault-free run bit-for-bit."""
    n, batch_rows = 2000, 250
    table = int_table(n, seed=12)
    check = checks_for(n)
    lost_id = mesh_device_ids(mesh8)[4]

    ref = (
        VerificationSuite.on_data(stream_table(table, batch_rows))
        .add_check(check)
        .on_batch_error("skip")
        .run()
    )
    assert ref.status == CheckStatus.SUCCESS

    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={2: ("lost", FaultSchedule.PERMANENT, lost_id)}
        )
    ):
        result = (
            VerificationSuite.on_data(stream_table(table, batch_rows))
            .add_check(check)
            .on_batch_error("skip")
            .run()
        )
    assert result.status == CheckStatus.SUCCESS
    assert result.resharded
    assert result.fallback_backend is None
    assert result.skipped_batches == []
    assert SCAN_STATS.mesh_reshards == 1
    assert metric_values(result) == metric_values(ref)


class _KillSwitch(BaseException):
    """Out-of-band abort, like SIGKILL from the runner's point of view."""


class _KillingSource:
    def __init__(self, inner, kill_at):
        self.inner = inner
        self.kill_at = kill_at

    @property
    def schema(self):
        return self.inner.schema

    @property
    def num_rows(self):
        return self.inner.num_rows

    @property
    def _batch_rows(self):
        return getattr(self.inner, "_batch_rows", None)

    def batches(self, columns=None, batch_rows=None):
        yield from self.batches_from(0, columns=columns, batch_rows=batch_rows)

    def batches_from(self, start=0, columns=None, batch_rows=None):
        idx = start
        for batch in self.inner.batches_from(
            start, columns=columns, batch_rows=batch_rows
        ):
            if idx >= self.kill_at:
                raise _KillSwitch(f"killed at batch {idx}")
            yield batch
            idx += 1


def test_kill_and_resume_through_reshard_bit_identical(tmp_path, mesh8):
    """Satellite acceptance: a chip dies at batch 2 (reshard), the run is
    killed at batch 6, the resumed run meets the SAME dead chip
    (pre-shrunken mesh via quarantine) and finishes — metrics
    bit-identical to a clean checkpointed run."""
    n, batch_rows = 2000, 200  # 10 batches
    table = int_table(n, seed=13)
    check = checks_for(n)
    lost_id = mesh_device_ids(mesh8)[1]

    def fresh_source():
        return stream_table(table, batch_rows=batch_rows).source

    ref = (
        VerificationSuite.on_data(StreamingTable(fresh_source()))
        .add_check(check)
        .with_checkpoint(str(tmp_path / "ref"), every_batches=4)
        .run()
    )
    assert ref.status == CheckStatus.SUCCESS

    ckpt = str(tmp_path / "run")
    # run 1: chip lost at batch 2, killed at batch 6 (after a checkpoint)
    killed = StreamingTable(_KillingSource(fresh_source(), kill_at=6))
    hook = FaultInjectingScanHook(
        faults={2: ("lost", FaultSchedule.PERMANENT, lost_id)}
    )
    with scan_faults(hook):
        with pytest.raises(_KillSwitch):
            (
                VerificationSuite.on_data(killed)
                .add_check(check)
                .with_checkpoint(ckpt, every_batches=4)
                .run()
            )
    assert ("lost", 2, 0, lost_id) in hook.injected
    assert lost_id in MESH_HEALTH.quarantined()

    # run 2: resumes past batch 4 on the quarantine-shrunken mesh (the
    # dead chip is STILL dead — any dispatch to it would fault again)
    SCAN_STATS.reset()
    resume_hook = FaultInjectingScanHook(
        faults={
            i: ("lost", FaultSchedule.PERMANENT, lost_id) for i in range(16)
        }
    )
    with scan_faults(resume_hook):
        resumed = (
            VerificationSuite.on_data(StreamingTable(fresh_source()))
            .add_check(check)
            .with_checkpoint(ckpt, every_batches=4)
            .run()
        )
    assert resumed.status == CheckStatus.SUCCESS
    assert resumed.fallback_backend is None
    assert metric_values(resumed) == metric_values(ref)


# -- stale residency (satellite) ---------------------------------------------


def test_reshard_evicts_residency_pinned_to_old_mesh(mesh8):
    """Residency is sharded onto the full mesh; after a chip loss the
    reshard must evict it (it cannot serve the shrunken mesh) and the
    HBM budget must drop to zero — no stale shards keep charging it."""
    table = int_table(2048, seed=14)
    persist_table(table, mesh=mesh8)
    assert table._device_cache is not None
    assert total_resident_bytes() > 0
    # the budget's reading is per device: an eighth of the total
    assert resident_bytes_per_device() == total_resident_bytes() // 8
    lost_id = mesh_device_ids(mesh8)[0]
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
        )
    ):
        run_scan(table, scan_ops(table))
    assert SCAN_STATS.mesh_reshards == 1
    assert table._device_cache is None
    assert total_resident_bytes() == 0 and resident_bytes_per_device() == 0
    (event,) = [
        e for e in SCAN_STATS.degradation_events if e["kind"] == "mesh_reshard"
    ]
    assert event["evicted_bytes"] > 0


def test_mesh_change_evicts_stale_residency(mesh8):
    """Satellite: a scan under a DIFFERENT mesh than the table was
    persisted with evicts the stale per-device shards (and uncharges the
    budget) instead of leaving them resident forever."""
    table = int_table(1024, seed=15)
    persist_table(table, mesh=mesh8)
    assert total_resident_bytes() > 0
    clean = run_scan(table, scan_ops(table))

    table2 = int_table(1024, seed=15)
    persist_table(table2, mesh=mesh8)
    SCAN_STATS.reset()
    smaller = mesh_excluding(mesh8, {mesh_device_ids(mesh8)[7]})
    with use_mesh(smaller):
        got = run_scan(table2, scan_ops(table2))
    assert table2._device_cache is None
    assert any(
        e["kind"] == "stale_residency_evicted"
        for e in SCAN_STATS.degradation_events
    )
    assert_results_equal(got, clean)


def test_evicted_cache_stops_charging_budget():
    """Satellite regression: _evict_device_cache must zero the cache's
    accounting — a held reference to the evicted cache object must not
    keep counting against MAX_RESIDENT_BYTES."""
    table = int_table(1024, seed=16)
    cache = persist_table(table)
    assert total_resident_bytes() > 0
    from deequ_tpu.ops.scan_engine import _evict_device_cache

    freed = _evict_device_cache(table)
    assert freed > 0
    # `cache` is still referenced HERE, yet charges nothing
    assert cache.nbytes == 0 and cache.per_device_bytes == 0
    assert total_resident_bytes() == 0 and resident_bytes_per_device() == 0


# -- multi-host peer loss ----------------------------------------------------


def test_split_row_range_balanced():
    """Satellite: the balanced split never differs by more than one row
    across parts and covers everything exactly once — including the
    7-rows/8-processes shape where the old ceil split let early hosts
    carry the remainder."""
    from deequ_tpu.parallel.distributed import split_row_range

    for total, n in [(7, 8), (10, 8), (10, 3), (8, 8), (0, 4), (3, 8),
                     (100, 1), (1, 1), (1000003, 7)]:
        sizes = []
        covered = 0
        for part in range(n):
            start, stop = split_row_range(total, n, part)
            assert 0 <= start <= stop <= total
            assert start == covered, (total, n, part)
            covered = stop
            sizes.append(stop - start)
        assert covered == total
        assert max(sizes) - min(sizes) <= 1, (total, n, sizes)

    with pytest.raises(ValueError):
        split_row_range(10, 0, 0)
    with pytest.raises(ValueError):
        split_row_range(10, 4, 4)


def test_host_row_range_balanced(monkeypatch):
    import jax

    from deequ_tpu.parallel.distributed import host_row_range

    monkeypatch.setattr(jax, "process_count", lambda: 8)
    sizes = []
    for pid in range(8):
        monkeypatch.setattr(jax, "process_index", lambda p=pid: p)
        start, stop = host_row_range(10)
        sizes.append(stop - start)
    assert sizes == [2, 2, 1, 1, 1, 1, 1, 1]


def test_check_peers_single_host_is_trivially_healthy():
    from deequ_tpu.parallel.distributed import check_peers

    report = check_peers(1000)
    assert not report.degraded
    assert report.lost == []


def test_check_peers_fail_raises_typed(monkeypatch):
    import jax

    from deequ_tpu.parallel.distributed import check_peers

    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 0)

    def probe(timeout):
        return [0, 1, 3]  # peer 2 never heartbeats

    with pytest.raises(PeerLostException) as exc:
        check_peers(1000, timeout=0.1, probe=probe)
    assert exc.value.lost_processes == (2,)


def test_check_peers_degrade_reports_unverified_ranges(monkeypatch):
    """on_peer_loss='degrade': the surviving hosts complete and the lost
    hosts' balanced row ranges are reported unverified — on the report,
    on ScanStats, and (via the delta) on VerificationResult."""
    import jax

    from deequ_tpu.parallel.distributed import check_peers, split_row_range

    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 0)

    SCAN_STATS.reset()
    report = check_peers(
        1003, timeout=0.1, on_peer_loss="degrade",
        probe=lambda t: [0, 1, 3],
    )
    assert report.degraded
    assert report.lost == [2]
    assert report.surviving == [0, 1, 3]
    want = split_row_range(1003, 4, 2)
    assert report.unverified_row_ranges == [want]
    assert SCAN_STATS.peer_losses == 1
    assert SCAN_STATS.unverified_row_ranges == [want]
    (event,) = [
        e for e in SCAN_STATS.degradation_events if e["kind"] == "peer_lost"
    ]
    assert (event["start"], event["stop"]) == want


def test_check_peers_unattributable_timeout_raises_even_degrade(monkeypatch):
    import jax

    from deequ_tpu.parallel.distributed import check_peers

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)

    def probe(timeout):
        raise TimeoutError("barrier stalled, everyone heartbeated")

    with pytest.raises(PeerLostException):
        check_peers(100, timeout=0.1, on_peer_loss="degrade", probe=probe)


def test_check_peers_validates_policy():
    from deequ_tpu.parallel.distributed import check_peers

    with pytest.raises(ValueError):
        check_peers(100, on_peer_loss="retry")


def test_unverified_ranges_surface_on_verification_result(monkeypatch):
    """Partial-result semantics end to end through the REAL wiring: the
    builder's .on_peer_loss("degrade") runs the peer check inside the
    run, so a lost host's row range lands on
    VerificationResult.unverified_row_ranges and mesh_events — and a
    fresh run after the degradation starts clean."""
    import jax

    from deequ_tpu.parallel import distributed
    from deequ_tpu.parallel.distributed import split_row_range

    n = 800
    table = int_table(n, seed=17)
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(
        distributed, "_default_peer_probe", lambda timeout: [0, 2, 3]
    )

    result = (
        VerificationSuite.on_data(table)
        .add_check(checks_for(n))
        .on_peer_loss("degrade", timeout=0.1)
        .run()
    )
    assert result.status == CheckStatus.SUCCESS
    assert result.unverified_row_ranges == [split_row_range(n, 4, 1)]
    (event,) = [e for e in result.mesh_events if e["kind"] == "peer_lost"]
    assert (event["start"], event["stop"]) == split_row_range(n, 4, 1)

    # "fail" raises typed through the same wiring
    with pytest.raises(PeerLostException):
        (
            VerificationSuite.on_data(table)
            .add_check(checks_for(n))
            .on_peer_loss("fail", timeout=0.1)
            .run()
        )
    with pytest.raises(ValueError):
        VerificationSuite.on_data(table).on_peer_loss("retry")

    # a fresh run WITHOUT the peer check does not inherit the degradation
    clean = VerificationSuite.on_data(table).add_check(checks_for(n)).run()
    assert clean.unverified_row_ranges == []
    assert clean.mesh_events == []


class _CountlessSource:
    """BatchSource wrapper that forgets its row count (num_rows = None,
    the generator-backed-source shape; StreamingTable.num_rows then
    RAISES TypeError)."""

    def __init__(self, inner):
        self.inner = inner

    @property
    def schema(self):
        return self.inner.schema

    @property
    def num_rows(self):
        return None

    @property
    def _batch_rows(self):
        return getattr(self.inner, "_batch_rows", None)

    def batches(self, columns=None, batch_rows=None):
        return self.inner.batches(columns=columns, batch_rows=batch_rows)

    def batches_from(self, start=0, columns=None, batch_rows=None):
        return self.inner.batches_from(
            start, columns=columns, batch_rows=batch_rows
        )


def test_on_peer_loss_survives_countless_stream(monkeypatch):
    """A streaming source that doesn't know its row count
    (StreamingTable.num_rows RAISES TypeError) still gets the peer
    check: no crash, the loss is reported as an event — the lost host's
    rows just can't be mapped to a [start, stop) range."""
    import jax

    from deequ_tpu.parallel import distributed

    n = 600
    table = int_table(n, seed=19)
    stream = StreamingTable(_CountlessSource(stream_table(table, 200).source))
    with pytest.raises(TypeError):
        stream.num_rows  # the shape under test
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(
        distributed, "_default_peer_probe", lambda timeout: [0, 2, 3]
    )
    result = (
        VerificationSuite.on_data(stream)
        .add_check(checks_for(n))
        .on_batch_error("skip")
        .on_peer_loss("degrade", timeout=0.1)
        .run()
    )
    assert result.status == CheckStatus.SUCCESS
    # the loss is reported even though no row range could be derived
    (event,) = [e for e in result.mesh_events if e["kind"] == "peer_lost"]
    assert event["lost_processes"] == [1]
    assert result.unverified_row_ranges == []
