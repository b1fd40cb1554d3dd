"""Device-fault tolerance: XLA error taxonomy, OOM chunk bisection, CPU
fallback, and the compute watchdog (exceptions.py + ops/device_policy.py
+ ops/scan_engine.py:run_scan).

The acceptance pair is the flagship: a seeded device-fault hook injecting
an OOM at batch k of a streaming run completes via chunk bisection with
metrics bit-identical to a fault-free run; a scripted PERSISTENT device
failure with ``on_device_error="fallback"`` completes on the CPU fallback
backend. Runs under JAX_PLATFORMS=cpu via the injection hook — the faults
are scripted, the recovery machinery is real.
"""

import math
import warnings
from contextlib import contextmanager

import numpy as np
import pytest

from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data.fs import (
    InMemoryFileSystem,
    _REGISTRY,
    register_filesystem,
)
from deequ_tpu.data.streaming import StreamingTable, stream_table
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import (
    DeviceCompileException,
    DeviceException,
    DeviceHangException,
    DeviceLostException,
    DeviceOOMException,
    GroupBudgetIgnoredWarning,
    MetricCalculationRuntimeException,
    ReusingNotPossibleResultsMissingException,
    classify_device_error,
)
from deequ_tpu.ops.device_policy import DEVICE_HEALTH
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    install_scan_fault_hook,
    run_scan,
)
from deequ_tpu.resilience import (
    FaultInjectingFileSystem,
    FaultInjectingScanHook,
    FaultSchedule,
    FlakyBatchSource,
    InjectedDeviceError,
    RetryPolicy,
)
from deequ_tpu.verification import VerificationSuite

pytestmark = pytest.mark.devicefault

FAST = RetryPolicy(max_attempts=4, base_delay=0.0005, max_delay=0.002)


@pytest.fixture(autouse=True)
def _clean_device_state():
    """Each test starts with a healthy backend and no installed hook."""
    DEVICE_HEALTH.reset()
    prev = install_scan_fault_hook(None)
    yield
    install_scan_fault_hook(prev)
    DEVICE_HEALTH.reset()


@contextmanager
def scan_faults(hook: FaultInjectingScanHook):
    prev = install_scan_fault_hook(hook)
    try:
        yield hook
    finally:
        install_scan_fault_hook(prev)


def int_table(n=2000, seed=0):
    """Integer-VALUED fractional + integral columns: every partial-state
    sum is exact in f64, so 'bit-identical across chunkings' is a fair
    assertion (bisection changes the reduction association)."""
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


def checks_for(n):
    return (
        Check(CheckLevel.ERROR, "devicefault")
        .is_complete("x")
        .has_size(lambda s: s == n)
        .has_mean("x", lambda v: v > 0)
        .has_min("x", lambda v: v >= 0)
        .has_uniqueness(["g"], lambda v: v >= 0.0)
    )


def metric_values(result):
    return {
        repr(a): m.value.get()
        for a, m in result.metrics.items()
        if m.value.is_success
    }


def basic_analyzers():
    from deequ_tpu.analyzers import (
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
    )

    return [Size(), Completeness("x"), Mean("x"), Minimum("x"), Maximum("x")]


# -- taxonomy ----------------------------------------------------------------


@pytest.mark.parametrize(
    "message,expected",
    [
        (
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "17179869184 bytes.",
            DeviceOOMException,
        ),
        ("Allocation of 8589934592 bytes exceeds HBM", DeviceOOMException),
        (
            "INVALID_ARGUMENT: Compilation failure: fusion root mismatch",
            DeviceCompileException,
        ),
        ("Mosaic failed to compile kernel", DeviceCompileException),
        ("UNAVAILABLE: device is lost; halting execution", DeviceLostException),
        (
            "INTERNAL: Unable to initialize backend 'tpu'",
            DeviceLostException,
        ),
        ("DATA_LOSS: device state corrupted", DeviceLostException),
    ],
)
def test_classify_runtime_messages(message, expected):
    """XLA status strings map onto the typed taxonomy."""
    typed = classify_device_error(RuntimeError(message), "execute")
    assert isinstance(typed, expected)
    assert typed.boundary == "execute"
    assert isinstance(typed, MetricCalculationRuntimeException)
    assert isinstance(typed.__cause__, RuntimeError)


def test_classify_preserves_boundary_and_trace_default():
    # positional trace-default applies only to STRONG device-shaped types
    # (jaxlib's XlaRuntimeError and friends), never to plain RuntimeErrors
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    typed = classify_device_error(
        XlaRuntimeError("something inscrutable"), "trace"
    )
    assert isinstance(typed, DeviceCompileException)
    assert typed.boundary == "trace"
    # a plain application RuntimeError with no status pattern stays
    # unclassified at the trace boundary — it is a bug, not weather
    assert classify_device_error(RuntimeError("app bug in update fn"), "trace") is None


def test_classify_memoryerror_is_oom():
    """A host MemoryError during chunk pack classifies as OOM: smaller
    chunks are exactly the right response there too."""
    typed = classify_device_error(MemoryError("cannot allocate"), "transfer")
    assert isinstance(typed, DeviceOOMException)
    assert typed.boundary == "transfer"


def test_classify_ignores_logic_errors():
    assert classify_device_error(ValueError("bug, not weather")) is None
    assert classify_device_error(KeyError("missing")) is None
    # an unrecognizable RuntimeError at the execute boundary is NOT
    # guessed at — it propagates untyped rather than mis-degrade
    assert classify_device_error(RuntimeError("some app bug")) is None


def test_classify_passes_through_already_typed():
    exc = DeviceOOMException("already typed", boundary="execute")
    assert classify_device_error(exc) is exc


def test_reusing_exception_lives_in_the_taxonomy():
    """Satellite: ReusingNotPossibleResultsMissingException moved into
    deequ_tpu/exceptions.py (runner re-exports for compat) and joined the
    MetricCalculationException hierarchy without dropping RuntimeError."""
    from deequ_tpu.analyzers import runner

    assert (
        runner.ReusingNotPossibleResultsMissingException
        is ReusingNotPossibleResultsMissingException
    )
    assert issubclass(
        ReusingNotPossibleResultsMissingException,
        MetricCalculationRuntimeException,
    )
    assert issubclass(ReusingNotPossibleResultsMissingException, RuntimeError)


# -- OOM chunk bisection -----------------------------------------------------


def test_oom_bisection_in_memory_bit_identical():
    """A transient device OOM on an in-memory fused scan halves the chunk
    and retries; metrics match the clean run exactly and the degradation
    is recorded."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(2000)
    analyzers = basic_analyzers()
    clean = AnalysisRunner.do_analysis_run(table, analyzers)
    clean_vals = {
        repr(a): m.value.get() for a, m in clean.metric_map.items()
    }

    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook(faults={0: ("oom", 1)})) as hook:
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
    vals = {repr(a): m.value.get() for a, m in ctx.metric_map.items()}
    assert vals == clean_vals
    assert hook.injected == [("oom", 0, 0)]
    assert SCAN_STATS.oom_bisections == 1
    assert SCAN_STATS.bisection_depth == 1
    (event,) = [
        e for e in SCAN_STATS.degradation_events if e["kind"] == "oom_bisect"
    ]
    assert event["chunk_to"] < event["chunk_from"]


def test_oom_bisection_goes_deeper_on_repeat():
    """Two consecutive OOMs bisect twice (chunk/4) before succeeding."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(2000)
    analyzers = basic_analyzers()
    clean_vals = {
        repr(a): m.value.get()
        for a, m in AnalysisRunner.do_analysis_run(
            table, analyzers
        ).metric_map.items()
    }
    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook(faults={0: ("oom", 2)})):
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
    assert {
        repr(a): m.value.get() for a, m in ctx.metric_map.items()
    } == clean_vals
    assert SCAN_STATS.oom_bisections == 2
    assert SCAN_STATS.bisection_depth == 2


def test_oom_evicts_device_residency():
    """The first response to OOM is freeing the persisted table's HBM
    residency — the biggest tenant — before retrying."""
    table = int_table(2000)
    table.persist()
    assert table._device_cache is not None
    with scan_faults(FaultInjectingScanHook(faults={0: ("oom", 1)})):
        result = run_scan(
            table,
            [a.scan_op(table) for a in basic_analyzers()],
        )
    assert len(result) == 5
    assert table._device_cache is None
    (event,) = [
        e for e in SCAN_STATS.degradation_events if e["kind"] == "oom_bisect"
    ]
    assert event["evicted_bytes"] > 0


def test_persistent_oom_without_fallback_raises_typed():
    """OOM at every chunk size bottoms out at the bisection floor and
    raises the TYPED exception (which the runner maps onto failure
    metrics per the shared-scan rule)."""
    table = int_table(500)
    ops = [a.scan_op(table) for a in basic_analyzers()]
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("oom", math.inf)})
    ):
        with pytest.raises(DeviceOOMException):
            run_scan(table, ops)
    assert SCAN_STATS.oom_bisections >= 1  # it tried before giving up


def test_persistent_oom_with_fallback_lands_on_cpu():
    table = int_table(500)
    clean = run_scan(table, [a.scan_op(table) for a in basic_analyzers()])
    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook(faults={0: ("oom", math.inf)})):
        result = run_scan(
            table,
            [a.scan_op(table) for a in basic_analyzers()],
            on_device_error="fallback",
        )
    for got, want in zip(result, clean):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want)
        )
    assert SCAN_STATS.fallback_scans == 1
    assert SCAN_STATS.fallback_backend == "cpu"
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "oom_bisect" in kinds and "cpu_fallback" in kinds


# -- acceptance: streaming run, OOM at batch k -------------------------------


def test_streaming_oom_at_batch_k_completes_via_bisection():
    """ACCEPTANCE: seeded hook injects an OOM at batch 3 of a streaming
    run; the run completes via chunk bisection, ScanStats records >= 1
    degradation event, and all metrics are bit-identical to a fault-free
    run."""
    n, batch_rows = 2000, 200
    table = int_table(n)
    check = checks_for(n)

    ref = (
        VerificationSuite.on_data(stream_table(table, batch_rows))
        .add_check(check)
        .on_batch_error("skip")  # same resilient loop as the faulted run
        .run()
    )
    assert ref.status == CheckStatus.SUCCESS

    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook(faults={3: ("oom", 1)})) as hook:
        result = (
            VerificationSuite.on_data(stream_table(table, batch_rows))
            .add_check(check)
            .on_batch_error("skip")
            .run()
        )
    assert result.status == CheckStatus.SUCCESS
    assert hook.injected == [("oom", 3, 0)]
    assert len(result.skipped_batches) == 0  # degraded, nothing dropped
    assert SCAN_STATS.oom_bisections >= 1
    assert len(SCAN_STATS.degradation_events) >= 1
    assert [e["kind"] for e in result.device_events] == ["oom_bisect"]
    assert metric_values(result) == metric_values(ref)


def test_streaming_persistent_failure_fallback_cpu():
    """ACCEPTANCE: with on_device_error="fallback" and a scripted
    PERSISTENT device failure, the same suite passes on the CPU fallback
    backend."""
    n, batch_rows = 2000, 200
    table = int_table(n)
    check = checks_for(n)

    ref = (
        VerificationSuite.on_data(stream_table(table, batch_rows))
        .add_check(check)
        .on_batch_error("skip")
        .run()
    )

    SCAN_STATS.reset()
    dead = {
        i: ("lost", FaultSchedule.PERMANENT) for i in range(n // batch_rows)
    }
    with scan_faults(FaultInjectingScanHook(faults=dead)):
        result = (
            VerificationSuite.on_data(stream_table(table, batch_rows))
            .add_check(check)
            .on_device_error("fallback")
            .run()
        )
    assert result.status == CheckStatus.SUCCESS
    assert result.fallback_backend == "cpu"
    assert SCAN_STATS.fallback_scans >= 1
    assert any(e["kind"] == "cpu_fallback" for e in result.device_events)
    assert metric_values(result) == metric_values(ref)


def test_streaming_device_fault_fail_policy_is_typed_not_raw():
    """Without fallback, a dead accelerator fails the pass's analyzers
    with the TYPED exception — callers never see raw runtime strings."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(400)
    with scan_faults(
        FaultInjectingScanHook(
            faults={i: ("lost", FaultSchedule.PERMANENT) for i in range(4)}
        )
    ):
        ctx = AnalysisRunner.do_analysis_run(
            stream_table(table, 100), basic_analyzers(),
            on_batch_error="skip",
        )
    failures = [m for m in ctx.all_metrics() if m.value.is_failure]
    assert failures
    for m in failures:
        assert isinstance(m.value.exception, DeviceLostException)


def test_device_health_forces_fallback_after_repeated_faults():
    """A backend that faults repeatedly routes subsequent fallback scans
    straight to CPU (no re-fail first); an accelerator success resets."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(300)
    analyzers = basic_analyzers()
    dead = FaultInjectingScanHook(
        faults={i: ("lost", FaultSchedule.PERMANENT) for i in range(10)}
    )
    with scan_faults(dead):
        for _ in range(DEVICE_HEALTH.threshold):
            AnalysisRunner.do_analysis_run(
                table, analyzers, on_device_error="fallback"
            )
    assert DEVICE_HEALTH.should_force_fallback()
    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook()):  # records calls only
        AnalysisRunner.do_analysis_run(
            table, analyzers, on_device_error="fallback"
        )
    assert any(
        e["kind"] == "cpu_fallback" and e.get("reason") == "unhealthy_backend"
        for e in SCAN_STATS.degradation_events
    )
    # a clean accelerator pass forgives
    AnalysisRunner.do_analysis_run(table, analyzers)
    assert not DEVICE_HEALTH.should_force_fallback()


def test_fallback_evicts_accelerator_residency():
    """The fallback attempt must not dispatch on accelerator-committed
    resident chunks (jax.default_device cannot move committed arrays):
    residency is dropped before the CPU re-run."""
    table = int_table(1000)
    table.persist()
    clean = run_scan(table, [a.scan_op(table) for a in basic_analyzers()])
    table.persist()
    with scan_faults(FaultInjectingScanHook(faults={0: ("lost", math.inf)})):
        result = run_scan(
            table,
            [a.scan_op(table) for a in basic_analyzers()],
            on_device_error="fallback",
        )
    assert table._device_cache is None
    for got, want in zip(result, clean):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_device_health_half_open_probe():
    """Forced fallback is a circuit breaker, not a one-way door: every
    probe_interval-th decision retries the accelerator, and one success
    resets the health entirely."""
    for _ in range(DEVICE_HEALTH.threshold):
        DEVICE_HEALTH.record_fault(DeviceLostException("blip"))
    decisions = [
        DEVICE_HEALTH.should_force_fallback()
        for _ in range(DEVICE_HEALTH.probe_interval * 2)
    ]
    assert decisions.count(False) == 2  # two half-open probes
    DEVICE_HEALTH.record_success()
    assert not DEVICE_HEALTH.should_force_fallback()


# -- compute watchdog --------------------------------------------------------


def test_watchdog_converts_hang_to_typed_exception():
    table = int_table(400)
    ops = [a.scan_op(table) for a in basic_analyzers()]
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("hang", math.inf)}, hang_seconds=5.0)
    ):
        with pytest.raises(DeviceHangException) as exc:
            run_scan(table, ops, device_deadline=0.2)
    assert exc.value.deadline == 0.2
    assert SCAN_STATS.watchdog_timeouts == 1


def test_watchdog_hang_feeds_fallback_policy():
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(400)
    analyzers = basic_analyzers()
    clean_vals = {
        repr(a): m.value.get()
        for a, m in AnalysisRunner.do_analysis_run(
            table, analyzers
        ).metric_map.items()
    }
    SCAN_STATS.reset()
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("hang", 1)}, hang_seconds=5.0)
    ):
        ctx = AnalysisRunner.do_analysis_run(
            table, analyzers,
            on_device_error="fallback", device_deadline=0.2,
        )
    assert {
        repr(a): m.value.get() for a, m in ctx.metric_map.items()
    } == clean_vals
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "watchdog_timeout" in kinds and "cpu_fallback" in kinds


def test_no_deadline_means_no_watchdog_machinery():
    """Without a deadline the dispatch path is direct (no worker thread):
    a short injected hang just… takes that long, and nothing is recorded."""
    table = int_table(200)
    ops = [a.scan_op(table) for a in basic_analyzers()]
    with scan_faults(
        FaultInjectingScanHook(faults={0: ("hang", 1)}, hang_seconds=0.05)
    ):
        run_scan(table, ops)
    assert SCAN_STATS.watchdog_timeouts == 0


# -- hook determinism --------------------------------------------------------


def test_scan_hook_injection_is_deterministic():
    """Same script + same workload => identical injection logs (the
    reproducibility contract the storage FaultSchedule already keeps)."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = int_table(800)
    logs = []
    for _ in range(2):
        DEVICE_HEALTH.reset()
        hook = FaultInjectingScanHook(
            faults={1: ("oom", 1), 2: ("oom", 2)}
        )
        with scan_faults(hook):
            AnalysisRunner.do_analysis_run(
                stream_table(table, 200), basic_analyzers(),
                on_batch_error="skip",
            )
        logs.append(list(hook.injected))
    assert logs[0] == logs[1]
    assert logs[0] == [("oom", 1, 0), ("oom", 2, 0), ("oom", 2, 1)]


# -- combined fault domains: device + I/O + kill-and-resume ------------------


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


def test_combined_device_and_io_faults_kill_and_resume(tmp_path):
    """Satellite acceptance: device faults (OOM at batch 5 before the
    kill, OOM at batch 12 after the resume) and I/O faults (checkpoint
    directory on a FaultInjectingFileSystem with transient errors, plus a
    FlakyBatchSource read fault) fire in the SAME run; the killed run
    resumes from its checkpoint and the final metrics are bit-identical
    to a clean run."""
    n, batch_rows = 2000, 100  # 20 batches
    table = int_table(n)
    check = checks_for(n)

    def fresh_source():
        return stream_table(table, batch_rows=batch_rows).source

    # clean reference through the same checkpointed resilient path
    ref = (
        VerificationSuite.on_data(StreamingTable(fresh_source()))
        .add_check(check)
        .with_checkpoint(str(tmp_path / "ref"), every_batches=4)
        .run()
    )
    assert ref.status == CheckStatus.SUCCESS

    # checkpoint store with transient I/O weather (every op fails once,
    # then succeeds — the checkpointer's retry layer absorbs it)
    inner_fs = InMemoryFileSystem()
    fs_sched = FaultSchedule(error_rate=0.3, seed=11)
    register_filesystem(
        "fault-dev",
        lambda path: FaultInjectingFileSystem(inner_fs, fs_sched),
    )
    try:
        from deequ_tpu.resilience import StreamCheckpointer

        def make_ckpt():
            return StreamCheckpointer(
                "fault-dev://ckpts", every_batches=4,
                retry=RetryPolicy(max_attempts=6, base_delay=0.0005),
            )

        # run 1: device OOM at batch 5 (bisected), killed at batch 10
        killed = StreamingTable(_KillingSource(fresh_source(), kill_at=10))
        with scan_faults(FaultInjectingScanHook(faults={5: ("oom", 1)})) as h1:
            with pytest.raises(_KillSwitch):
                (
                    VerificationSuite.on_data(killed)
                    .add_check(check)
                    .with_checkpoint(make_ckpt())
                    .run()
                )
        assert ("oom", 5, 0) in h1.injected

        # run 2: resumes past batch 8; device OOM at batch 12 AND a
        # transient batch-read fault at batch 14 in the same run
        DEVICE_HEALTH.reset()
        io_sched = FaultSchedule(fail={("batch", 14): 1})
        resumed_table = StreamingTable(
            FlakyBatchSource(fresh_source(), io_sched)
        ).with_retry(FAST)
        SCAN_STATS.reset()
        with scan_faults(
            FaultInjectingScanHook(faults={12 - 8: ("oom", 1)})
        ) as h2:
            resumed = (
                VerificationSuite.on_data(resumed_table)
                .add_check(check)
                .with_checkpoint(make_ckpt())
                .run()
            )
        assert resumed.status == CheckStatus.SUCCESS
        # both fault domains actually fired post-resume
        assert h2.injected, "device fault did not fire on the resumed run"
        assert any(k[0] == "ioerror" for k in io_sched.injected)
        assert SCAN_STATS.oom_bisections >= 1
        # retries are visible now
        assert resumed.retry_stats["retries"] >= 1
        # and the metrics are exactly the clean run's
        assert metric_values(resumed) == metric_values(ref)
    finally:
        _REGISTRY.pop("fault-dev", None)


# -- satellite: retry telemetry ----------------------------------------------


def test_retry_stats_surfaced_on_result():
    """Retries used to be invisible; now the run reports its attempt
    counts, backoff sleep, and last exception."""
    n = 1000
    table = int_table(n)
    sched = FaultSchedule(fail={("batch", 2): 2, ("batch", 5): 1})
    flaky = StreamingTable(
        FlakyBatchSource(stream_table(table, 100).source, sched)
    ).with_retry(FAST)
    result = (
        VerificationSuite.on_data(flaky)
        .add_check(checks_for(n))
        .on_batch_error("skip")
        .run()
    )
    assert result.status == CheckStatus.SUCCESS
    stats = result.retry_stats
    assert stats["retries"] >= 3
    assert stats["backoff_seconds"] > 0
    assert "InjectedIOError" in stats["last_exception"]
    assert result.skipped_batches == []


def test_retry_stats_clean_run_is_zero():
    n = 400
    result = (
        VerificationSuite.on_data(stream_table(int_table(n), 100))
        .add_check(checks_for(n))
        .on_batch_error("skip")
        .run()
    )
    assert result.retry_stats["retries"] == 0
    assert result.retry_stats["exhausted"] == 0
    assert result.retry_stats["last_exception"] is None


# -- satellite: budget+checkpoint warns once per run -------------------------


def test_group_budget_with_checkpoint_warns_once_per_run(tmp_path):
    """group_memory_budget + checkpointing disables spill with exactly ONE
    GroupBudgetIgnoredWarning per run — not per batch, and run 2 warns
    again (no process-lifetime dedup)."""
    n, batch_rows = 1200, 100  # 12 batches: per-batch warning would show
    table = int_table(n)

    for run_idx in range(2):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            result = (
                VerificationSuite.on_data(stream_table(table, batch_rows))
                .add_check(checks_for(n))
                .with_group_memory_budget(1 << 20)
                .with_checkpoint(
                    str(tmp_path / f"ck{run_idx}"), every_batches=4
                )
                .run()
            )
        assert result.status == CheckStatus.SUCCESS
        budget_warnings = [
            w for w in caught
            if issubclass(w.category, GroupBudgetIgnoredWarning)
        ]
        assert len(budget_warnings) == 1, (
            f"run {run_idx}: expected exactly 1 warning, got "
            f"{len(budget_warnings)}"
        )
    # spill was disabled: the run's grouping folds never touched disk
    assert SCAN_STATS.spill_runs == 0


# -- telemetry surfaces ------------------------------------------------------


def test_execution_report_includes_device_counters():
    import deequ_tpu

    # round 11: execution_report() is the unified registry snapshot;
    # the device counters live in its "scan" section, and the old flat
    # shape survives as the deprecation-free scan_execution_report()
    report = deequ_tpu.execution_report()["scan"]
    legacy = deequ_tpu.scan_execution_report()
    for key in (
        "device_faults", "oom_bisections", "bisection_depth",
        "watchdog_timeouts", "fallback_scans", "fallback_backend",
        "degradation_events",
    ):
        assert key in report
        assert key in legacy
    # the snapshot's event list is a copy, not a live view
    report["degradation_events"].append({"kind": "bogus"})
    assert all(
        e.get("kind") != "bogus" for e in SCAN_STATS.degradation_events
    )


def test_injected_device_error_is_realistic():
    """The injected stand-in classifies exactly like a real XlaRuntimeError
    message — the harness exercises the production classifier."""
    typed = classify_device_error(
        InjectedDeviceError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "8589934592 bytes."
        ),
        "execute",
    )
    assert isinstance(typed, DeviceOOMException)


def test_on_device_error_validation():
    table = int_table(100)
    with pytest.raises(ValueError):
        VerificationSuite.on_data(table).on_device_error("retry")
    with pytest.raises(ValueError):
        run_scan(table, [], on_device_error="bogus")


def test_oom_mid_fold_restarts_device_accumulator_cleanly():
    """With the on-device partial fold, an OOM injected at a LATER chunk
    dispatch aborts an accumulator that already holds earlier chunks; the
    bisected retry must start a FRESH accumulator — no chunk folded
    twice, metrics identical to a fault-free run, still one fetch."""
    from deequ_tpu.ops.scan_engine import persist_table

    table = int_table(8192, seed=5)
    clean = run_scan(
        table, [a.scan_op(table) for a in basic_analyzers()],
        chunk_rows=1024,
    )

    SCAN_STATS.reset()
    # chunk 3 of attempt 0 OOMs (chunks 0-2 already merged into the
    # accumulator); the bisected retry rescans everything at chunk 512
    hook_obj = FaultInjectingScanHook(faults={0: ("oom", 1)})
    with scan_faults(
        lambda boundary, ctx: (
            hook_obj(boundary, ctx)
            if int(ctx.get("chunk_index", -1)) == 3
            else None
        )
    ):
        result = run_scan(
            table, [a.scan_op(table) for a in basic_analyzers()],
            chunk_rows=1024,
        )
    assert SCAN_STATS.oom_bisections == 1
    for got, want in zip(result, clean):
        for g, w in zip(
            np.asarray(list(got.values()) if isinstance(got, dict) else [got]),
            np.asarray(
                list(want.values()) if isinstance(want, dict) else [want]
            ),
        ):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the retry's fetch is the scan's only one (the aborted attempt's
    # accumulator was discarded, never drained). Read through the
    # SYNCHRONIZED snapshot: the historical flake here was a late-waking
    # watchdog-abandoned worker from an EARLIER suite bumping the
    # process-global counter mid-test — record_fetch now drops abandoned
    # calls' fetches and snapshot() reads the ledger under its lock
    assert SCAN_STATS.snapshot()["device_fetches"] == 1


def test_multi_chunk_resident_scan_survives_injected_oom():
    """An OOM at the first dispatch of a multi-chunk resident scan evicts
    the residency and bisects like any other scan — correct metrics,
    recorded degradation."""
    from deequ_tpu.ops.scan_engine import persist_table

    table = int_table(8192, seed=6)
    clean = run_scan(
        table, [a.scan_op(table) for a in basic_analyzers()],
        chunk_rows=1024,
    )
    persist_table(table, chunk_rows=1024)
    assert table._device_cache is not None

    SCAN_STATS.reset()
    with scan_faults(FaultInjectingScanHook(faults={0: ("oom", 1)})):
        result = run_scan(
            table, [a.scan_op(table) for a in basic_analyzers()],
        )
    assert table._device_cache is None  # residency evicted
    assert SCAN_STATS.oom_bisections == 1
    for got, want in zip(result, clean):
        gl = list(got.values()) if isinstance(got, dict) else [got]
        wl = list(want.values()) if isinstance(want, dict) else [want]
        for g, w in zip(gl, wl):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
