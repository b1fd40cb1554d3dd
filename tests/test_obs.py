"""Observability suite (deequ_tpu/obs): flight-recorder span semantics
across the fault ladder, ring-buffer bounding, disarmed-is-free,
Perfetto export validity, the unified metrics registry, and the serve
layer's latency histograms.

Tier-1 marker: ``obs``.
"""

import json

import numpy as np
import pytest

from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.obs import (
    SEAM_NAMES,
    FlightRecorder,
    current_recorder,
    install_global_recorder,
    recording_scope,
    to_chrome_trace,
)
from deequ_tpu.obs.registry import REGISTRY, Histogram, HistogramFamily
from deequ_tpu.ops.scan_engine import SCAN_STATS

pytestmark = pytest.mark.obs


def _table(n=4096, cols=2, seed=7):
    rng = np.random.default_rng(seed)
    return ColumnarTable(
        [
            Column(
                f"c{i}", DType.FRACTIONAL,
                values=rng.normal(100.0 + i, 5.0, n),
                mask=rng.random(n) > 0.05,
            )
            for i in range(cols)
        ]
    )


def _analyzers(cols=2):
    out = [Size()]
    for i in range(cols):
        out += [Completeness(f"c{i}"), Mean(f"c{i}"),
                Minimum(f"c{i}"), Maximum(f"c{i}")]
    return out


def _spans(rec, name=None):
    return [
        r for r in rec.records()
        if r.kind == "span" and (name is None or r.name == name)
    ]


def _events(rec, name=None):
    return [
        r for r in rec.records()
        if r.kind == "instant" and (name is None or r.name == name)
    ]


# -- recorder semantics ------------------------------------------------------


def test_span_nesting_and_parenting():
    rec = FlightRecorder()
    with rec.span("outer", a=1):
        with rec.span("inner"):
            rec.event("ping", x=2)
    records = {r.name: r for r in rec.records()}
    assert records["inner"].parent_id == records["outer"].span_id
    assert records["ping"].parent_id == records["inner"].span_id
    assert records["outer"].parent_id is None
    assert records["outer"].t_end >= records["inner"].t_end
    assert records["ping"].args == {"x": 2}


def test_ring_buffer_bounded_with_drop_count():
    rec = FlightRecorder(capacity=8)
    for i in range(30):
        rec.event("e", i=i)
    assert len(rec) == 8
    assert rec.dropped == 22
    # the ring keeps the NEWEST records
    assert [r.args["i"] for r in rec.records()] == list(range(22, 30))


def test_recording_scope_is_thread_local_and_restores():
    rec = FlightRecorder()
    assert current_recorder() is None
    with recording_scope(rec):
        assert current_recorder() is rec
        with recording_scope(None):  # suppression wins over outer scope
            assert current_recorder() is None
        assert current_recorder() is rec
    assert current_recorder() is None


def test_scan_spans_nest_under_attempt():
    rec = FlightRecorder()
    table = _table()
    with recording_scope(rec):
        ctx = AnalysisRunner.do_analysis_run(table, _analyzers())
    assert all(m.value.is_success for m in ctx.all_metrics())
    attempts = _spans(rec, "scan_attempt")
    assert len(attempts) == 1
    attempt = attempts[0]
    seam_spans = [
        r for r in _spans(rec)
        if r.name in ("pack", "stage", "build", "dispatch", "fetch")
    ]
    assert {"stage", "build", "fetch"} <= {r.name for r in seam_spans}, (
        "no device-boundary spans recorded"
    )
    # spans of one scan share its scan_id
    assert {r.args["scan_id"] for r in seam_spans} == {
        attempt.args["scan_id"]
    }
    # every seam span of this scan parents (transitively) to the attempt
    by_id = {r.span_id: r for r in rec.records()}
    for r in seam_spans:
        cur = r
        while cur.parent_id is not None and cur.parent_id in by_id:
            cur = by_id[cur.parent_id]
        assert cur.span_id == attempt.span_id, (r.name, r.args)


def test_oom_bisect_rung_event_lands_under_its_attempt_span():
    """An OOM-bisected scan: attempt 0 faults, the oom_bisect rung event
    records INSIDE attempt 0's span, and the retry opens attempt 1."""
    from deequ_tpu.ops.device_policy import install_scan_fault_hook
    from deequ_tpu.resilience import FaultInjectingScanHook
    from deequ_tpu.resilience.governance import fault_state_scope

    rec = FlightRecorder()
    table = _table(n=8192)
    with fault_state_scope():
        install_scan_fault_hook(
            FaultInjectingScanHook(faults={0: ("oom", 1)}, relative=True)
        )
        with recording_scope(rec):
            ctx = AnalysisRunner.do_analysis_run(table, _analyzers())
    assert all(m.value.is_success for m in ctx.all_metrics())
    attempts = sorted(
        _spans(rec, "scan_attempt"), key=lambda r: r.args["attempt"]
    )
    assert len(attempts) >= 2, "bisection retry did not open a new attempt"
    assert attempts[0].args["attempt"] == 0
    rungs = _events(rec, "oom_bisect")
    assert len(rungs) == 1
    # the rung fired inside the attempt it degraded
    assert rungs[0].parent_id == attempts[0].span_id
    assert rungs[0].args["chunk_to"] < rungs[0].args["chunk_from"]


def test_budget_charge_events_on_recording():
    from deequ_tpu.ops.device_policy import install_scan_fault_hook
    from deequ_tpu.resilience import FaultInjectingScanHook
    from deequ_tpu.resilience.governance import (
        RunPolicy,
        fault_state_scope,
        run_budget_scope,
    )

    rec = FlightRecorder()
    table = _table(n=8192)
    with fault_state_scope():
        install_scan_fault_hook(
            FaultInjectingScanHook(faults={0: ("oom", 1)}, relative=True)
        )
        budget = RunPolicy(max_total_attempts=16).arm()
        with recording_scope(rec), run_budget_scope(budget):
            AnalysisRunner.do_analysis_run(table, _analyzers())
    charges = _events(rec, "budget_charge")
    assert len(charges) == budget.attempts == 1
    assert charges[0].args["charge_kind"] == "oom_bisect"


def test_disarmed_run_records_nothing_and_writes_no_instruments():
    from deequ_tpu.obs import recorder as rec_mod

    assert current_recorder() is None
    serve_before = REGISTRY.snapshot()["serve"]
    ctx = AnalysisRunner.do_analysis_run(_table(), _analyzers())
    assert all(m.value.is_success for m in ctx.all_metrics())
    # structurally disarmed: the module armed-counter stays zero (every
    # seam's disarmed fast path is one read of it) and no global
    # recorder appeared as a side effect of the run
    assert rec_mod._armed == 0
    assert rec_mod.global_recorder() is None
    # an untraced scan must not touch the registry's owned instruments
    serve_after = REGISTRY.snapshot()["serve"]
    assert serve_after["submitted"] == serve_before["submitted"]
    assert serve_after["latency"]["count"] == serve_before["latency"]["count"]


def test_trace_false_suppresses_env_armed_global():
    rec = FlightRecorder()
    prev = install_global_recorder(rec)
    try:
        from deequ_tpu.verification import VerificationSuite

        VerificationSuite.do_verification_run(
            _table(), [], _analyzers(), trace=False
        )
        assert len(rec) == 0, "trace=False must suppress the global recorder"
        VerificationSuite.do_verification_run(_table(), [], _analyzers())
        assert len(rec) > 0, "ambient global recorder was not picked up"
    finally:
        install_global_recorder(prev)


def test_trace_true_does_not_leak_process_wide():
    """run(trace=True) without env arming uses a run-scoped anonymous
    recorder: it lands on result.trace_recorder, and NOTHING stays
    armed afterwards (the off-by-default contract)."""
    from deequ_tpu.obs.recorder import global_recorder
    from deequ_tpu.verification import VerificationSuite

    assert global_recorder() is None and current_recorder() is None
    result = VerificationSuite.do_verification_run(
        _table(), [], _analyzers(), trace=True
    )
    assert result.trace_recorder is not None
    assert result.run_trace["spans"] > 0
    assert global_recorder() is None, "trace=True leaked a global recorder"
    assert current_recorder() is None
    # a later untraced run records nothing into the earlier recorder
    n = len(result.trace_recorder)
    VerificationSuite.do_verification_run(_table(), [], _analyzers())
    assert len(result.trace_recorder) == n


def test_env_var_arms_global_recorder(monkeypatch):
    from deequ_tpu.obs.recorder import global_recorder, maybe_arm_from_env

    prev = install_global_recorder(None)
    try:
        monkeypatch.setenv("DEEQU_TPU_TRACE", "1")
        monkeypatch.setenv("DEEQU_TPU_TRACE_CAPACITY", "128")
        rec = maybe_arm_from_env()
        assert rec is not None and global_recorder() is rec
        assert rec.capacity == 128
        ctx = AnalysisRunner.do_analysis_run(_table(), _analyzers())
        assert all(m.value.is_success for m in ctx.all_metrics())
        assert len(rec) > 0
    finally:
        install_global_recorder(prev)


def test_env_var_trace_garbage_raises_typed(monkeypatch):
    from deequ_tpu.envcfg import env_value
    from deequ_tpu.exceptions import EnvConfigError

    monkeypatch.setenv("DEEQU_TPU_TRACE", "yes")
    with pytest.raises(EnvConfigError):
        env_value("DEEQU_TPU_TRACE")
    monkeypatch.setenv("DEEQU_TPU_TRACE_CAPACITY", "-5")
    with pytest.raises(EnvConfigError):
        env_value("DEEQU_TPU_TRACE_CAPACITY")


# -- the seam primitive ------------------------------------------------------


def _seam_counters():
    from deequ_tpu.obs import seam_fields

    fields = [f for name in SEAM_NAMES for f in seam_fields(name)]
    fields += ["dispatch_seconds", "drain_wait_seconds", "scan_seconds",
               "run_seconds", "unfed_seconds"]
    return {f: getattr(SCAN_STATS, f) for f in fields}


def _seam_deltas(before):
    return {k: v - before[k] for k, v in _seam_counters().items()}


@pytest.mark.parametrize("name", SEAM_NAMES)
def test_seam_fields_are_numbers_straight_after_reset(name):
    """The benchmark takes numbers only from ``snapshot()`` and leaves a
    metric out when a name is missing: every seam's two fields must be
    there, as numbers, before the seam ever opened."""
    from deequ_tpu.obs import seam_fields
    from deequ_tpu.ops.scan_engine import ScanStats

    stats = ScanStats()
    stats.reset()
    snap = stats.snapshot()
    seconds, count = seam_fields(name)
    assert "." not in seconds and seconds.startswith("seam_")
    assert type(snap[seconds]) is float and snap[seconds] == 0.0
    assert type(snap[count]) is int and snap[count] == 0


def _exclusive_seconds(d):
    """Every seam's exclusive seconds, each second once: ``fetch.copy``
    feeds ``seam_fetch_seconds`` too and is a part of it."""
    return sum(
        v for k, v in d.items()
        if k.startswith("seam_") and k.endswith("_seconds")
    ) - d["seam_fetch_copy_seconds"]


def test_seam_exclusive_accounting_sums_to_the_root():
    """While a child seam is open the parent's clock stands still: the
    exclusive seconds of nested seams on one thread, the two enclosing
    ones included, sum to the root seam's wall exactly, and an enclosing
    seam bills its whole wall beside its own time."""
    import time

    from deequ_tpu.obs import seam

    before = _seam_counters()
    t0 = time.perf_counter()
    with seam("run", run_id=-1):
        time.sleep(0.002)
        with seam("scan_attempt", scan_id=-1):
            with seam("plan"):
                time.sleep(0.010)
                with seam("pack", chunk=0):
                    time.sleep(0.010)
                    with seam("stage"):
                        time.sleep(0.005)
            time.sleep(0.002)
            with seam("fetch"):
                with seam("fetch.copy", bytes=8):
                    time.sleep(0.002)
            with seam("evaluate"):
                time.sleep(0.005)
    wall = time.perf_counter() - t0
    d = _seam_deltas(before)
    # the seams share their timestamps: the sum IS the root's wall (a
    # second counted twice, or the root's own time dropped, would show
    # whatever the machine's load)
    assert _exclusive_seconds(d) == pytest.approx(d["run_seconds"], abs=1e-9)
    assert d["seam_run_seconds"] >= 0.002 and d["seam_run_count"] == 1
    assert d["seam_scan_attempt_seconds"] >= 0.002
    assert d["seam_scan_attempt_count"] == 1
    assert d["seam_fetch_copy_seconds"] >= 0.002
    assert d["seam_fetch_seconds"] >= d["seam_fetch_copy_seconds"]
    assert d["seam_fetch_count"] == d["seam_fetch_copy_count"] == 1
    root = d["scan_seconds"]
    assert 0.034 <= root <= d["run_seconds"] - 0.002 <= wall
    # each seam kept its OWN time only: at least its sleep, and no more
    # than the root's wall less the sleeps that ran under the other seams
    # (no ceiling on the sleeps themselves: a loaded machine oversleeps)
    assert 0.010 <= d["seam_plan_seconds"] <= root - 0.020 + 1e-9
    assert 0.010 <= d["seam_pack_seconds"] <= root - 0.020 + 1e-9
    assert 0.005 <= d["seam_stage_seconds"] <= root - 0.025 + 1e-9
    assert 0.005 <= d["seam_evaluate_seconds"] <= root - 0.025 + 1e-9
    assert d["seam_plan_count"] == d["seam_pack_count"] == 1
    # the older fields are the sums they are defined as
    assert d["dispatch_seconds"] == pytest.approx(d["seam_stage_seconds"])
    assert d["drain_wait_seconds"] == pytest.approx(d["seam_fetch_seconds"])
    # nothing was dispatched: every second of it was unfed
    assert d["unfed_seconds"] == pytest.approx(d["run_seconds"], abs=1e-9)


def test_unknown_seam_name_is_an_error():
    from deequ_tpu.obs import seam

    with pytest.raises(KeyError):
        with seam("no_such_seam"):
            pass


def test_worker_thread_seam_is_a_span_only():
    """Seams on an engine worker thread add to no counter (the caller's
    wait for the worker is the counted seam) but still record, with the
    ids the caller handed over."""
    import threading

    from deequ_tpu.obs import seam
    from deequ_tpu.obs.recorder import worker_seams

    rec = FlightRecorder()
    before = _seam_counters()

    def work():
        with recording_scope(rec), worker_seams({"run_id": 41}):
            with seam("pack", chunk=3):
                pass

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert all(v == 0 for v in _seam_deltas(before).values())
    (span,) = _spans(rec, "pack")
    assert span.args == {"run_id": 41, "chunk": 3}


def test_watchdog_call_counts_once_on_the_caller():
    """device_call under a deadline runs its body on a watchdog worker:
    the caller's seam counts the wait, a seam inside the body does not
    count again."""
    from deequ_tpu.obs import seam
    from deequ_tpu.ops.device_policy import device_call

    def body():
        with seam("fetch", bytes=8):
            return 7

    before = _seam_counters()
    assert device_call(body, "fetch", what="probe", deadline=30.0) == 7
    d = _seam_deltas(before)
    assert d["seam_fetch_count"] == 1
    assert d["drain_wait_seconds"] == pytest.approx(d["seam_fetch_seconds"])


# -- the feed gauge, fetch.copy, the accumulator's put ------------------------


@pytest.fixture
def gauge_at_zero():
    """The gauge is the thread's: a test starts and must end with every
    dispatch known ready."""
    from deequ_tpu.obs.recorder import device_ready, feed_gauge

    device_ready()
    yield
    assert feed_gauge() == 0
    device_ready()


def test_fed_and_unfed_stretches_add_up_to_the_seams_own_seconds(
        gauge_at_zero):
    """A seam that straddles a dispatch counts only the stretch before
    it as unfed; one that straddles the proof of readiness only the
    stretch behind it; a child's time is the child's."""
    import time

    from deequ_tpu.obs import seam
    from deequ_tpu.obs.recorder import device_fed, device_ready, feed_gauge

    rec = FlightRecorder()
    before = _seam_counters()
    with recording_scope(rec):
        with seam("dispatch"):
            time.sleep(0.004)          # unfed: nothing enqueued yet
            device_fed()
            assert feed_gauge() == 1
            time.sleep(0.006)          # fed
        with seam("evaluate"):
            time.sleep(0.003)          # fed: the host overlaps the device
            with seam("sketch_fold"):
                time.sleep(0.003)      # the child's, fed
        with seam("fetch"):
            time.sleep(0.004)          # fed: the wait
            device_ready()
            assert feed_gauge() == 0
            with seam("fetch.copy", bytes=8):
                time.sleep(0.005)      # unfed: the device stands
    d = _seam_deltas(before)
    (dispatch,) = _spans(rec, "dispatch")
    (fetch,) = _spans(rec, "fetch")
    (copy,) = _spans(rec, "fetch.copy")
    assert 0.004 <= dispatch.args["unfed_s"] <= d["seam_dispatch_seconds"] - 0.006
    assert copy.args["unfed_s"] == pytest.approx(
        d["seam_fetch_copy_seconds"], abs=1e-9)
    assert copy.args["unfed_s"] >= 0.005
    # the wait itself was fed: what fetch holds unfed is its few
    # microseconds around the copy
    assert fetch.args.get("unfed_s", 0.0) < 0.004
    for name in ("evaluate", "sketch_fold"):
        (span,) = _spans(rec, name)
        assert "unfed_s" not in span.args
    unfed = sum(r.args.get("unfed_s", 0.0) for r in _spans(rec))
    assert d["unfed_seconds"] == pytest.approx(unfed, abs=1e-9)
    fed = _exclusive_seconds(d) - d["unfed_seconds"]
    assert fed >= 0.006 + 0.003 + 0.003 + 0.004
    phases = rec.summary()["phases"]
    assert phases["fetch.copy"]["unfed_seconds"] == pytest.approx(
        copy.args["unfed_s"], abs=1e-6)
    assert "unfed_seconds" not in phases["evaluate"]


def test_a_throttle_on_an_older_result_leaves_the_gauge_up(gauge_at_zero):
    """``device_call`` moves the gauge: a dispatch feeds, a put and a
    wait on an older result move nothing, the fetch that names the
    newest dispatch zeroes it."""
    import jax.numpy as jnp

    from deequ_tpu.obs.recorder import feed_gauge
    from deequ_tpu.ops.device_policy import device_call, device_fetch
    from deequ_tpu.ops.scan_engine import _block_throttle

    put = device_call(lambda: jnp.arange(4.0), "transfer", what="a put")
    assert feed_gauge() == 0
    first = device_call(lambda: put + 1.0, "execute", what="chunk 0")
    second = device_call(lambda: first * 2.0, "execute", what="chunk 1")
    assert feed_gauge() == 2
    _block_throttle(first, "throttle", None)
    assert feed_gauge() == 2
    older = device_fetch(first, "an older result")
    assert feed_gauge() == 2 and older.tolist() == [1.0, 2.0, 3.0, 4.0]
    newest = device_fetch(second, "the last result", newest=True)
    assert feed_gauge() == 0 and newest.tolist() == [2.0, 4.0, 6.0, 8.0]


def test_fetch_copy_is_a_part_of_fetch_and_counts_no_second_fetch(
        gauge_at_zero):
    import jax.numpy as jnp

    from deequ_tpu.ops.device_policy import device_call, device_fetch

    rec = FlightRecorder()
    before = _seam_counters()
    with recording_scope(rec):
        out = device_call(lambda: jnp.ones(1024) * 3.0, "execute", what="op")
        host = device_fetch(out, "probe", newest=True)
    d = _seam_deltas(before)
    assert host.shape == (1024,) and isinstance(host, np.ndarray)
    assert d["seam_fetch_count"] == 1 and d["seam_fetch_copy_count"] == 1
    assert 0 < d["seam_fetch_copy_seconds"] < d["seam_fetch_seconds"]
    assert d["drain_wait_seconds"] == pytest.approx(
        d["seam_drain_seconds"] + d["seam_fetch_seconds"], abs=1e-12)
    (copy,) = _spans(rec, "fetch.copy")
    (fetch,) = _spans(rec, "fetch")
    assert copy.parent_id == fetch.span_id
    assert copy.args["bytes"] == host.nbytes


def test_under_a_watchdog_the_fetch_is_not_split_and_the_gauge_drops(
        gauge_at_zero):
    """An armed deadline runs the body on a pooled thread, whose seams
    are spans only: the caller's ``fetch`` holds all of it."""
    import jax.numpy as jnp

    from deequ_tpu.obs.recorder import feed_gauge
    from deequ_tpu.ops.device_policy import device_call, device_fetch

    before = _seam_counters()
    out = device_call(lambda: jnp.ones(8) + 1.0, "execute", what="op",
                      deadline=30.0)
    assert feed_gauge() == 1
    host = device_fetch(out, "probe", deadline=30.0, newest=True)
    d = _seam_deltas(before)
    assert host.tolist() == [2.0] * 8 and feed_gauge() == 0
    assert d["seam_fetch_count"] == 1 and d["seam_fetch_copy_count"] == 0
    assert d["seam_fetch_copy_seconds"] == 0.0


def test_a_worker_thread_counts_no_unfed_time_and_moves_no_gauge():
    import threading
    import time

    from deequ_tpu.obs import seam
    from deequ_tpu.obs.recorder import (
        device_fed, device_ready, feed_gauge, worker_seams,
    )

    before = _seam_counters()
    seen = []

    def work():
        with worker_seams():
            with seam("pack", chunk=0):
                time.sleep(0.003)
                device_fed()
                seen.append(feed_gauge())
                device_ready()

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and seen == [0]
    assert all(v == 0 for v in _seam_deltas(before).values())


def test_the_older_of_two_deferred_scans_leaves_the_gauge_up(gauge_at_zero):
    """A deferred scan keeps the mark of its last dispatch: fetching the
    older of two in flight proves nothing of the newer."""
    from deequ_tpu.obs.recorder import feed_gauge
    from deequ_tpu.ops.scan_engine import run_scan

    ops, _, _ = AnalysisRunner._build_scan_ops(_table(), _analyzers())
    run_scan(_table(), ops)  # builds the program
    assert feed_gauge() == 0
    older = run_scan(_table(seed=1), ops, defer=True)
    newer = run_scan(_table(seed=2), ops, defer=True)
    assert feed_gauge() == 2
    older.result()
    assert feed_gauge() == 1
    newer.result()
    assert feed_gauge() == 0


def _three_chunk_table(monkeypatch, n=3000):
    from deequ_tpu.ops import scan_engine

    monkeypatch.setattr(scan_engine, "MAX_RESIDENT_CHUNK_ROWS", 1024)
    table = _table(n=n)
    table.persist()
    assert len(table._device_cache.device_chunks) == 3
    return table


def test_a_three_chunk_resident_scan_ends_fed_to_zero_with_its_put_staged(
        monkeypatch, gauge_at_zero):
    """Three dispatches, two merges into an accumulator whose put is a
    ``stage`` like a chunk's (its bytes in ``bytes_staged``, no chunk
    counted), one fetch, and the gauge back at zero."""
    from deequ_tpu import Check, CheckLevel, VerificationSuite
    from deequ_tpu.obs.recorder import feed_gauge

    table = _three_chunk_table(monkeypatch)

    def run(recorder=None):
        builder = (
            VerificationSuite.on_data(table)
            .add_check(Check(CheckLevel.ERROR, "t").has_size(lambda n: n == 3000))
            .add_required_analyzers(_analyzers())
        )
        if recorder is not None:
            builder = builder.with_tracing(recorder)
        return builder.run()

    try:
        run()  # builds the programs
        before = _seam_counters()
        staged = (SCAN_STATS.bytes_staged, SCAN_STATS.chunks_staged)
        overlap = SCAN_STATS.ingest_overlap_frac
        rec = FlightRecorder()
        result = run(rec)
    finally:
        table.unpersist()
    assert str(result.status).endswith("SUCCESS") and feed_gauge() == 0
    d = _seam_deltas(before)
    assert d["seam_dispatch_count"] == 6      # three steps, three merges
    assert d["seam_fetch_count"] == d["seam_fetch_copy_count"] == 1
    (put,) = _spans(rec, "stage")
    assert put.args["what"] == "fold accumulator"
    assert SCAN_STATS.bytes_staged - staged[0] == put.args["bytes"] > 0
    assert SCAN_STATS.chunks_staged == staged[1]
    assert SCAN_STATS.ingest_overlap_frac == overlap
    assert 0 < d["unfed_seconds"] <= d["run_seconds"]
    assert _exclusive_seconds(d) == pytest.approx(d["run_seconds"], abs=1e-9)
    # the chunks between the first dispatch and the last wait were fed
    (fetch,) = _spans(rec, "fetch")
    assert d["unfed_seconds"] < d["run_seconds"] - (
        d["seam_fetch_seconds"] - d["seam_fetch_copy_seconds"]
        - fetch.args.get("unfed_s", 0.0)) + 1e-9
    phases = result.run_trace["phases"]
    assert phases["plan"]["unfed_seconds"] > 0
    assert phases["fetch.copy"]["unfed_seconds"] > 0


def test_an_own_pass_top_k_feeds_once_and_ends_at_zero(gauge_at_zero):
    """``resident_top_k`` goes through the boundary like a scan: its one
    dispatch feeds, its one fetch waits, copies and zeroes the gauge."""
    from deequ_tpu.obs.recorder import feed_gauge
    from deequ_tpu.ops.segment import resident_top_k

    rng = np.random.default_rng(3)
    words = np.array([f"w{i}" for i in range(40)], dtype=object)
    table = ColumnarTable([
        Column("s", DType.STRING,
               codes=rng.integers(0, 40, 5000).astype(np.int32),
               dictionary=words),
    ])
    table.persist()
    try:
        resident_top_k(table, [("s", 5)])  # builds the program
        before = _seam_counters()
        stats, _ = resident_top_k(table, [("s", 5)])
    finally:
        table.unpersist()
    d = _seam_deltas(before)
    assert feed_gauge() == 0 and len(stats[0].top) == 5
    assert d["seam_dispatch_count"] == 1 and d["seam_grouping_count"] == 1
    assert d["seam_fetch_count"] == d["seam_fetch_copy_count"] == 1
    assert 0 < d["unfed_seconds"] < _exclusive_seconds(d)


def _append(table, states, repository, key):
    from deequ_tpu import Check, CheckLevel, VerificationSuite
    from deequ_tpu.repository import ResultKey

    return (
        VerificationSuite.on_data(table)
        .add_check(Check(CheckLevel.ERROR, "a").has_size(lambda n: n > 0))
        .add_required_analyzers(_analyzers())
        .aggregate_with(states)
        .save_states_with(states)
        .use_repository(repository)
        .save_or_append_result(ResultKey(key, {"stream": "append"}))
        .run()
    )


def _suite_run():
    from deequ_tpu import Check, CheckLevel, VerificationSuite

    result = (
        VerificationSuite.on_data(_table())
        .add_check(Check(CheckLevel.ERROR, "t").has_size(lambda n: n == 4096))
        .add_required_analyzers(_analyzers())
        .run()
    )
    assert str(result.status).endswith("SUCCESS")
    return 1


def _two_appends():
    from deequ_tpu.repository.memory import InMemoryMetricsRepository
    from deequ_tpu.states import InMemoryStateProvider

    states, repository = InMemoryStateProvider(), InMemoryMetricsRepository()
    for key in (0, 1):
        result = _append(_table(seed=key), states, repository, key)
        assert str(result.status).endswith("SUCCESS")
    return 2


def test_legacy_wait_fields_equal_their_seam_sums():
    before = _seam_counters()
    _suite_run()
    _two_appends()
    d = _seam_deltas(before)
    assert d["seam_dispatch_count"] + d["seam_build_count"] >= 3
    assert d["dispatch_seconds"] == pytest.approx(
        d["seam_stage_seconds"] + d["seam_dispatch_seconds"], abs=1e-9
    )
    assert d["drain_wait_seconds"] == pytest.approx(
        d["seam_drain_seconds"] + d["seam_fetch_seconds"], abs=1e-9
    )
    assert d["seam_states_count"] == 2 and d["seam_repository_count"] == 2


@pytest.mark.parametrize(
    "workload,leaves",
    [
        (_suite_run, ("pack", "stage", "dispatch", "fetch", "evaluate")),
        (_two_appends, ("pack", "stage", "dispatch", "fetch", "states",
                        "evaluate", "repository")),
    ],
    ids=["suite", "two_appends"],
)
def test_seams_land_on_the_profilers_clock(workload, leaves, tmp_path):
    """Inside a profiler session every seam is a ``deequ.<name>``
    annotation in the ``.xplane.pb``: the leaves lie inside a
    ``deequ.run`` and never overlap each other on the caller's thread."""
    import jax

    from chipbench import trace_reduce

    workload()  # builds the program: the traced calls dispatch, not build
    with jax.profiler.trace(str(tmp_path)):
        runs = workload()
    names = ["deequ." + n for n in SEAM_NAMES + ("run", "scan_attempt")]
    host = trace_reduce.read_xplane(str(tmp_path), names)["host"]
    by_name = {}
    for name, start, end in host:
        by_name.setdefault(name, []).append((start, end))
    assert len(by_name["deequ.run"]) == runs
    assert len(by_name["deequ.scan_attempt"]) == runs
    spans = sorted(
        (start, end, name) for name, start, end in host
        if name[len("deequ."):] in leaves
    )
    assert {name for _, _, name in spans} == {"deequ." + n for n in leaves}
    for start, end, name in spans:
        assert any(
            lo <= start and end <= hi for lo, hi in by_name["deequ.run"]
        ), name
    for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
        assert start >= end, f"{a} overlaps {b}"


def test_fused_step_names_every_op_of_the_scan_suite():
    """The device side of the boundaries: each op's reductions lower
    under ``deequ.<Analyzer>.<column>`` (metadata only), so a device
    trace names what its XLA ops belong to."""
    import json
    import os

    from chipbench import cells, suite_build
    from chipbench.generators import profile_table
    from deequ_tpu.ops import scan_engine as eng

    with open(os.path.join(cells.ROOT, "chipbench", "suites", "scan.json")) as f:
        suite = json.load(f)
    cell = cells.load_cell("profile10m.scan")
    rows = 2048
    table = suite_build.table_of(
        profile_table.generate(rows, 7, cell["config"]["generator_params"])
    )
    analyzers = suite_build.analyzers_of(suite)
    ops, scannable, failures = AnalysisRunner._build_scan_ops(table, analyzers)
    assert len(ops) == len(suite["analyzers"]) and not failures
    needed = sorted({c for op in ops for c in op.columns})
    packer = eng._ChunkPacker({n: table[n] for n in needed}, rows)
    step_fn, _, _ = eng._build_step_fns(ops, packer.unpack_view(), None, rows)
    text = step_fn.lower(*packer.pack(0, rows), {}).as_text(debug_info=True)
    for op, entry in zip(ops, suite["analyzers"]):
        scope = f"deequ.{entry['analyzer']}.{'_'.join(entry['args'])}"
        assert eng.op_scope(op) == scope
        assert scope + "/" in text, scope
    assert "deequ.unpack/" in text and "deequ.flatten/" in text


# -- verification surface ----------------------------------------------------


def test_with_tracing_summary_on_result():
    from deequ_tpu import Check, CheckLevel, VerificationSuite

    result = (
        VerificationSuite.on_data(_table())
        .add_check(
            Check(CheckLevel.ERROR, "t").has_size(lambda n: n == 4096)
        )
        .with_tracing()
        .run()
    )
    assert str(result.status).endswith("SUCCESS")
    assert result.trace_recorder is not None
    assert result.run_trace["spans"] > 0
    assert "run" in result.run_trace["phases"]
    assert "scan_attempt" in result.run_trace["phases"]
    # untraced runs carry an empty summary
    plain = VerificationSuite.run(_table(), [])
    assert plain.run_trace == {} and plain.trace_recorder is None


def test_run_trace_reconciles_with_scan_stats():
    """The per-phase wall breakdown must reconcile with the ScanStats
    wall counters: the attempt span contains the dispatch window and
    the drain wait, and the boundary spans (stage, build, dispatch,
    drain, fetch) cover the same device time
    dispatch_seconds/drain_wait_seconds account (generous absolute
    slack — the recorder and the counters read different clocks)."""
    from deequ_tpu.verification import VerificationSuite

    before = {
        k: getattr(SCAN_STATS, k)
        for k in ("dispatch_seconds", "drain_wait_seconds", "scan_seconds")
    }
    result = VerificationSuite.do_verification_run(
        _table(n=50_000), [], _analyzers(), trace=FlightRecorder()
    )
    dispatch = SCAN_STATS.dispatch_seconds - before["dispatch_seconds"]
    drain = SCAN_STATS.drain_wait_seconds - before["drain_wait_seconds"]
    scan = SCAN_STATS.scan_seconds - before["scan_seconds"]
    phases = result.run_trace["phases"]
    SLACK = 0.25  # host-line slack on a noisy container
    attempt_wall = phases["scan_attempt"]["wall_seconds"]
    # containment: the attempt span brackets the whole scan wall
    assert attempt_wall + SLACK >= scan >= dispatch
    # coverage: the boundary spans account the same device time the
    # ScanStats wall counters do
    boundary_wall = sum(
        phases.get(name, {"wall_seconds": 0.0})["wall_seconds"]
        for name in ("stage", "build", "dispatch", "drain", "fetch")
    )
    assert boundary_wall >= (dispatch + drain) - SLACK
    assert boundary_wall <= attempt_wall + SLACK
    assert phases["run"]["wall_seconds"] + SLACK >= attempt_wall


# -- export ------------------------------------------------------------------


def _assert_tracks_well_formed(trace: dict) -> None:
    """Spans on one track must be monotone and properly nested: sorted
    by start, every pair is either disjoint or contained — never
    partially overlapping."""
    by_tid = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    for tid, events in by_tid.items():
        events.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in events:
            start, end = e["ts"], e["ts"] + e["dur"]
            while stack and start >= stack[-1] - 1e-6:
                stack.pop()
            if stack:
                assert end <= stack[-1] + 1e-6, (
                    f"partially overlapping spans on track {tid}: {e}"
                )
            stack.append(end)


def _traced_bisected_scan(rec):
    """A traced OOM-bisected scan — spans + rung events on the record."""
    from deequ_tpu.ops.device_policy import install_scan_fault_hook
    from deequ_tpu.resilience import FaultInjectingScanHook
    from deequ_tpu.resilience.governance import fault_state_scope

    with fault_state_scope():
        install_scan_fault_hook(
            FaultInjectingScanHook(faults={0: ("oom", 1)}, relative=True)
        )
        with recording_scope(rec):
            ctx = AnalysisRunner.do_analysis_run(
                _table(n=8192), _analyzers()
            )
    assert all(m.value.is_success for m in ctx.all_metrics())


def test_perfetto_export_is_valid_and_well_formed():
    rec = FlightRecorder()
    _traced_bisected_scan(rec)
    trace = json.loads(json.dumps(to_chrome_trace(rec)))
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    phs = {e["ph"] for e in trace["traceEvents"]}
    assert "X" in phs and "M" in phs and "i" in phs
    for e in trace["traceEvents"]:
        assert "pid" in e and "tid" in e and "name" in e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    _assert_tracks_well_formed(trace)
    # thread-name metadata covers every tid used
    named = {e["tid"] for e in trace["traceEvents"] if e["ph"] == "M"}
    used = {e["tid"] for e in trace["traceEvents"] if e["ph"] != "M"}
    assert used <= named


def test_truncated_trace_is_well_formed():
    """A recording stopped mid-span (kill-and-resume, a crash) still
    exports valid JSON: the open span closes at the recording's end and
    is marked truncated."""
    rec = FlightRecorder()
    with recording_scope(rec):
        span = rec.span("outer_work", phase="doomed")
        span.__enter__()
        rec.event("mid", ok=True)
        with rec.span("finished_child"):
            pass
        # ... the process dies here: `span` never exits
    assert len(rec.open_spans()) == 1
    trace = json.loads(json.dumps(to_chrome_trace(rec)))
    _assert_tracks_well_formed(trace)
    truncated = [
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e["args"].get("truncated")
    ]
    assert len(truncated) == 1
    assert truncated[0]["name"] == "outer_work"
    # the live recorder still holds the span open (export copies)
    assert len(rec.open_spans()) == 1


# -- metrics registry --------------------------------------------------------


def test_histogram_quantiles_and_bounds():
    h = Histogram("h", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 0.5, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["min"] == 0.0005 and snap["max"] == 2.0
    assert snap["p50"] == 0.01  # upper bound of the crossing bucket
    assert snap["p99"] == 2.0  # overflow bucket reports the observed max
    assert abs(snap["sum"] - 2.5605) < 1e-9


def test_histogram_family_bounds_label_cardinality():
    fam = HistogramFamily("f", max_labels=4, buckets=(0.1, 1.0))
    for i in range(10):
        fam.observe(f"tenant-{i}", 0.05)
    assert len(fam.labels()) == 4
    assert fam.evicted_labels == 6
    assert fam.aggregate.snapshot()["count"] == 10  # nothing lost overall


def test_execution_report_is_unified_registry_snapshot():
    import deequ_tpu

    report = deequ_tpu.execution_report()
    for section in ("scan", "retry", "hbm", "serve", "env", "instruments"):
        assert section in report, section
    # the "scan" section IS the legacy shape (read-through, not a fork)
    legacy = deequ_tpu.scan_execution_report()
    assert set(report["scan"]) == set(legacy)
    assert report["scan"]["scan_passes"] == legacy["scan_passes"]
    # env section reflects the registered switch set
    assert "DEEQU_TPU_TRACE" in report["env"]
    # text exposition renders scalar collector fields + instruments
    text = deequ_tpu.execution_report_text()
    assert "deequ_tpu_scan_scan_passes" in text
    assert "deequ_tpu_serve_latency_seconds_count" in text


def test_registry_reads_through_not_forked():
    """Mutating the singleton must be visible through the registry
    immediately — the unification is a view, not a copy."""
    before = REGISTRY.snapshot()["scan"]["rows_scanned"]
    SCAN_STATS.rows_scanned += 1234
    assert REGISTRY.snapshot()["scan"]["rows_scanned"] == before + 1234


# -- serve layer -------------------------------------------------------------


@pytest.fixture
def no_mesh():
    from deequ_tpu.parallel.mesh import use_mesh

    with use_mesh(None):
        yield


def _tenant_table(seed, n=64):
    r = np.random.default_rng(seed)
    return ColumnarTable(
        [
            Column("x", DType.FRACTIONAL, values=r.normal(0, 1, n),
                   mask=np.ones(n, dtype=np.bool_)),
        ]
    )


def _tenant_check(n=64):
    from deequ_tpu import Check, CheckLevel

    return (
        Check(CheckLevel.ERROR, "s")
        .has_size(lambda k: k == n)
        .has_completeness("x", lambda c: c == 1.0)
    )


def test_serve_latency_histograms_match_futures(no_mesh):
    from deequ_tpu.obs.registry import SERVE_LATENCY
    from deequ_tpu.serve import VerificationService

    SERVE_LATENCY.reset()
    with VerificationService(max_batch=8, coalesce_window=0.005) as svc:
        futures = {
            f"t{i}": svc.submit(
                _tenant_table(i), [_tenant_check()], tenant=f"t{i}"
            )
            for i in range(5)
        }
        results = {t: f.result(timeout=60) for t, f in futures.items()}
    assert all(str(r.status).endswith("SUCCESS") for r in results.values())
    snap = SERVE_LATENCY.snapshot()
    # one observation per resolved future, bit-equal sums
    assert snap["_all"]["count"] == 5
    observed_sum = sum(f.latency_seconds for f in futures.values())
    assert abs(snap["_all"]["sum"] - observed_sum) < 1e-6
    # per-tenant histograms exist and each saw exactly its own future
    for tenant, fut in futures.items():
        h = SERVE_LATENCY.label(tenant)
        assert h is not None and h.count == 1
        assert h.min <= fut.latency_seconds <= (h.max or np.inf)
        # the aggregate's quantile estimate is an UPPER bound for p50
    assert snap["_all"]["p50"] >= min(
        f.latency_seconds for f in futures.values()
    )


def test_traced_coalesced_serve_exports_tenant_spans(no_mesh, tmp_path):
    """The acceptance shape: one coalesced dispatch shows K tenant
    submit->resolve spans resolving against a single dispatch+fetch
    span pair, and the export is Perfetto-loadable JSON."""
    from deequ_tpu.obs import write_chrome_trace
    from deequ_tpu.serve import VerificationService

    rec = FlightRecorder()
    K = 4
    with VerificationService(
        trace=rec, max_batch=K, coalesce_window=0.05
    ) as svc:
        futures = [
            svc.submit(_tenant_table(9), [_tenant_check()], tenant=f"t{i}")
            for i in range(K)
        ]
        for f in futures:
            assert str(f.result(timeout=60).status).endswith("SUCCESS")
    tenant_spans = _spans(rec, "serve_request")
    assert len(tenant_spans) == K
    assert {r.track for r in tenant_spans} == {
        f"tenant/t{i}" for i in range(K)
    }
    # exactly one coalesced dispatch+fetch pair served all K tenants (the
    # first call of the packed program is its `build`)
    exec_spans = [
        r for r in _spans(rec)
        if r.name in ("dispatch", "build")
        and "coalesced dispatch" in r.args.get("what", "")
    ]
    fetch_spans = [
        r for r in _spans(rec, "fetch")
        if "coalesced" in r.args.get("what", "")
    ]
    assert len(exec_spans) == 1 and len(fetch_spans) == 1
    assert SCAN_STATS.coalesced_batches >= 1
    # every tenant span brackets the shared dispatch+fetch pair
    for r in tenant_spans:
        assert r.t_start <= exec_spans[0].t_start
        assert r.t_end >= fetch_spans[0].t_end - 1e-6
    assert [
        r for r in _spans(rec, "pack")
        if r.args.get("what") == "coalesce_assembly"
    ]
    assert _events(rec, "serve_submit")
    path = write_chrome_trace(rec, str(tmp_path / "serve.json"))
    trace = json.load(open(path))
    _assert_tracks_well_formed(trace)


def test_serve_kill_and_resume_trace_is_truncated_then_completes(no_mesh):
    """stop(drain=False) with pending work leaves a well-formed
    truncated trace; resume() on a fresh service completes the original
    futures and their spans appear on the SAME recording."""
    from deequ_tpu.serve import VerificationService

    rec = FlightRecorder()
    svc = VerificationService(
        trace=rec, start=False, max_batch=4, coalesce_window=0.0
    )
    futures = [
        svc.submit(_tenant_table(3), [_tenant_check()], tenant=f"t{i}")
        for i in range(3)
    ]
    pending = svc.stop(drain=False)
    assert len(pending) == 3 and not any(f.done() for f in futures)
    # the killed recording exports clean: submits recorded, no resolves
    trace = json.loads(json.dumps(to_chrome_trace(rec)))
    _assert_tracks_well_formed(trace)
    assert len(_events(rec, "serve_submit")) == 3
    assert not _spans(rec, "serve_request")
    # resume on a fresh service sharing the recorder
    svc2 = VerificationService(
        trace=rec, max_batch=4, coalesce_window=0.0
    )
    try:
        svc2.resume(pending)
        for f in futures:
            assert str(f.result(timeout=60).status).endswith("SUCCESS")
    finally:
        svc2.stop()
    assert len(_spans(rec, "serve_request")) == 3


# -- lint: the span-in-jit rule ----------------------------------------------


def test_span_in_jit_rule_flags_emission_in_traced_code():
    from deequ_tpu.lint.repo_lint import lint_source

    src = (
        "import jax\n"
        "def step(x, rec):\n"
        "    rec.event('bad', x=1)\n"
        "    return x * 2\n"
        "jitted = jax.jit(step)\n"
    )
    findings = lint_source(src, "ops/fake.py")
    assert [f.rule for f in findings] == ["span-in-jit"]
    assert "host callback" in findings[0].message


def test_span_in_jit_rule_allows_host_seams():
    from deequ_tpu.lint.repo_lint import lint_source

    src = (
        "import jax\n"
        "from deequ_tpu.obs.recorder import current_recorder\n"
        "def host_driver(x):\n"
        "    rec = current_recorder()\n"
        "    if rec is not None:\n"
        "        with rec.span('dispatch'):\n"
        "            return jax.jit(lambda a: a + 1)(x)\n"
        "    return jax.jit(lambda a: a + 1)(x)\n"
    )
    assert lint_source(src, "ops/fake.py") == []


def test_span_in_jit_transitive_callee_flagged():
    from deequ_tpu.lint.repo_lint import lint_source

    src = (
        "import jax\n"
        "def helper(x, rec):\n"
        "    rec.span('inner')\n"
        "    return x\n"
        "def step(x, rec):\n"
        "    return helper(x, rec)\n"
        "jitted = jax.jit(step)\n"
    )
    findings = lint_source(src, "ops/fake.py")
    assert [f.rule for f in findings] == ["span-in-jit"]


def test_repo_lint_gate_still_zero_findings():
    from deequ_tpu.lint.repo_lint import lint_paths

    findings = lint_paths()
    assert findings == [], [str(f) for f in findings]
