"""Closed-loop quality control plane suite (deequ_tpu/control, round
16) — tier-1 `ctrl`.

Contracts pinned here:

- serving-grade profiling: every profiler pass emitted through the
  ScanPlan/plan-cache seam is BIT-IDENTICAL to the offline profiler per
  column family (string/categorical incl. histograms + type inference,
  fractional, integral, nullable, KLL), and a repeat profile of the
  same tenant shape is a pure plan-cache hit — zero ``programs_built``,
  zero ``plan_lint_traces`` (with plan lint ON);
- the profiler x repository satellite: saved profiles now carry their
  pass-3 histograms through ``ColumnarMetricsRepository`` and reload
  bit-identically, including reuse-only runs
  (``fail_if_results_for_reusing_missing=True``) against a cold-reload
  repository;
- replay reproducibility: re-minting from the recorded profile history
  + recorded schema produces the identical check set (ids and codes) —
  no access to the original data;
- lifecycle: candidate -> shadow -> enforcing -> demoted with typed
  ``ControlPlaneException`` on illegal transitions; shadow evaluation
  is confined to the ``best_effort`` SLO class (typed otherwise) and a
  load-shed shadow window is harmless (no streak movement, zero impact
  on enforcing traffic — completed results bit-identical to unloaded);
- anomaly-gated promotion: exactly ``DEEQU_TPU_PROMOTE_WINDOWS``
  consecutive clean windows promote, an anomalous window demotes an
  enforcing check, and the typed events are exactly-once through
  kill-and-resume (the per-check ``last_window`` watermark makes window
  replay a no-op);
- registry persistence: checksummed atomic state — torn/corrupt files
  surface typed ``CorruptStateException``, never silent event
  duplication.
"""

import json
import os
import struct

import numpy as np
import pytest

from deequ_tpu import VerificationSuite
from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
from deequ_tpu.control import (
    CONTROL_STATS,
    CheckRegistry,
    ControlLoop,
    DemotionEvent,
    PromotionEvent,
    PromotionGate,
    ServeProfileRuns,
    ShadowOutcome,
    SuggestionEngine,
    profile_key,
)
from deequ_tpu.data.table import ColumnarTable
from deequ_tpu.exceptions import (
    ControlPlaneException,
    CorruptStateException,
    EnvConfigError,
)
from deequ_tpu.parallel.mesh import use_mesh
from deequ_tpu.profiles import ColumnProfiler, ColumnProfilerRunner
from deequ_tpu.repository import (
    ColumnarMetricsRepository,
    InMemoryMetricsRepository,
    ResultKey,
)
from deequ_tpu.serve import Slo, VerificationService

pytestmark = pytest.mark.ctrl


def _bits(v):
    return struct.pack("<d", v).hex() if isinstance(v, float) else v


def _window_table(seed=0, n=160):
    """One observation window of multi-family tenant data: categorical
    string, fractional, nullable fractional, unique integral."""
    r = np.random.default_rng(seed)
    vals = r.uniform(1.0, 5.0, size=n)
    return ColumnarTable.from_pydict({
        "cat": r.choice(["a", "b", "c"], size=n).tolist(),
        "value": vals.tolist(),
        "maybe": [float(v) if i % 10 else None for i, v in enumerate(vals)],
        "ident": list(range(n)),
    })


def _assert_profiles_identical(a, b, kll=False):
    assert a.num_records == b.num_records
    assert sorted(a.profiles) == sorted(b.profiles)
    for name in a.profiles:
        pa, pb = a.profiles[name], b.profiles[name]
        assert type(pa) is type(pb), name
        assert _bits(pa.completeness) == _bits(pb.completeness), name
        assert (
            pa.approximate_num_distinct_values
            == pb.approximate_num_distinct_values
        ), name
        assert pa.data_type == pb.data_type, name
        assert pa.is_data_type_inferred == pb.is_data_type_inferred
        assert pa.type_counts == pb.type_counts, name
        assert (pa.histogram is None) == (pb.histogram is None), name
        if pa.histogram is not None:
            assert sorted(pa.histogram.values) == sorted(pb.histogram.values)
            for k in pa.histogram.values:
                va, vb = pa.histogram.values[k], pb.histogram.values[k]
                assert va.absolute == vb.absolute, (name, k)
                assert _bits(va.ratio) == _bits(vb.ratio), (name, k)
        if hasattr(pa, "mean"):
            for field in ("mean", "maximum", "minimum", "sum", "std_dev"):
                va, vb = getattr(pa, field), getattr(pb, field)
                assert (va is None) == (vb is None), (name, field)
                if va is not None:
                    assert _bits(va) == _bits(vb), (name, field)
            if kll:
                assert (pa.approx_percentiles is None) == (
                    pb.approx_percentiles is None
                )
                if pa.approx_percentiles is not None:
                    assert [
                        _bits(v) for v in pa.approx_percentiles
                    ] == [_bits(v) for v in pb.approx_percentiles], name


@pytest.fixture
def single_device():
    with use_mesh(None):
        yield


# -- serving-grade profiling ---------------------------------------------


def test_fused_profile_bit_identical_to_offline(single_device):
    """Every pass through the serving seam (ServeProfileRuns) produces
    profiles bit-identical to the offline profiler across all column
    families — string/categorical (histograms + inferred types),
    fractional, nullable, integral, and the KLL sketch."""
    data = _window_table(seed=3)
    offline = ColumnProfiler.profile(data, kll_profiling=True)
    svc = VerificationService(plan_lint="error")
    svc.start()
    try:
        fused = ColumnProfiler.profile(
            data, kll_profiling=True,
            runs=ServeProfileRuns(svc, tenant="t0"),
        )
    finally:
        svc.stop(drain=False)
    _assert_profiles_identical(offline, fused, kll=True)


def test_repeat_profile_is_pure_plan_cache_hit(single_device):
    """The repeat-tenant contract extends to profiling: a second
    profile of the same tenant shape builds zero programs and performs
    zero lint traces — with plan lint enforcing."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    svc = VerificationService(plan_lint="error")
    svc.start()
    try:
        repo = InMemoryMetricsRepository()
        registry = CheckRegistry()
        engine = SuggestionEngine(repo, registry, service=svc)
        engine.profile_tenant(_window_table(seed=10), "t0", 1)
        built = SCAN_STATS.programs_built
        linted = SCAN_STATS.plan_lint_traces
        fetches = SCAN_STATS.device_fetches
        batches = SCAN_STATS.coalesced_batches
        grouped = SCAN_STATS.seam_grouping_count
        engine.profile_tenant(_window_table(seed=11), "t0", 2)
        assert SCAN_STATS.programs_built == built
        assert SCAN_STATS.plan_lint_traces == linted
        # one-fetch contract: the repeat profile's passes each drained
        # exactly one fetch per coalesced batch; the histogram pass over
        # the resident string column is one dispatch and one fetch of its
        # own (counted since PR 32: it was three uncounted round trips)
        new_batches = SCAN_STATS.coalesced_batches - batches
        assert new_batches >= 2  # generic pass + per-schema passes
        own_passes = SCAN_STATS.seam_grouping_count - grouped
        assert own_passes == 1
        assert SCAN_STATS.device_fetches - fetches == new_batches + own_passes
    finally:
        svc.stop(drain=False)


def test_profile_series_lands_in_repository_per_tenant(single_device):
    """Profiles serialize as metrics into the repository as a
    per-tenant time series under {tenant, kind=profile} tags."""
    svc = VerificationService()
    svc.start()
    try:
        repo = ColumnarMetricsRepository()
        registry = CheckRegistry()
        engine = SuggestionEngine(repo, registry, service=svc)
        for w in (1, 2):
            engine.profile_tenant(_window_table(seed=w), "t0", w)
        engine.profile_tenant(_window_table(seed=9), "other", 1)
        assert engine.history("t0") == [1, 2]
        assert engine.history("other") == [1]
        saved = repo.load_by_key(profile_key("t0", 1))
        assert saved is not None
        assert Size() in saved.analyzer_context.metric_map
        # pass-3 histograms ride the repository too (the satellite fix)
        from deequ_tpu.analyzers import Histogram

        assert Histogram("cat") in saved.analyzer_context.metric_map
    finally:
        svc.stop(drain=False)


# -- profiler x repository satellite -------------------------------------


def test_profiler_builder_against_columnar_repository(tmp_path):
    """ColumnProfilerRunBuilder.use_repository/save_or_append_result
    against the columnar backend: saved profiles (histograms included)
    reload bit-identically, including a reuse-ONLY run against a
    cold-reloaded repository with fail_if_missing=True."""
    data = _window_table(seed=7)
    key = ResultKey(42, {"tenant": "t0", "kind": "profile"})
    repo = ColumnarMetricsRepository(str(tmp_path / "repo"))
    first = (
        ColumnProfilerRunner.on_data(data)
        .use_repository(repo)
        .save_or_append_result(key)
        .run()
    )
    # cold reload: a fresh repository over the same segments serves the
    # whole profile from storage — no recomputation possible on empty
    # data (reuse-only, typed failure if anything were missing)
    cold = ColumnarMetricsRepository(str(tmp_path / "repo"))
    again = (
        ColumnProfilerRunner.on_data(data)
        .use_repository(cold)
        .reuse_existing_results_for_key(key, fail_if_missing=True)
        .run()
    )
    _assert_profiles_identical(first, again)
    assert first.profiles["cat"].histogram is not None


# -- replay + suggestion --------------------------------------------------


def test_replay_reproduces_identical_check_set(single_device):
    """The reproducibility acceptance: a second registry re-minting
    from the SAME recorded profile history + schema produces the
    identical check ids and codes — no access to the original data."""
    svc = VerificationService()
    svc.start()
    try:
        repo = InMemoryMetricsRepository()
        registry = CheckRegistry()
        engine = SuggestionEngine(repo, registry, service=svc)
        for w in (1, 2, 3):
            engine.profile_tenant(_window_table(seed=w), "t0", w)
            engine.suggest("t0", w)
    finally:
        svc.stop(drain=False)

    replayed = CheckRegistry()
    replayed.note_tenant_schema("t0", registry.tenant_schema("t0"))
    engine2 = SuggestionEngine(repo, replayed)  # no service, no data
    for w in (1, 2, 3):
        engine2.suggest("t0", w)
    orig = {c.check_id: c.code for c in registry.checks("t0")}
    mint = {c.check_id: c.code for c in replayed.checks("t0")}
    assert orig == mint
    assert orig  # non-trivial check set
    assert CONTROL_STATS.profile_replays >= 6


def test_replay_without_history_raises_typed():
    engine = SuggestionEngine(InMemoryMetricsRepository(), CheckRegistry())
    with pytest.raises(ControlPlaneException):
        engine.replay("ghost")


# -- lifecycle + SLO isolation -------------------------------------------


def test_lifecycle_transitions_typed():
    reg = CheckRegistry()
    reg.register_candidate("c1", "t0", "x", "R", ".code()", "d", "v")
    with pytest.raises(ControlPlaneException):
        reg.promote("c1", 1)  # candidate cannot promote directly
    reg.to_shadow("c1")
    with pytest.raises(ControlPlaneException):
        reg.to_shadow("c1")  # already shadow
    event = reg.promote("c1", 5)
    assert isinstance(event, PromotionEvent) and event.check_id == "c1"
    demo = reg.demote("c1", 6, "anomaly")
    assert isinstance(demo, DemotionEvent) and demo.reason == "anomaly"
    # demoted -> shadow re-trial is legal; streak restarts
    retried = reg.to_shadow("c1")
    assert retried.state == "shadow" and retried.clean_windows == 0
    with pytest.raises(ControlPlaneException):
        reg.promote("ghost", 1)


def test_shadow_eval_confined_to_best_effort(single_device):
    svc = VerificationService(start=False)
    try:
        repo = InMemoryMetricsRepository()
        registry = CheckRegistry()
        engine = SuggestionEngine(repo, registry, service=svc)
        registry.register_candidate(
            "t0:x:R", "t0", "x", "R", ".c()", "d", "v",
            constraint=object(),
        )
        registry.to_shadow("t0:x:R")
        for cls in ("critical", "standard"):
            with pytest.raises(ControlPlaneException):
                engine.evaluate_shadow(
                    _window_table(), "t0", 1, slo=Slo(cls=cls),
                )
    finally:
        svc.stop(drain=False)


def test_shadow_shed_under_chaos_load_zero_enforcing_impact(single_device):
    """Under a chaos-load-seam-derived critical burst that saturates
    the queue, the best_effort shadow evaluation sheds TYPED (streaks
    untouched) while every enforcing-class result completes
    bit-identically to its unloaded serial run — and no critical
    request is ever shed by shadow traffic."""
    from deequ_tpu.resilience.chaos import ChaosSchedule

    schedule = ChaosSchedule.generate_load(seed=16)
    burst = max(
        (e["burst"] for e in schedule.events if e["kind"] == "spike"),
        default=8,
    )
    table = _window_table(seed=16, n=96)
    analyzers = [Size(), Completeness("value"), Mean("value"), Sum("ident")]
    serial = VerificationSuite.run(table, [], required_analyzers=analyzers)

    repo = InMemoryMetricsRepository()
    registry = CheckRegistry()
    # mint real shadow checks from offline history first
    engine = SuggestionEngine(repo, registry)
    engine.profile_tenant(table, "t0", 1)
    engine.suggest("t0", 1)
    shadow_before = {
        c.check_id: c.clean_windows for c in registry.checks("t0", "shadow")
    }
    assert shadow_before

    pending = max(8, min(burst, 12))
    svc = VerificationService(
        start=False, max_pending=pending, coalesce_window=0.0,
    )
    try:
        engine.service = svc
        # scripted spike: the unstarted worker holds the queue full of
        # critical traffic (class share 1.0), so the best_effort shadow
        # submission is refused typed at admission
        flood = [
            svc.submit(
                table, required_analyzers=analyzers,
                tenant=f"burst{i}", slo=Slo(cls="critical"),
            )
            for i in range(pending)
        ]
        shed = CONTROL_STATS.shadow_evals_shed
        outcome = engine.evaluate_shadow(table, "t0", 2)
        assert outcome.status == "shed"
        assert CONTROL_STATS.shadow_evals_shed == shed + 1
        # a shed window moves no streak and mints no event
        gate = PromotionGate(registry, windows=3)
        assert gate.observe_window("t0", 2, outcome) == []
        assert {
            c.check_id: c.clean_windows
            for c in registry.checks("t0", "shadow")
        } == shadow_before
        # zero enforcing impact: the critical flood all completes,
        # bit-identical to the unloaded serial run
        svc.start()
        for f in flood:
            got = f.result(timeout=120).metrics
            for a in analyzers:
                assert _bits(got[a].value.get()) == _bits(
                    serial.metrics[a].value.get()
                )
    finally:
        svc.stop(drain=False)


# -- anomaly-gated promotion ----------------------------------------------


def _mint_shadow(registry, tenant="t0", n=2):
    ids = []
    for i in range(n):
        cid = f"{tenant}:c{i}:R"
        registry.register_candidate(
            cid, tenant, f"c{i}", "R", f".c{i}()", "d", "v",
            constraint=object(),
        )
        registry.to_shadow(cid)
        ids.append(cid)
    return ids


def test_promotion_after_n_clean_windows_envcfg(monkeypatch):
    monkeypatch.setenv("DEEQU_TPU_PROMOTE_WINDOWS", "2")
    registry = CheckRegistry()
    (cid,) = _mint_shadow(registry, n=1)
    gate = PromotionGate(registry)  # windows resolved from envcfg
    assert gate.windows == 2
    assert gate.observe_window("t0", 1) == []
    events = gate.observe_window("t0", 2)
    assert [e.kind for e in events] == ["promotion"]
    assert registry.get(cid).state == "enforcing"
    monkeypatch.setenv("DEEQU_TPU_PROMOTE_WINDOWS", "zero")
    with pytest.raises(EnvConfigError):
        PromotionGate(CheckRegistry())


def test_dirty_window_resets_streak_and_demotes_enforcing():
    registry = CheckRegistry()
    a, b = _mint_shadow(registry, n=2)
    gate = PromotionGate(registry, windows=3)
    gate.observe_window("t0", 1)
    gate.observe_window("t0", 2)
    # shadow failure on `a` resets ONLY a's streak
    gate.observe_window(
        "t0", 3, ShadowOutcome("t0", 3, "failed", (a,)),
    )
    assert registry.get(a).clean_windows == 0
    assert registry.get(b).clean_windows == 3  # promoted this window
    assert registry.get(b).state == "enforcing"
    # an anomalous window demotes the enforcing check, exactly once

    class _Alert:
        def __init__(self, time, series):
            self.time, self.series = time, series

    class _Monitor:
        alerts = [
            _Alert(4, 'Completeness(c1)|{"kind":"profile","tenant":"t0"}'),
        ]

    gate2 = PromotionGate(registry, monitor=_Monitor(), windows=3)
    events = gate2.observe_window("t0", 4)
    assert [e.kind for e in events] == ["demotion"]
    assert registry.get(b).state == "demoted"
    # replaying the same window is a watermark no-op — exactly-once
    assert gate2.observe_window("t0", 4) == []


def test_promotion_events_exactly_once_through_kill_and_resume(tmp_path):
    """Kill-and-resume mid-streak: the resumed registry replays the
    already-observed windows as no-ops (persisted last_window
    watermark), promotes on the FIRST new clean window, and the typed
    event ledger holds each event exactly once with monotone seqs."""
    state_dir = str(tmp_path / "ctrl")
    registry = CheckRegistry(state_dir=state_dir)
    ids = _mint_shadow(registry, n=2)
    gate = PromotionGate(registry, windows=3)
    gate.observe_window("t0", 1)
    gate.observe_window("t0", 2)
    blob_before = json.dumps(registry.state_blob(), sort_keys=True)

    # kill: drop the registry; resume from disk
    resumed = CheckRegistry(state_dir=state_dir)
    assert (
        json.dumps(resumed.state_blob(), sort_keys=True) == blob_before
    )
    gate2 = PromotionGate(resumed, windows=3)
    # replay of already-folded windows: watermark no-ops
    assert gate2.observe_window("t0", 1) == []
    assert gate2.observe_window("t0", 2) == []
    events = gate2.observe_window("t0", 3)
    assert sorted(e.check_id for e in events) == sorted(ids)
    assert all(e.kind == "promotion" for e in events)
    # and a second resume still holds each event exactly once
    final = CheckRegistry(state_dir=state_dir)
    ledger = final.events
    assert len(ledger) == 2
    assert sorted(e.check_id for e in ledger) == sorted(ids)
    assert [e.seq for e in ledger] == sorted(set(e.seq for e in ledger))
    assert PromotionGate(final, windows=3).observe_window("t0", 3) == []
    assert len(final.events) == 2


def test_registry_torn_write_recovery(tmp_path):
    """A torn or corrupted registry state file surfaces typed
    CorruptStateException at resume — never a silently emptied (or
    event-duplicating) lifecycle."""
    state_dir = str(tmp_path / "ctrl")
    registry = CheckRegistry(state_dir=state_dir)
    _mint_shadow(registry, n=1)
    path = os.path.join(state_dir, "control-registry.json")
    blob = open(path, "rb").read()

    # torn tail (partial write surviving a crash without the atomic
    # rename would be truncated): checksum mismatch, typed
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(CorruptStateException):
        CheckRegistry(state_dir=state_dir)

    # bit flip inside the payload: checksum mismatch, typed
    flipped = bytearray(blob)
    flipped[-3] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(flipped))
    with pytest.raises(CorruptStateException):
        CheckRegistry(state_dir=state_dir)

    # restore + a leftover temp file from a killed writer: harmless
    with open(path, "wb") as f:
        f.write(blob)
    with open(path + ".tmp.123", "wb") as f:
        f.write(b"garbage")
    resumed = CheckRegistry(state_dir=state_dir)
    assert [c.check_id for c in resumed.checks()] == ["t0:c0:R"]


# -- the closed loop end-to-end -------------------------------------------


def test_cold_tenant_reaches_enforcing_check_set(single_device, monkeypatch):
    """The acceptance scenario: a cold tenant, zero hand-written
    constraints, reaches an enforcing anomaly-vetted check set through
    profile -> suggest -> shadow -> promote, with the obs control
    section reporting the lifecycle census."""
    monkeypatch.setenv("DEEQU_TPU_MONITOR", "1")
    from deequ_tpu.anomaly import OnlineNormalStrategy
    from deequ_tpu.repository.monitor import QualityMonitor

    repo = InMemoryMetricsRepository()
    registry = CheckRegistry()
    monitor = QualityMonitor()
    monitor.watch(
        OnlineNormalStrategy(), metric_name="Completeness",
        tags={"kind": "profile"}, warmup=10, name="profile-completeness",
    )
    svc = VerificationService(plan_lint="error")
    svc.start()
    try:
        engine = SuggestionEngine(repo, registry, service=svc)
        loop = ControlLoop(
            engine, PromotionGate(registry, monitor=monitor, windows=3)
        )
        promotions = []
        for w in range(1, 5):
            step = loop.step(_window_table(seed=100 + w), "cold", w)
            assert step.shadow is None or step.shadow.status in (
                "passed", "failed",
            )
            promotions += [e for e in step.events if e.kind == "promotion"]
        enforcing = registry.checks("cold", "enforcing")
        assert enforcing, "cold tenant never reached an enforcing set"
        assert {e.check_id for e in promotions} == {
            c.check_id for c in enforcing
        }
        # every enforcing check was minted by the loop, not hand-written
        assert all(c.rule for c in enforcing)
        check = engine.build_check("cold", "enforcing")
        assert check is not None and len(check.constraints) == len(enforcing)

        from deequ_tpu import execution_report

        section = execution_report()["control"]
        assert section["active"] is True
        assert section["checks_by_state"]["enforcing"] == len(enforcing)
        assert section["promotions"] >= len(enforcing)
    finally:
        svc.stop(drain=False)


def test_adaptation_resets_shadow_streak(single_device):
    """Auto-tighten/loosen: a re-mint whose code moved (the threshold
    tracked newer history) records an adaptation and restarts the
    vetting streak — the check being vetted changed."""
    registry = CheckRegistry()
    registry.register_candidate(
        "t0:x:R", "t0", "x", "R", ".has(0.9)", "d", "v", constraint=object()
    )
    registry.to_shadow("t0:x:R")
    registry.record_window("t0:x:R", 1, "clean", promote_after=5)
    registry.record_window("t0:x:R", 2, "clean", promote_after=5)
    assert registry.get("t0:x:R").clean_windows == 2
    before = CONTROL_STATS.adaptations
    registry.register_candidate(
        "t0:x:R", "t0", "x", "R", ".has(0.95)", "d", "v", constraint=object()
    )
    check = registry.get("t0:x:R")
    assert check.adaptations == 1 and check.clean_windows == 0
    assert CONTROL_STATS.adaptations == before + 1
    # unchanged code: idempotent re-bind, streak untouched
    registry.record_window("t0:x:R", 3, "clean", promote_after=5)
    registry.register_candidate(
        "t0:x:R", "t0", "x", "R", ".has(0.95)", "d", "v", constraint=object()
    )
    assert registry.get("t0:x:R").clean_windows == 1
