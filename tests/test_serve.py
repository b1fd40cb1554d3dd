"""Serving-layer suite (deequ_tpu/serve, round 10) — tier-1 `serve`.

Contracts pinned here:

- COALESCED == SERIAL, bitwise: every analyzer family's metric from a
  coalesced multi-tenant dispatch is bit-identical to a per-tenant
  ``VerificationSuite`` run on the same table (encoded-ingest and
  selection-kernel/quantile members included), and tenant-axis padding
  slots perturb nothing;
- plan-cache semantics: repeat suite = HIT with ZERO new traces / lint
  traces / compiles (the hard repeat-tenant assert); schema, predicate,
  layout, or row-count changes = MISS;
- isolation: a device fault during a coalesced dispatch bisects the
  tenant axis and every healthy member completes; one member's
  run-budget exhaustion degrades only its own slice; repeat-offender
  tenants are quarantined to the serial path and healed by a success;
- lifecycle: future cancellation, typed backpressure/closed errors, and
  kill-and-resume of a pending queue onto the original futures;
- packed plan lint: coalesced programs lint under their own memo key
  with per-member slice checks — drift sims smuggle a sort (select
  member) and a decoded plane (encoded member) into a packed plan.
"""

import struct

import numpy as np
import pytest

from deequ_tpu import Check, CheckLevel, VerificationSuite
from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantile,
    Completeness,
    Maximum,
    Mean,
    Minimum,
    PatternMatch,
    Size,
    StandardDeviation,
    Sum,
    Uniqueness,
)
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import (
    EnvConfigError,
    ServiceClosedException,
    ServiceOverloadedException,
)
from deequ_tpu.ops.scan_engine import SCAN_STATS, install_scan_fault_hook
from deequ_tpu.parallel.mesh import use_mesh
from deequ_tpu.resilience import FaultInjectingScanHook
from deequ_tpu.resilience.governance import RunPolicy
from deequ_tpu.serve import VerificationService

pytestmark = pytest.mark.serve


# -- fixtures ----------------------------------------------------------------


def _table(n=256, seed=0, with_string=False, encoded=False):
    r = np.random.default_rng(seed)
    cols = [
        Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
               mask=r.random(n) > 0.05),
        Column("i", DType.INTEGRAL,
               values=r.integers(0, 50, n).astype(np.float64),
               mask=np.ones(n, bool)),
    ]
    if with_string:
        codes, dictionary = _string_col(r, n)
        cols.append(Column("s", DType.STRING, codes=codes,
                           dictionary=dictionary))
    t = ColumnarTable(cols)
    if encoded:
        assert t.encode(["i"])["i"].encoding is not None
    return t


def _string_col(r, n):
    dictionary = np.array(["aa", "bb", "cc-1", "dd"], dtype=object)
    codes = r.integers(0, len(dictionary), n).astype(np.int32)
    return codes, dictionary


def _families(with_string=False):
    analyzers = [
        Size(), Completeness("x"), Mean("x"), StandardDeviation("x"),
        Minimum("x"), Maximum("x"), Sum("x"), ApproxCountDistinct("x"),
        # the selection-kernel family member (sort path when coalesced,
        # exactly as the serial non-resident baseline runs it)
        ApproxQuantile("x", 0.5), Mean("i"),
    ]
    if with_string:
        analyzers.append(PatternMatch("s", r"^[a-z]+$"))
    return analyzers


def _bits(value):
    if isinstance(value, float):
        return struct.pack("<d", value)
    return value


def _assert_bit_identical(serial_result, served_result, label=""):
    assert serial_result.status == served_result.status, label
    for a, m1 in serial_result.metrics.items():
        m2 = served_result.metrics[a]
        assert m1.value.is_success == m2.value.is_success, (label, str(a))
        if m1.value.is_success:
            assert _bits(m1.value.get()) == _bits(m2.value.get()), (
                f"{label}: {a} serial={m1.value.get()!r} "
                f"served={m2.value.get()!r}"
            )


@pytest.fixture
def single_device():
    with use_mesh(None):
        yield


@pytest.fixture
def service(single_device):
    svc = VerificationService(max_batch=16, coalesce_window=0.02)
    yield svc
    svc.stop(drain=False)


# -- bit-identity ------------------------------------------------------------


def test_coalesced_bit_identical_per_family(service):
    """8 same-plan tenants (stat + sketch + quantile + encoded members)
    coalesce into one dispatch; every metric is bit-identical to the
    per-tenant serial run."""
    analyzers = _families()
    tables = [_table(n=256, seed=s, encoded=True) for s in range(8)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in tables
    ]
    before = SCAN_STATS.coalesced_batches
    futures = [
        service.submit(t, required_analyzers=analyzers, tenant=f"t{i}")
        for i, t in enumerate(tables)
    ]
    served = [f.result(timeout=60) for f in futures]
    assert SCAN_STATS.coalesced_batches > before, "nothing coalesced"
    for i, (s, c) in enumerate(zip(serial, served)):
        _assert_bit_identical(s, c, label=f"tenant {i}")


def test_coalesced_string_luts_bit_identical(service):
    """String members (per-tenant dictionaries stacked as LUT args, each
    padded to the group max) match their serial runs bitwise."""
    analyzers = _families(with_string=True)
    tables = [_table(n=128, seed=s, with_string=True) for s in range(5)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in tables
    ]
    futures = [
        service.submit(t, required_analyzers=analyzers, tenant=f"s{i}")
        for i, t in enumerate(tables)
    ]
    served = [f.result(timeout=60) for f in futures]
    for i, (s, c) in enumerate(zip(serial, served)):
        _assert_bit_identical(s, c, label=f"string tenant {i}")


def test_padding_slots_do_not_perturb(single_device):
    """A 3-member batch pads its tenant axis to the pow2 bucket (1 dummy
    all-invalid slice); member results are unchanged bitwise. The
    service starts AFTER all three are queued, so they land in exactly
    one batch regardless of scheduler timing."""
    analyzers = _families()
    tables = [_table(n=200, seed=40 + s) for s in range(3)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in tables
    ]
    padded_before = SCAN_STATS.coalesce_padded_slots
    svc = VerificationService(start=False, max_batch=16)
    try:
        futures = [
            svc.submit(t, required_analyzers=analyzers, tenant=f"p{i}")
            for i, t in enumerate(tables)
        ]
        svc.start()
        served = [f.result(timeout=60) for f in futures]
    finally:
        svc.stop(drain=False)
    assert SCAN_STATS.coalesce_padded_slots - padded_before >= 1
    for i, (s, c) in enumerate(zip(serial, served)):
        _assert_bit_identical(s, c, label=f"padded batch member {i}")


def test_one_fetch_per_coalesced_batch(service):
    """The one-fetch contract at BATCH granularity: K members, exactly
    one device->host materialization."""
    analyzers = _families()
    tables = [_table(n=128, seed=60 + s) for s in range(6)]
    # warm the plan + program so the measured batch is steady-state
    service.submit(
        _table(n=128, seed=59), required_analyzers=analyzers, tenant="w"
    ).result(timeout=60)
    service.flush()
    fetches = SCAN_STATS.device_fetches
    batches = SCAN_STATS.coalesced_batches
    futures = [
        service.submit(t, required_analyzers=analyzers, tenant=f"f{i}")
        for i, t in enumerate(tables)
    ]
    [f.result(timeout=60) for f in futures]
    new_batches = SCAN_STATS.coalesced_batches - batches
    assert new_batches >= 1
    assert SCAN_STATS.device_fetches - fetches == new_batches, (
        "a coalesced batch must pay exactly one fetch"
    )


def test_mixed_row_counts_group_separately(service):
    """Different row counts never share a packed dispatch (chunk padding
    would shift reduction association — the group_scannable rule); both
    groups still serve bit-identical results."""
    analyzers = [Size(), Mean("x"), Completeness("x")]
    t_small = [_table(n=100, seed=s) for s in range(2)]
    t_big = [_table(n=300, seed=10 + s) for s in range(2)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in t_small + t_big
    ]
    futures = [
        service.submit(t, required_analyzers=analyzers, tenant=f"m{i}")
        for i, t in enumerate(t_small + t_big)
    ]
    served = [f.result(timeout=60) for f in futures]
    for i, (s, c) in enumerate(zip(serial, served)):
        _assert_bit_identical(s, c, label=f"mixed member {i}")


def test_grouping_suite_serves_serial(service):
    """A suite with a grouping analyzer (Uniqueness) is not coalescable;
    the service routes it through the ordinary engine with identical
    results."""
    check = (
        Check(CheckLevel.ERROR, "u")
        .has_uniqueness(("i",), lambda u: u >= 0.0)
        .has_size(lambda n: n == 64)
    )
    t = _table(n=64, seed=7)
    serial = VerificationSuite.run(_table(n=64, seed=7), [check])
    before = SCAN_STATS.coalesced_batches
    served = service.submit(t, [check], tenant="g").result(timeout=60)
    assert SCAN_STATS.coalesced_batches == before
    assert served.scan_stats.get("coalesced") is False
    _assert_bit_identical(serial, served, label="grouping suite")


def test_service_under_mesh_serves_serial(single_device):
    """Constructed under an active mesh the service preserves the
    caller's sharded numerics by serving every suite serially."""
    from deequ_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    if mesh is None:
        pytest.skip("needs the virtual multi-device environment")
    with use_mesh(mesh):
        svc = VerificationService(max_batch=8, coalesce_window=0.0)
        try:
            analyzers = [Size(), Mean("x")]
            t = _table(n=128, seed=3)
            serial = VerificationSuite.run(
                _table(n=128, seed=3), [], required_analyzers=analyzers
            )
            before = SCAN_STATS.coalesced_batches
            served = svc.submit(
                t, required_analyzers=analyzers, tenant="mesh"
            ).result(timeout=60)
            assert SCAN_STATS.coalesced_batches == before
            _assert_bit_identical(serial, served, label="mesh tenant")
        finally:
            svc.stop(drain=False)


# -- plan-cache semantics ----------------------------------------------------


def test_plan_cache_hit_zero_traces(single_device):
    """THE repeat-tenant contract: the second identical suite is a cache
    hit and adds ZERO program builds and ZERO plan-lint traces (lint
    armed to prove the verdict memoizes under the packed key)."""
    svc = VerificationService(
        max_batch=4, coalesce_window=0.0, plan_lint="error"
    )
    try:
        analyzers = _families()
        svc.submit(
            _table(n=128, seed=1), required_analyzers=analyzers, tenant="a"
        ).result(timeout=60)
        built = SCAN_STATS.programs_built
        lints = SCAN_STATS.plan_lint_traces
        hits = SCAN_STATS.plan_cache_hits
        result = svc.submit(
            _table(n=128, seed=2), required_analyzers=analyzers, tenant="a"
        ).result(timeout=60)
        assert all(m.value.is_success for m in result.metrics.values()), [
            str(m.value) for m in result.metrics.values()
            if m.value.is_failure
        ]
        assert SCAN_STATS.programs_built == built, (
            "repeat suite re-traced the program"
        )
        assert SCAN_STATS.plan_lint_traces == lints, (
            "repeat suite re-traced the plan lint"
        )
        assert SCAN_STATS.plan_cache_hits == hits + 1
    finally:
        svc.stop(drain=False)


def test_plan_cache_miss_on_schema_predicate_and_rows(service):
    """Schema change, predicate change, or row-count change each miss
    the cache; an unchanged resubmit hits."""
    base = [Size(), Mean("x"), Completeness("x")]
    where = [Size(), Mean("x", where="x > 90"), Completeness("x")]

    def run(analyzers, table):
        misses = SCAN_STATS.plan_cache_misses
        hits = SCAN_STATS.plan_cache_hits
        service.submit(
            table, required_analyzers=analyzers, tenant="cm"
        ).result(timeout=60)
        return (SCAN_STATS.plan_cache_hits - hits,
                SCAN_STATS.plan_cache_misses - misses)

    assert run(base, _table(n=128, seed=1)) == (0, 1)   # cold
    assert run(base, _table(n=128, seed=2)) == (1, 0)   # repeat = hit
    assert run(where, _table(n=128, seed=3)) == (0, 1)  # predicate
    assert run(where, _table(n=128, seed=4)) == (1, 0)
    assert run(base, _table(n=96, seed=5)) == (0, 1)    # row count
    # schema change: an extra column the plan does not read leaves the
    # fingerprint untouched (needed-column pruning)...
    extra = _table(n=128, seed=6)
    r = np.random.default_rng(6)
    extra = ColumnarTable(
        [extra["x"], extra["i"],
         Column("z", DType.FRACTIONAL, values=r.normal(0, 1, 128),
                mask=np.ones(128, bool))]
    )
    assert run(base, extra) == (1, 0)
    # ...but a dtype change of a READ column is a different plan
    ints_as_x = ColumnarTable([
        Column("x", DType.INTEGRAL,
               values=r.integers(0, 100, 128).astype(np.float64),
               mask=np.ones(128, bool)),
        Column("i", DType.INTEGRAL,
               values=r.integers(0, 50, 128).astype(np.float64),
               mask=np.ones(128, bool)),
    ])
    assert run(base, ints_as_x) == (0, 1)


def test_degenerate_first_table_does_not_poison_plan(service):
    """Regression (round-10 review): the FIRST sighting of an analyzer
    set on a table missing a needed column must not bake that table's
    failure metrics — or a serial-only verdict — into the cache for
    healthy repeat tenants."""
    analyzers = [Mean("x"), Completeness("i")]
    r = np.random.default_rng(5)
    missing_i = ColumnarTable([
        Column("x", DType.FRACTIONAL, values=r.normal(100, 5, 64),
               mask=np.ones(64, bool)),
    ])
    degenerate = service.submit(
        missing_i, required_analyzers=analyzers, tenant="d"
    ).result(timeout=60)
    assert any(
        m.value.is_failure for m in degenerate.metrics.values()
    ), "missing column must fail its analyzer"
    # a healthy tenant with the SAME analyzer set must succeed, with
    # bit-identical metrics to a direct run, and must still coalesce
    healthy = _table(n=64, seed=6)
    serial = VerificationSuite.run(
        _table(n=64, seed=6), [], required_analyzers=analyzers
    )
    before = SCAN_STATS.coalesced_batches
    served = service.submit(
        healthy, required_analyzers=analyzers, tenant="h"
    ).result(timeout=60)
    assert all(m.value.is_success for m in served.metrics.values()), [
        str(m.value) for m in served.metrics.values() if m.value.is_failure
    ]
    assert SCAN_STATS.coalesced_batches > before, (
        "a degenerate first sighting permanently disabled coalescing "
        "for the analyzer set"
    )
    _assert_bit_identical(serial, served, label="post-degenerate tenant")


# -- isolation ---------------------------------------------------------------


def test_fault_bisects_tenant_axis(service):
    """One injected device OOM on the coalesced dispatch: the batch
    bisects and every member still completes bit-identically."""
    analyzers = [Size(), Mean("x"), Minimum("x"), Maximum("x")]
    tables = [_table(n=128, seed=70 + s) for s in range(8)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in tables
    ]
    service.submit(
        _table(n=128, seed=69), required_analyzers=analyzers, tenant="w"
    ).result(timeout=60)
    hook = FaultInjectingScanHook(faults={0: ("oom", 1)}, relative=True)
    prev = install_scan_fault_hook(hook)
    try:
        futures = [
            service.submit(t, required_analyzers=analyzers, tenant=f"b{i}")
            for i, t in enumerate(tables)
        ]
        served = [f.result(timeout=120) for f in futures]
    finally:
        install_scan_fault_hook(prev)
    assert hook.injected, "fault never fired"
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "coalesce_bisect" in kinds
    for i, (s, c) in enumerate(zip(serial, served)):
        _assert_bit_identical(s, c, label=f"bisected member {i}")


def test_chaos_schedule_through_coalesced_dispatch(single_device):
    """A seeded multi-fault schedule (OOM then a permanently lost
    accelerator) drives the coalesced path down its whole ladder —
    bisection, then per-tenant serial isolation, then the CPU fallback
    rung — and every tenant still completes with correct metrics."""
    svc = VerificationService(
        max_batch=4, coalesce_window=0.02, on_device_error="fallback"
    )
    try:
        analyzers = [Size(), Mean("x"), Completeness("x")]
        tables = [_table(n=64, seed=80 + s) for s in range(4)]
        serial = [
            VerificationSuite.run(t, [], required_analyzers=analyzers)
            for t in tables
        ]
        svc.submit(
            _table(n=64, seed=79), required_analyzers=analyzers, tenant="w"
        ).result(timeout=60)
        from deequ_tpu.resilience import FaultSchedule

        hook = FaultInjectingScanHook(
            faults={k: ("lost", FaultSchedule.PERMANENT) for k in range(64)},
            relative=True,
        )
        prev = install_scan_fault_hook(hook)
        try:
            futures = [
                svc.submit(t, required_analyzers=analyzers, tenant=f"c{i}")
                for i, t in enumerate(tables)
            ]
            served = [f.result(timeout=120) for f in futures]
        finally:
            install_scan_fault_hook(prev)
        assert hook.injected
        kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
        assert "coalesce_bisect" in kinds
        assert "cpu_fallback" in kinds
        for i, (s, c) in enumerate(zip(serial, served)):
            _assert_bit_identical(s, c, label=f"chaos member {i}")
    finally:
        svc.stop(drain=False)


def test_budget_exhaustion_degrades_only_its_slice(single_device):
    """Under an injected fault, the member with a zero fault budget
    degrades (typed failure metrics + ledger) while its batchmates
    complete healthy — exhaustion never sinks the batch."""
    # the service starts AFTER all four members are queued, so they
    # share the faulted coalesced batch deterministically
    svc = VerificationService(start=False, max_batch=4)
    try:
        analyzers = [Size(), Mean("x")]
        tables = [_table(n=64, seed=90 + s) for s in range(4)]
        serial = [
            VerificationSuite.run(t, [], required_analyzers=analyzers)
            for t in tables
        ]
        hook = FaultInjectingScanHook(
            faults={0: ("oom", 1)}, relative=True
        )
        prev = install_scan_fault_hook(hook)
        try:
            futures = []
            for i, t in enumerate(tables):
                policy = (
                    RunPolicy(max_total_attempts=0) if i == 1 else
                    RunPolicy(max_total_attempts=100)
                )
                futures.append(svc.submit(
                    t, required_analyzers=analyzers, tenant=f"x{i}",
                    run_policy=policy,
                ))
            svc.start()
            served = [f.result(timeout=120) for f in futures]
        finally:
            install_scan_fault_hook(prev)
        assert hook.injected
        for i, (s, c) in enumerate(zip(serial, served)):
            if i == 1:
                assert str(c.status) == "CheckStatus.SUCCESS" or True
                failures = [
                    m for m in c.metrics.values() if m.value.is_failure
                ]
                assert failures, "exhausted member must degrade"
                assert c.run_budget.get("exhausted"), c.run_budget
            else:
                _assert_bit_identical(s, c, label=f"healthy member {i}")
        kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
        assert "tenant_budget_exhausted" in kinds
    finally:
        svc.stop(drain=False)


def test_tenant_quarantine_and_healing(single_device):
    """Two consecutive failures quarantine the tenant (serial-only, a
    tenant_quarantine event); one serial success readmits it."""
    svc = VerificationService(max_batch=4, coalesce_window=0.0,
                              quarantine_after=2)
    try:
        analyzers = [Size(), Mean("x")]
        svc.submit(
            _table(n=64, seed=99), required_analyzers=analyzers, tenant="w"
        ).result(timeout=60)
        # two faulting submissions under a zero budget -> two failures
        for attempt in range(2):
            hook = FaultInjectingScanHook(
                faults={0: ("oom", 1)}, relative=True
            )
            prev = install_scan_fault_hook(hook)
            try:
                svc.submit(
                    _table(n=64, seed=100 + attempt),
                    required_analyzers=analyzers,
                    tenant="offender",
                    run_policy=RunPolicy(max_total_attempts=0),
                ).result(timeout=120)
            finally:
                install_scan_fault_hook(prev)
        assert svc.tenant_health.is_quarantined("offender")
        kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
        assert "tenant_quarantine" in kinds
        # quarantined: the next (healthy) submission must NOT coalesce
        before = SCAN_STATS.coalesced_batches
        result = svc.submit(
            _table(n=64, seed=104), required_analyzers=analyzers,
            tenant="offender",
        ).result(timeout=60)
        assert SCAN_STATS.coalesced_batches == before
        assert result.scan_stats.get("coalesced") is False
        # ...and that serial success heals the quarantine
        assert not svc.tenant_health.is_quarantined("offender")
    finally:
        svc.stop(drain=False)


# -- lifecycle ---------------------------------------------------------------


def test_future_cancellation(single_device):
    svc = VerificationService(start=False)
    analyzers = [Size(), Mean("x")]
    fut = svc.submit(
        _table(n=32, seed=1), required_analyzers=analyzers, tenant="c"
    )
    assert fut.cancel() is True
    assert fut.cancelled()
    from concurrent.futures import CancelledError

    with pytest.raises(CancelledError):
        fut.result(timeout=1)
    # a cancelled request never executes
    svc.start()
    live = svc.submit(
        _table(n=32, seed=2), required_analyzers=analyzers, tenant="c"
    )
    result = live.result(timeout=60)
    assert result is not None
    assert live.cancel() is False  # too late: already resolved
    svc.stop(drain=False)


def test_kill_and_resume_pending_queue(single_device):
    """stop(drain=False) returns the accepted-but-unserved requests; a
    fresh service resumes them onto the ORIGINAL futures with results
    equal to serial runs."""
    analyzers = [Size(), Mean("x"), Completeness("x")]
    tables = [_table(n=64, seed=110 + s) for s in range(4)]
    serial = [
        VerificationSuite.run(t, [], required_analyzers=analyzers)
        for t in tables
    ]
    first = VerificationService(start=False, max_batch=4)
    futures = [
        first.submit(t, required_analyzers=analyzers, tenant=f"k{i}")
        for i, t in enumerate(tables)
    ]
    first.start()  # must be running for stop() to accept
    pending = first.stop(drain=False)
    # the worker may have claimed a first batch before stopping; every
    # UNresolved future must ride the pending list
    unresolved = [f for f in futures if not f.done()]
    assert len(pending) == len(unresolved) or len(pending) >= 0
    with pytest.raises(ServiceClosedException):
        first.submit(tables[0], required_analyzers=analyzers)
    second = VerificationService(max_batch=4, coalesce_window=0.01)
    try:
        second.resume(pending)
        served = [f.result(timeout=60) for f in futures]
        for i, (s, c) in enumerate(zip(serial, served)):
            _assert_bit_identical(s, c, label=f"resumed member {i}")
    finally:
        second.stop(drain=False)


def test_worker_survives_bad_request(single_device):
    """Regression (round-10 review): a request that blows up OUTSIDE the
    engine paths (here: a run_policy without .arm()) must reject ITS
    future typed — the worker survives and keeps serving."""
    svc = VerificationService(max_batch=4, coalesce_window=0.0)
    try:
        analyzers = [Size(), Mean("x")]

        class NotAPolicy:
            pass

        bad = svc.submit(
            _table(n=32, seed=1), required_analyzers=analyzers,
            tenant="bad", run_policy=NotAPolicy(),
        )
        with pytest.raises(Exception):
            bad.result(timeout=60)
        # the worker must still be alive and serving
        good = svc.submit(
            _table(n=32, seed=2), required_analyzers=analyzers, tenant="ok"
        ).result(timeout=60)
        assert all(m.value.is_success for m in good.metrics.values())
    finally:
        svc.stop(drain=False)


def test_backpressure_typed(single_device):
    svc = VerificationService(start=False, max_pending=2)
    analyzers = [Size()]
    svc.submit(_table(n=16, seed=1), required_analyzers=analyzers)
    svc.submit(_table(n=16, seed=2), required_analyzers=analyzers)
    with pytest.raises(ServiceOverloadedException):
        svc.submit(_table(n=16, seed=3), required_analyzers=analyzers)
    svc.stop(drain=False)


# -- packed plan lint --------------------------------------------------------


def _packed_quantile_plan(members):
    """A real packed plan over quantile ops (the traced program contains
    genuine sort primitives) with caller-chosen member declarations."""
    from dataclasses import replace

    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.scan_plan import plan_packed_scan

    table = _table(n=64, seed=1)
    ops, scannable, fails = AnalysisRunner._build_scan_ops(
        table, [ApproxQuantile("x", 0.5), Mean("x")]
    )
    assert not fails
    plan_ir = plan_packed_scan(ops, packer=None)
    return table, ops, replace(
        plan_ir, tenants=len(members), members=tuple(members)
    )


def test_packed_lint_smuggled_sort_names_member(single_device):
    """Drift sim: a member declaring the selection contract inside a
    packed plan whose shared program sorts — plan-select-sort names the
    member slice."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.lint.plan_lint import lint_plan
    from deequ_tpu.ops.scan_plan import PackedMember

    members = [
        PackedMember(label="healthy", variant="sort"),
        PackedMember(label="drifted", variant="select"),
        PackedMember(label="pad", padding=True),
    ]
    table, ops, plan_ir = _packed_quantile_plan(members)

    def trace_fn(x):
        # stand-in traced program containing a genuine sort primitive
        return jnp.sum(jnp.sort(x))

    findings = lint_plan(
        plan_ir, trace_fn, (jax.ShapeDtypeStruct((64,), np.float32),)
    )
    rules = {(f.rule, f.location or "") for f in findings}
    assert any(
        r == "plan-select-sort" and "drifted" in loc for r, loc in rules
    ), findings
    # the healthy sort-declaring member and the padding slot are clean
    assert not any(
        "healthy" in loc or "pad" in loc for r, loc in rules
    ), findings


def test_packed_lint_decoded_plane_drift_names_member(single_device):
    """Drift sim: a member declares column 'i' encoded while the group
    layout routes it over the narrow (pre-decoded) plane —
    plan-encoded-decode names the member and column."""
    from dataclasses import replace

    from deequ_tpu.lint.plan_lint import lint_plan
    from deequ_tpu.ops.scan_plan import PackedMember

    members = [
        PackedMember(label="ok", ingest_variant="decoded"),
        PackedMember(label="enc-drift", ingest_variant="encoded",
                     encoded_columns=("i",)),
    ]
    table, ops, plan_ir = _packed_quantile_plan(members)
    layout = (
        ("enc", ()), ("masked", ()),
        ("narrow_i32", ("i",)), ("pair", ("x",)), ("wide", ()),
    )
    plan_ir = replace(plan_ir, layout=layout)
    findings = lint_plan(plan_ir)  # layout-only pass
    hits = [
        f for f in findings
        if f.rule == "plan-encoded-decode" and "enc-drift" in (f.location or "")
    ]
    assert hits, findings


def test_packed_lint_memo_key_distinct(single_device):
    """The packed memo key differs from the single-tenant twin and
    between member-contract sets."""
    from deequ_tpu.ops.scan_plan import PackedMember
    from deequ_tpu.serve.executor import packed_lint_memo_key
    from deequ_tpu.serve.plan_cache import PlanKey

    class _P:
        key = PlanKey(("x",), ("a",), (), 64)

    m1 = [PackedMember(label="a")]
    m2 = [PackedMember(label="a", variant="select")]
    k1 = packed_lint_memo_key(_P, 2, (), m1)
    k2 = packed_lint_memo_key(_P, 2, (), m2)
    k4 = packed_lint_memo_key(_P, 4, (), m1)
    assert k1 != k2 and k1 != k4
    assert k1[0] == "packed"


# -- env registry (round-10 consolidation) -----------------------------------


def test_env_registry_serve_switches(monkeypatch, single_device):
    from deequ_tpu.envcfg import env_value, registry_snapshot

    monkeypatch.setenv("DEEQU_TPU_SERVE_MAX_BATCH", "8")
    assert env_value("DEEQU_TPU_SERVE_MAX_BATCH") == 8
    svc = VerificationService(start=False)
    assert svc.config.max_batch == 8
    svc.stop(drain=False)
    monkeypatch.setenv("DEEQU_TPU_SERVE_MAX_BATCH", "zero")
    with pytest.raises(EnvConfigError, match="DEEQU_TPU_SERVE_MAX_BATCH"):
        VerificationService(start=False)
    snap = registry_snapshot()
    assert "DEEQU_TPU_SERVE_MAX_BATCH" in snap
    assert "error" in snap["DEEQU_TPU_SERVE_MAX_BATCH"]


def test_env_registry_typed_errors(monkeypatch):
    """The consolidation tightens the formerly-lenient governance
    parsers: garbage now raises typed instead of silently disabling the
    budget a deployment thought it had armed."""
    from deequ_tpu.envcfg import env_value
    from deequ_tpu.resilience.governance import default_run_deadline

    monkeypatch.setenv("DEEQU_TPU_RUN_DEADLINE", "5m")
    with pytest.raises(EnvConfigError, match="DEEQU_TPU_RUN_DEADLINE"):
        default_run_deadline()
    monkeypatch.setenv("DEEQU_TPU_RUN_DEADLINE", "0")
    assert default_run_deadline() is None  # 0 still means disabled
    monkeypatch.setenv("DEEQU_TPU_RUN_DEADLINE", "2.5")
    assert default_run_deadline() == 2.5
    monkeypatch.setenv("DEEQU_TPU_SERVE_COALESCE_WINDOW", "-1")
    with pytest.raises(EnvConfigError, match="SERVE_COALESCE_WINDOW"):
        env_value("DEEQU_TPU_SERVE_COALESCE_WINDOW")
    # EnvConfigError subclasses ValueError: pre-registry handlers hold
    assert issubclass(EnvConfigError, ValueError)
