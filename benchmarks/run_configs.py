"""BASELINE.md benchmark configs 1-5, runnable at scaled sizes.

Each config prints one JSON line: {"config", "metric", "rows", "value",
"unit", "wall_seconds", ...}. Row counts default to small sizes; pass
--rows to scale up. Config 2 is bench.py (the driver headline).

Usage:
    python benchmarks/run_configs.py --config 1
    python benchmarks/run_configs.py --all
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# sibling benchmark modules (config_scale_proof's deterministic sources)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))


def _emit(**kwargs):
    print(json.dumps(kwargs), flush=True)
    return kwargs


def _fetch_floor_seconds() -> float:
    """One trivial dispatch+fetch round trip — the hard latency floor any
    single scan pays on this host<->device link (measured the same way
    as bench.py)."""
    import jax
    import jax.numpy as jnp

    probe = jax.jit(lambda a: a * 2.0)
    arg = jnp.ones((8,), jnp.float32)
    np.asarray(probe(arg))  # compile
    t0 = time.time()
    np.asarray(probe(arg))
    return time.time() - t0


def _floor_telemetry(wall: float) -> dict:
    """Floor-normalized fields for the parsed JSON: runs compare engine
    work (compute above the fetch floor, bytes shipped over the
    host<->device link) instead of the link's own floor.
    Call AFTER the timed section; the caller resets SCAN_STATS at t0."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    floor = _fetch_floor_seconds()
    snap = SCAN_STATS.snapshot()
    return {
        "fetch_floor_ms": round(floor * 1000, 2),
        "compute_above_floor_ms": round(max(wall - floor, 0.0) * 1000, 2),
        # link traffic both ways: host->device packing + device->host
        # result fetches (resident configs ship ~only fetches)
        "bytes_shipped": int(snap["bytes_packed"]) + int(snap["bytes_fetched"]),
    }


def config1():
    """VerificationSuite {Size, Completeness, Uniqueness} on titanic.csv."""
    from deequ_tpu import Check, CheckLevel, VerificationSuite
    from deequ_tpu.data.io import read_csv

    path = "/root/reference/test-data/titanic.csv"
    table = read_csv(path)
    check = (
        Check(CheckLevel.ERROR, "titanic integrity")
        .has_size(lambda n: n == 891)
        .is_complete("PassengerId")
        .has_completeness("Age", lambda c: c > 0.7)
        .has_uniqueness(("PassengerId",), lambda u: u == 1.0)
    )
    suite = VerificationSuite().on_data(table).add_check(check)
    suite.run()  # warmup/compile
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.reset()
    t0 = time.time()
    result = suite.run()
    wall = time.time() - t0
    assert str(result.status).endswith("SUCCESS"), result.status
    return _emit(
        config=1, metric="titanic_verification_wall", rows=table.num_rows,
        value=round(wall, 4), unit="seconds", wall_seconds=round(wall, 4),
        **_floor_telemetry(wall),
    )


def config6(n_tenants: int):
    """SERVING config (round 10, deequ_tpu/serve): the config-1 shape at
    fleet scale — an ``n_tenants`` open-loop load of small suites served
    through the VerificationService's compiled-plan cache + request
    coalescer. ONE workload definition, shared with bench.py's
    ``measure_serving_load`` probe (which hard-asserts bit-identity vs
    serial, the repeat-tenant zero-trace contract, one fetch per
    coalesced batch, and the >=5x sustained-throughput gate before it
    reports anything) — the suites/sec row lands next to rows/sec."""
    import bench

    probe = bench.measure_serving_load(n_tenants)
    return _emit(
        config=6, metric="serving_suites_per_sec", tenants=n_tenants,
        value=probe["serving_suites_per_sec"], unit="suites/sec",
        **{k: v for k, v in probe.items() if k != "serving_suites_per_sec"},
    )


def config7(n_tenants: int):
    """FLEET config (round 12, deequ_tpu/serve/fleet.py): the config-6
    load routed over 4 serving workers by consistent hash, plus a
    scripted mid-load worker death. ONE workload definition, shared with
    bench.py's ``measure_fleet_failover`` probe, which hard-asserts —
    before it reports anything — that the death re-dispatches exactly
    the dead worker's accepted requests, every result (re-dispatched
    included) is bit-identical to the healthy serial run, every accepted
    future resolves exactly once (chaos oracle 8), and throughput
    scales near-linearly vs one worker (a gate that arms itself only on
    >= 4-device hardware; on a shared-device container it banks the
    measured ratio as ``pending-parallel-hw`` and gates on
    no-collapse >= 0.5x instead — the config-3 banked-acceptance
    idiom)."""
    import bench

    probe = bench.measure_fleet_failover(n_tenants)
    return _emit(
        config=7, metric="fleet_suites_per_sec", tenants=n_tenants,
        value=probe["fleet_suites_per_sec"], unit="suites/sec",
        **{k: v for k, v in probe.items() if k != "fleet_suites_per_sec"},
    )


def config8(n_tenants: int):
    """REPOSITORY config (round 13, deequ_tpu/repository): an
    ``n_tenants x 32``-date columnar metric history with the online
    QualityMonitor watching one series, then the cross-tenant aggregate
    query compiled onto the engine's fused-scan path vs the loader-side
    decode baseline. ONE workload definition, shared with bench.py's
    ``measure_repository_query`` probe, which hard-asserts — before it
    reports anything — bit-identity between the two paths, the
    one-fetch-per-scan contract on the compiled query, the >= 2x
    encoded staged-byte reduction, O(result) append cost across the
    load, and exactly one online alert for the scripted spike. The
    emitted row carries the obs read-through of the ``repository``
    registry section (saves, segments, query passes, alerts)."""
    import bench

    probe = bench.measure_repository_query(n_tenants)
    return _emit(
        config=8, metric="repository_query_speedup_x", tenants=n_tenants,
        value=probe["repository_query_speedup_x"], unit="x vs loader-side",
        **{
            k: v for k, v in probe.items()
            if k != "repository_query_speedup_x"
        },
    )


def config9():
    """KERNEL-VARIANT config (round 14, ops/histogram_device.py): the
    histogram/segment-fold kernel tier A/B — XLA scatter vs the blocked
    one-hot matmul (vs pallas interpret for correctness) on standalone
    bincount shapes PLUS the resident quantile scan forced through each
    variant. ONE workload definition, shared with bench.py's
    ``measure_kernel_ab`` probe, which hard-asserts — before it reports
    anything — bit-exact counts vs np.bincount on every shape, plan
    lint CLEAN in error mode per variant (the plan-hist-scatter rule at
    zero findings), scan bit-identity + zero-sort + one-fetch under
    each forced variant, no default-policy regression vs the scatter
    baseline, and >=1.2x on at least one shape on this container; the
    chip-side >=2x acceptance records live on accelerator backends and
    banks as ``pending-parallel-hw`` on CPU-only sessions (the
    config-3 banked-acceptance idiom)."""
    import bench

    probe = bench.measure_kernel_ab()
    return _emit(
        config=9, metric="kernel_ab_speedup_max",
        value=probe["kernel_ab_speedup_max"], unit="x vs scatter",
        **{k: v for k, v in probe.items() if k != "kernel_ab_speedup_max"},
    )


def config10(n_submissions: int):
    """OVERLOAD config (round 15, deequ_tpu/serve/admission.py): the
    config-7 fleet under paced open-loop load — ~0.5x then ~2x its own
    measured unloaded capacity — with every submission carrying an SLO
    class. ONE workload definition, shared with bench.py's
    ``measure_overload_shedding`` probe, which hard-asserts — before it
    reports anything — zero sheds at <= 0.5x load, zero critical sheds
    + critical p99 within its SLO under 2x, typed best_effort sheds,
    goodput >= 0.8x unloaded capacity, bit-identity of every completed
    result vs the unloaded serial run, and a clean 4-seed chaos
    ``load``-seam quick-soak (exactly-once incl. typed sheds, no
    priority inversion)."""
    import bench

    probe = bench.measure_overload_shedding(n_submissions)
    return _emit(
        config=10, metric="overload_goodput_frac",
        submissions=n_submissions,
        value=probe["overload_goodput_frac"], unit="x vs unloaded",
        **{k: v for k, v in probe.items() if k != "overload_goodput_frac"},
    )


def config11(n_windows: int):
    """CONTROL-PLANE config (round 16, deequ_tpu/control): a cold
    tenant driven through the closed quality loop — serving-backed
    profiling, recorded history, constraint suggestion, best_effort
    shadow evaluation, anomaly-gated promotion — until its first
    enforcing check set, with verification traffic sharing the
    service. ONE workload definition, shared with bench.py's
    ``measure_suggestion_loop`` probe, which hard-asserts — before it
    reports anything — profile passes coalescing under the
    one-fetch-per-batch contract (fetches == batches with traffic in
    the mix), repeat profiles of a warm tenant shape at zero compiled
    programs + zero plan-lint traces, the shadow-class flood shedding
    TYPED without ever shedding (or degrading) a critical request, and
    the whole check set re-minting bit-identically from the recorded
    profile history alone."""
    import bench

    probe = bench.measure_suggestion_loop(n_windows)
    return _emit(
        config=11, metric="suggestion_windows_to_enforcing",
        value=probe["suggestion_windows_to_enforcing"], unit="windows",
        **{
            k: v for k, v in probe.items()
            if k != "suggestion_windows_to_enforcing"
        },
    )


def config12(n_rows: int):
    """PLAN-OPTIMIZER config (round 19, ops/segment
    ``fused_group_counts`` + serve/plan_cache ``SUBPLAN_CACHE`` +
    ops/plan_cost): a 3-grouping-pass suite A/B fused vs
    ``DEEQU_TPU_PLAN_FUSION=0``, an overlapping-tenant mix of permuted
    suites through the service, and the cost-priced admission check.
    ONE workload definition, shared with bench.py's
    ``measure_plan_fusion`` probe, which hard-asserts — before it
    reports anything — ONE dispatch + fewer fetches + bit-identity for
    the fused 3-pass suite, sub-plan sharing raising cache
    effectiveness above exact-key hits alone (every permuted suite
    misses its exact key yet builds zero programs), and retry_after_s
    ordering by predicted queued cost at equal queue depth."""
    import bench

    probe = bench.measure_plan_fusion(n_rows)
    return _emit(
        config=12, metric="plan_fusion_dispatch_reduction_x",
        rows=n_rows,
        value=probe["plan_fusion_dispatch_reduction_x"], unit="x dispatches",
        **{
            k: v for k, v in probe.items()
            if k != "plan_fusion_dispatch_reduction_x"
        },
    )


def config13(n_streams: int):
    """WINDOWED-VERIFICATION config (round 20, deequ_tpu/windows: the
    window fold axis + watermark close protocol): a ~1k-stream
    SLO-classed tenant fleet of tumbling event-time windows driven
    batch-by-batch under a RAISED overload level, plus a sliding
    4-open-pane stream, sampled one-shot references, and a scripted
    double kill-and-resume. ONE workload definition, shared with
    bench.py's ``measure_windowed_stream`` probe, which hard-asserts —
    before it reports anything — exactly ONE device dispatch per
    stream-batch (pane count notwithstanding), a program cache bounded
    by pane-bucket shapes rather than stream count, per-window
    bit-identity vs one-shot VerificationSuite runs, close-batch p99
    under the 250ms SLO with ZERO sheds for on-time closes (critical
    included), and exactly-once alert delivery through the double
    resume."""
    import bench

    probe = bench.measure_windowed_stream(n_streams)
    return _emit(
        config=13, metric="wstream_closes_per_sec",
        rows=n_streams,
        value=probe["wstream_closes_per_sec"], unit="closes/sec",
        **{k: v for k, v in probe.items() if k != "wstream_closes_per_sec"},
    )


def config3_workload(n_rows: int, n_cols: int = 50):
    """(table, analyzers) for the config-3 shape — 25 correlations + 50
    median columns over correlated normals. ONE definition shared by
    ``config3`` below and bench.py's ``measure_config3_selection`` probe
    so the probe can never drift from the reported config."""
    from deequ_tpu.analyzers import ApproxQuantile, Correlation
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(42)
    base = rng.normal(0, 1, n_rows)
    cols = [
        Column(
            f"c{i}", DType.FRACTIONAL,
            values=base * (0.5 + 0.01 * i) + rng.normal(0, 1, n_rows),
        )
        for i in range(n_cols)
    ]
    table = ColumnarTable(cols)
    analyzers = [Correlation(f"c{2*i}", f"c{2*i+1}") for i in range(n_cols // 2)]
    analyzers += [ApproxQuantile(f"c{i}", 0.5) for i in range(n_cols)]
    return table, analyzers


def enforce_config3_contract(
    snap: dict, resident: bool, select_enabled=None
) -> bool:
    """The PR-6 zero-sort contract, in ONE place for every config-3
    harness (this module and bench.py's probe): on a resident run with
    the selection kernel enabled and the default pair-plane layout, the
    recorded ScanStats must show zero device sort passes and at least
    one selection pass — otherwise the harness REFUSES to report config
    3 (AssertionError), like PR 4's one-fetch assert. Returns True when
    the contract bound (and held), False when it legitimately does not
    apply (non-resident or kernel disabled).

    ``select_enabled``: the RESOLVED kernel switch of the run the
    snapshot came from; pass it whenever the run pinned the kernel
    programmatically (``run_scan(select_kernel=...)`` or a scoped env) —
    defaulting to the ambient env here could silently skip the assert
    for exactly the run it should bind on."""
    from deequ_tpu.ops.scan_plan import select_kernel_enabled

    if select_enabled is None:
        select_enabled = select_kernel_enabled()
    if not (resident and select_enabled):
        return False
    assert snap["device_sort_passes"] == 0, (
        "config-3 contract violation: resident selection path ran "
        f"{snap['device_sort_passes']} device sort passes — refusing "
        "to report config 3"
    )
    assert snap["device_select_passes"] > 0, (
        "config-3 contract violation: selection kernel never ran on the "
        "resident path — refusing to report config 3"
    )
    return True


def config3(n_rows: int):
    """Correlation + ApproxQuantile(KLL) over 50 numeric columns."""
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table, analyzers = config3_workload(n_rows)

    # the timed quantity is the steady-state RESIDENT scan (persist is the
    # untimed df.cache() analogue): once resident, a same-table warmup is
    # fair because no bytes move during timed runs. If persist fails
    # (table exceeds the HBM budget) the non-resident path runs COLD
    # (compile + transfer included) and the emitted record says so.
    try:
        table.persist()
    except MemoryError:
        pass
    if table.is_persisted:
        AnalysisRunner.do_analysis_run(table, analyzers)
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.reset()
    t0 = time.time()
    ctx = AnalysisRunner.do_analysis_run(table, analyzers)
    wall = time.time() - t0
    failed = [a for a, m in ctx.metric_map.items() if m.value.is_failure]
    assert not failed, failed[:3]
    snap = SCAN_STATS.snapshot()
    enforce_config3_contract(snap, table.is_persisted)
    return _emit(
        config=3, metric="corr_kll_50col_rows_per_sec", rows=n_rows,
        value=round(n_rows / wall, 1), unit="rows/sec",
        wall_seconds=round(wall, 3), resident=table.is_persisted,
        device_sort_passes=snap["device_sort_passes"],
        device_select_passes=snap["device_select_passes"],
        **_floor_telemetry(wall),
    )


def config4(n_rows: int):
    """ApproxCountDistinct + Histogram + Uniqueness on high-cardinality
    dictionary-encoded strings."""
    from deequ_tpu.analyzers import ApproxCountDistinct, Histogram, Uniqueness
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(43)
    cardinality = max(n_rows // 3, 1)
    codes = rng.integers(0, cardinality, n_rows).astype(np.int32)
    dictionary = np.array([f"id_{i:09d}" for i in range(cardinality)], dtype=object)
    table = ColumnarTable(
        [Column("key", DType.STRING, codes=codes, dictionary=dictionary)]
    )
    analyzers = [
        ApproxCountDistinct("key"), Histogram("key"), Uniqueness(("key",)),
    ]
    # timed runs are HBM-resident when possible; cold otherwise (see
    # config3 comment on the content-dedup hazard)
    try:
        table.persist()
    except MemoryError:
        pass
    if table.is_persisted:
        AnalysisRunner.do_analysis_run(table, analyzers)
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.reset()
    t0 = time.time()
    ctx = AnalysisRunner.do_analysis_run(table, analyzers)
    wall = time.time() - t0
    failed = [a for a, m in ctx.metric_map.items() if m.value.is_failure]
    assert not failed, failed[:3]
    acd = ctx.metric_map[analyzers[0]].value.get()
    distinct = len(np.unique(codes))
    assert abs(acd - distinct) / distinct < 0.15, (acd, distinct)
    return _emit(
        config=4, metric="hll_histogram_highcard_rows_per_sec", rows=n_rows,
        value=round(n_rows / wall, 1), unit="rows/sec",
        wall_seconds=round(wall, 3), resident=table.is_persisted,
        **_floor_telemetry(wall),
    )


def config5_from_disk(n_batches: int, batch_rows: int, tmpdir: str = "/tmp"):
    """Config #5 with batches arriving FROM DISK (Parquet): the incremental
    monitoring loop reads each day's delta out-of-core via stream_parquet,
    merges into running states, and never materializes more than a batch —
    the spec-scale (1B rows / 100 batches) shape, scaled to this host."""
    import os
    import shutil

    from deequ_tpu.analyzers import Mean, Size, StandardDeviation
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.io import stream_parquet, write_parquet
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.repository import AnalysisResult, ResultKey
    from deequ_tpu.repository.memory import InMemoryMetricsRepository
    from deequ_tpu.states import InMemoryStateProvider

    import tempfile

    workdir = tempfile.mkdtemp(prefix="deequ_cfg5_", dir=tmpdir)
    try:
        rng = np.random.default_rng(44)
        paths = []
        for b in range(n_batches):
            path = os.path.join(workdir, f"batch_{b:04d}.parquet")
            write_parquet(
                ColumnarTable(
                    [Column("v", DType.FRACTIONAL,
                            values=rng.normal(100.0, 5.0, batch_rows))]
                ),
                path,
            )
            paths.append(path)

        analyzers = [Size(), Mean("v"), StandardDeviation("v")]
        repo = InMemoryMetricsRepository()
        states = InMemoryStateProvider()
        from deequ_tpu.ops.scan_engine import SCAN_STATS

        SCAN_STATS.reset()
        t0 = time.time()
        for b, path in enumerate(paths):
            ctx = AnalysisRunner.do_analysis_run(
                stream_parquet(path), analyzers,
                aggregate_with=states, save_states_with=states,
            )
            repo.save(AnalysisResult(ResultKey(b, {"stream": "disk"}), ctx))
        wall = time.time() - t0
        total = n_batches * batch_rows
        final = repo.load_by_key(ResultKey(n_batches - 1, {"stream": "disk"}))
        size = final.analyzer_context.metric_map[Size()].value.get()
        assert size == total, (size, total)
        ingest_snap = SCAN_STATS.snapshot()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return _emit(
        config=5, metric="incremental_disk_stream_rows_per_sec", rows=total,
        value=round(total / wall, 1), unit="rows/sec",
        wall_seconds=round(wall, 3), batches=n_batches,
        # round-8 ingest telemetry: host->device staging ledger of the
        # whole incremental loop (bench.py's measure_ingest_overlap is
        # the contract-asserting probe; these are the observables)
        bytes_staged=ingest_snap["bytes_staged"],
        ingest_overlap_frac=ingest_snap["ingest_overlap_frac"],
        encoded_scan_passes=ingest_snap["encoded_scan_passes"],
        **_floor_telemetry(wall),
    )


def config5(
    n_batches: int,
    batch_rows: int,
    pipelined: bool = True,
    seed: int = 44,
    with_strings: bool = False,
):
    """Incremental state stream + anomaly detection over the repository
    (BASELINE config #5 shape, scaled). ``pipelined`` uses the round-4
    IncrementalAnalysisStream (several batches' scans in flight, drains
    FIFO) — the serial loop pays one full device fetch round trip per
    batch. ``with_strings`` adds a dictionary-encoded string column with
    PatternMatch + MaxLength (the realistic monitoring-stream shape; the
    r5 group path carries dictionary LUTs as stacked jit arguments, so
    the pipeline no longer excludes it)."""
    from deequ_tpu.analyzers import (
        Completeness,
        MaxLength,
        Mean,
        PatternMatch,
        Size,
        StandardDeviation,
    )
    from deequ_tpu.analyzers.incremental import IncrementalAnalysisStream
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.anomaly import AnomalyDetector, OnlineNormalStrategy
    from deequ_tpu.anomaly.history import DataPoint
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.repository import AnalysisResult, ResultKey
    from deequ_tpu.repository.memory import InMemoryMetricsRepository
    from deequ_tpu.states import InMemoryStateProvider

    analyzers = [Size(), Mean("v"), StandardDeviation("v")]
    if with_strings:
        analyzers += [
            Completeness("s"),
            PatternMatch("s", r"^[a-z0-9]+@[a-z.]+$"),
            MaxLength("s"),
        ]
    repo = InMemoryMetricsRepository()
    states = InMemoryStateProvider()
    rng = np.random.default_rng(seed)

    # pre-generate batches: data generation is not part of the measured
    # incremental loop (batches "arrive")
    batches = []
    for b in range(n_batches):
        cols = [
            Column("v", DType.FRACTIONAL,
                   values=rng.normal(100.0, 5.0, batch_rows))
        ]
        if with_strings:
            card = 1000 + 13 * b  # fresh dictionary per batch, like prod
            dic = np.array(
                [
                    f"user{i}@mail.com" if i % 5 else f"bad row {i}"
                    for i in range(card)
                ]
            )
            cols.append(
                Column("s", DType.STRING,
                       codes=rng.integers(0, card, batch_rows).astype(
                           np.int32),
                       dictionary=dic)
            )
        batches.append(ColumnarTable(cols))

    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.reset()
    t0 = time.time()
    if pipelined:
        stream = IncrementalAnalysisStream(
            analyzers, aggregate_with=states, save_states_with=states,
            window=6,
        )
        done = []
        for b, batch in enumerate(batches):
            done.extend(stream.submit(batch, tag=b))
        done.extend(stream.close())
        for b, ctx in done:
            repo.save(AnalysisResult(ResultKey(b, {"stream": "s1"}), ctx))
    else:
        for b, batch in enumerate(batches):
            # merge into running states AND persist the merged result, so
            # each batch updates dataset-level metrics without rescanning
            # history
            ctx = AnalysisRunner.do_analysis_run(
                batch, analyzers,
                aggregate_with=states, save_states_with=states,
            )
            repo.save(AnalysisResult(ResultKey(b, {"stream": "s1"}), ctx))
    wall = time.time() - t0
    ingest_snap = SCAN_STATS.snapshot()

    # anomaly detection over the metric time series
    series = repo.load().with_tag_values({"stream": "s1"}).get()
    means = [
        DataPoint(r.result_key.data_set_date, m.value.get())
        for r in series
        for a, m in r.analyzer_context.metric_map.items()
        if a == Mean("v")
    ]
    detector = AnomalyDetector(OnlineNormalStrategy())
    result = detector.detect_anomalies_in_history(means)
    total = n_batches * batch_rows
    return _emit(
        config=5, metric="incremental_stream_rows_per_sec", rows=total,
        value=round(total / wall, 1), unit="rows/sec",
        wall_seconds=round(wall, 3), batches=n_batches,
        anomalies=len(result.anomalies),
        bytes_staged=ingest_snap["bytes_staged"],
        ingest_overlap_frac=ingest_snap["ingest_overlap_frac"],
        **_floor_telemetry(wall),
    )


def _spill_proof_analyzers():
    from deequ_tpu.analyzers import ApproxCountDistinct, Histogram, Uniqueness

    return [
        ApproxCountDistinct("key"),
        Histogram("key", max_detail_bins=100),
        Uniqueness(("key",)),
    ]


def _spill_proof_metrics(ctx, analyzers) -> dict:
    """Comparable (JSON-stable) projection of the config-4 metrics:
    histogram compares bin count + the full top-N detail, exactly."""
    acd = ctx.metric_map[analyzers[0]].value.get()
    hist = ctx.metric_map[analyzers[1]].value.get()
    uniq = ctx.metric_map[analyzers[2]].value.get()
    return {
        "approx_count_distinct": acd,
        "histogram_bins": hist.number_of_bins,
        "histogram_top": sorted(
            (k, v.absolute) for k, v in hist.values.items()
        ),
        "uniqueness": uniq,
    }


def spill_proof_child(n_rows: int, budget_bytes: int):
    """The budgeted run, in ITS OWN process so ru_maxrss is a clean
    measurement of the spilling path (invoked by spill_proof below)."""
    import resource

    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.streaming import StreamingTable
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.states import InMemoryStateProvider

    from config_scale_proof import string_source

    analyzers = _spill_proof_analyzers()
    source = string_source(
        n_rows, batch_rows=1_000_000, row_offset=0, seed=400,
        global_card=max(n_rows // 3, 1),
    )
    t0 = time.time()
    ctx = AnalysisRunner.do_analysis_run(
        StreamingTable(source), analyzers,
        save_states_with=InMemoryStateProvider(),
        group_memory_budget=budget_bytes,
    )
    wall = time.time() - t0
    out = _spill_proof_metrics(ctx, analyzers)
    out.update(
        wall_seconds=round(wall, 1),
        peak_rss_mb=round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1
        ),
        spill_runs=SCAN_STATS.spill_runs,
        spill_merge_passes=SCAN_STATS.spill_merge_passes,
        spill_bytes_written=SCAN_STATS.spill_bytes_written,
        spill_bytes_read=SCAN_STATS.spill_bytes_read,
        peak_group_state_bytes=SCAN_STATS.peak_group_state_bytes,
    )
    print(json.dumps(out), flush=True)


def spill_proof(n_rows: int, budget_bytes: int, rss_cap_mb: float):
    """The ISSUE-1 acceptance proof: a config-4 shaped high-cardinality
    grouping under a hard group memory budget completes within the RSS
    cap AND produces metrics byte-identical to the unbounded in-RAM path
    (which runs in THIS process, whose RSS is not under test). Wire-in:
    ``python benchmarks/run_configs.py --spill-proof [--rows N]``."""
    import subprocess

    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.streaming import StreamingTable

    from config_scale_proof import string_source

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.run(
        [
            sys.executable, os.path.abspath(__file__),
            "--spill-proof-child", "--rows", str(n_rows),
            "--budget-bytes", str(budget_bytes),
        ],
        capture_output=True, text=True, env=env,
    )
    assert child.returncode == 0, child.stderr[-3000:]
    got = json.loads(child.stdout.strip().splitlines()[-1])
    # echo the child's stats before asserting so a cap failure still
    # records what the budgeted run measured
    print(json.dumps({"spill_proof_child": got}), flush=True)
    assert got["spill_runs"] >= 1, "budget did not force spilling"
    assert got["peak_group_state_bytes"] <= budget_bytes, got
    assert got["peak_rss_mb"] <= rss_cap_mb, (
        f"budgeted run RSS {got['peak_rss_mb']}MB exceeds cap {rss_cap_mb}MB"
    )

    # unbounded in-RAM reference over the IDENTICAL deterministic stream.
    # A process-wide DEEQU_TPU_GROUP_MEMORY_BUDGET would make the
    # reference spill too (spill-vs-spill proves nothing) — strip it;
    # the child got its budget via an explicit --budget-bytes.
    os.environ.pop("DEEQU_TPU_GROUP_MEMORY_BUDGET", None)
    analyzers = _spill_proof_analyzers()
    t0 = time.time()
    ref_ctx = AnalysisRunner.do_analysis_run(
        StreamingTable(string_source(
            n_rows, batch_rows=1_000_000, row_offset=0, seed=400,
            global_card=max(n_rows // 3, 1),
        )),
        analyzers,
    )
    ref_wall = time.time() - t0
    ref = _spill_proof_metrics(ref_ctx, analyzers)
    mismatch = {
        k: (got[k], ref[k])
        for k in ref
        if (got[k] if k != "histogram_top" else [
            tuple(t) for t in got[k]
        ]) != ref[k]
    }
    assert not mismatch, f"spill vs in-RAM metrics differ: {mismatch}"
    return _emit(
        metric="spill_proof_config4_shape", rows=n_rows,
        budget_bytes=budget_bytes, rss_cap_mb=rss_cap_mb,
        value=got["peak_rss_mb"], unit="MB_peak_rss",
        wall_seconds=got["wall_seconds"],
        unbounded_wall_seconds=round(ref_wall, 1),
        spill_runs=got["spill_runs"],
        spill_merge_passes=got["spill_merge_passes"],
        spill_bytes_written=got["spill_bytes_written"],
        spill_bytes_read=got["spill_bytes_read"],
        peak_group_state_bytes=got["peak_group_state_bytes"],
        metrics_byte_identical=True,
        histogram_bins=got["histogram_bins"],
        uniqueness=got["uniqueness"],
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument(
        "--spill-proof", action="store_true",
        help="RSS-budget regression proof: high-cardinality grouping "
        "under a hard budget, metrics byte-identical to in-RAM",
    )
    ap.add_argument("--spill-proof-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--budget-bytes", type=int, default=None)
    ap.add_argument("--rss-cap-mb", type=float, default=2048.0)
    args = ap.parse_args()

    if args.spill_proof_child:
        spill_proof_child(
            args.rows or 4_000_000, args.budget_bytes or (64 << 20)
        )
        return
    if args.spill_proof:
        rows = args.rows or 4_000_000
        # default budget scales with the workload's group state
        # (~180B/group at cardinality rows/3) so small proofs still spill
        budget = args.budget_bytes or max(
            16 << 20, min(int(rows / 3 * 60), 768 << 20)
        )
        spill_proof(rows, budget, args.rss_cap_mb)
        return

    runners = {
        1: lambda: config1(),
        3: lambda: config3(args.rows or 4_000_000),
        4: lambda: config4(args.rows or 4_000_000),
        5: lambda: config5(50, (args.rows or 10_000_000) // 50),
        # config 5 with a string column (PatternMatch/MaxLength): the
        # realistic monitoring stream; LUTs ride the pipelined group path
        55: lambda: config5(
            50, (args.rows or 10_000_000) // 50, with_strings=True
        ),
        # config 5 with batches read out-of-core from Parquet on disk
        # (python benchmarks/run_configs.py --config 50)
        50: lambda: config5_from_disk(20, (args.rows or 10_000_000) // 20),
        # round-10 serving config: 1k-tenant open-loop suite load through
        # the multi-tenant service (plan cache + coalescer), suites/sec
        6: lambda: config6(args.rows or 1000),
        # round-12 fleet config: the routed 4-worker load + scripted
        # worker death (failover bit-identity / exactly-once asserted)
        7: lambda: config7(args.rows or 144),
        # round-13 repository config: columnar metric history, compiled
        # fused-scan query vs loader-side decode (bit-identity /
        # one-fetch / encoded-staging asserted), obs read-through
        8: lambda: config8(args.rows or 48),
        # round-14 kernel-variant config: the histogram tier A/B
        # (scatter vs one-hot matmul vs pallas) with exactness /
        # plan-lint / one-fetch / no-regression gates asserted inside
        9: lambda: config9(),
        # round-15 overload config: the SLO-classed fleet under 0.5x /
        # 2x paced open-loop load (zero-shed-when-unloaded, critical-
        # survives, typed best_effort sheds, goodput, bit-identity, and
        # the chaos load quick-soak asserted inside)
        10: lambda: config10(args.rows or 2400),
        # round-16 control-plane config: the closed suggestion ->
        # shadow -> promotion loop to a cold tenant's first enforcing
        # check set (profile coalescing / repeat zero-trace / shadow-
        # never-sheds-critical / replay reproducibility asserted inside)
        11: lambda: config11(args.rows or 6),
        # round-19 plan-optimizer config: the 3-pass grouping fusion
        # A/B + permuted-suite sub-plan sharing + cost-priced admission
        # (one-dispatch / bit-identity / sharing-beats-exact-hits /
        # cost-ordered-retries gates asserted inside)
        12: lambda: config12(args.rows or (1 << 16)),
        # round-20 windowed-verification config: the ~1k-stream windowed
        # tenant fleet (one-dispatch-per-batch / shared pane programs /
        # bit-identity / p99-close SLO / exactly-once-through-kill gates
        # asserted inside)
        13: lambda: config13(args.rows or 1000),
    }
    if args.all:
        for k in sorted(runners):
            runners[k]()
        print("config 2 is the driver bench: python bench.py", file=sys.stderr)
    elif args.config in runners:
        runners[args.config]()
    elif args.config == 2:
        import bench

        bench.main()
    else:
        ap.error("--config {1,2,3,4,5,6,7,8,9,10,11,12,13} or --all")


if __name__ == "__main__":
    main()
