"""AnalysisRunner — the query planner (reference layer L4,
analyzers/runners/AnalysisRunner.scala).

Planning pipeline, mirroring doAnalysisRun (reference L97-203):

1. skip analyzers whose results already exist in the repository;
2. partition analyzers by failing preconditions -> failure metrics;
3. split {scan-shareable | grouping | own-pass (KLL / quantile / histogram)};
4. fuse ALL scan-shareable analyzers into ONE compiled device pass
   (ops/scan_engine.py — the analogue of the single data.agg(...) job);
5. for each distinct grouping-column set, compute frequencies ONCE and run
   all its analyzers against the shared frequency state;
6. merge contexts, optionally save states / results.

Partial failure is data: a failure inside the fused scan maps onto every
participating analyzer (reference L320-323); precondition failures become
failure metrics instead of aborting (L137-145).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from deequ_tpu.analyzers.base import (
    Analyzer,
    ScanShareableAnalyzer,
    State,
    find_first_failing,
    merge_states,
    metrics_from_states,
)
from deequ_tpu.analyzers.grouping import (
    FrequenciesAndNumRows,
    FrequencyBasedAnalyzer,
    Histogram,
)
from deequ_tpu.analyzers.scan import DataType
from deequ_tpu.analyzers.sketches import ApproxCountDistinct
from deequ_tpu.data.table import ColumnarTable, DType, Schema
from deequ_tpu.exceptions import (
    GroupBudgetIgnoredWarning,
    MetricCalculationRuntimeException,
    PlanLintError,
    ReusingNotPossibleResultsMissingException,  # noqa: F401 — canonical home
    # is the exceptions taxonomy; re-exported here for compatibility (the
    # class was born in this module)
    RunBudgetExhaustedException,
    wrap_if_necessary,
)
from deequ_tpu.metrics import DoubleMetric, Metric
from deequ_tpu.obs.recorder import seam
from deequ_tpu.ops.scan_engine import run_scan


@dataclass
class AnalyzerContext:
    """Result map Analyzer -> Metric (reference AnalyzerContext.scala:29-105).

    ``skipped_batches`` records stream batch indices quarantined by the
    resilient streaming pass (``on_batch_error="skip"``) — skipped data is
    REPORTED, never silently dropped (it surfaces on VerificationResult)."""

    metric_map: Dict[Analyzer, Metric] = field(default_factory=dict)
    skipped_batches: List[int] = field(default_factory=list)

    @staticmethod
    def empty() -> "AnalyzerContext":
        return AnalyzerContext({})

    def all_metrics(self) -> List[Metric]:
        return list(self.metric_map.values())

    def __add__(self, other: "AnalyzerContext") -> "AnalyzerContext":
        merged = dict(self.metric_map)
        merged.update(other.metric_map)
        skipped = list(self.skipped_batches)
        seen = set(skipped)
        skipped += [i for i in other.skipped_batches if i not in seen]
        return AnalyzerContext(merged, skipped)

    def metric(self, analyzer: Analyzer) -> Optional[Metric]:
        return self.metric_map.get(analyzer)

    @staticmethod
    def success_metrics_as_rows(
        analyzer_context: "AnalyzerContext",
        for_analyzers: Optional[Sequence[Analyzer]] = None,
    ) -> List[dict]:
        """Flattened successful metrics as row dicts (DataFrame analogue)."""
        rows = []
        for analyzer, metric in analyzer_context.metric_map.items():
            if for_analyzers and analyzer not in for_analyzers:
                continue
            if not metric.value.is_success:
                continue
            for m in metric.flatten():
                if m.value.is_success:
                    rows.append(
                        {
                            "entity": m.entity.value,
                            "instance": m.instance,
                            "name": m.name,
                            "value": m.value.get(),
                        }
                    )
        return rows

    @staticmethod
    def success_metrics_as_json(
        analyzer_context: "AnalyzerContext",
        for_analyzers: Optional[Sequence[Analyzer]] = None,
    ) -> str:
        return json.dumps(
            AnalyzerContext.success_metrics_as_rows(analyzer_context, for_analyzers)
        )


def _is_grouping_shared(analyzer: Analyzer) -> bool:
    """Grouping analyzers that share a frequency table per grouping set.
    Histogram is excluded: its null handling and row count differ, so it
    runs its own pass (reference Histogram.scala is a plain Analyzer)."""
    return isinstance(analyzer, FrequencyBasedAnalyzer) and not isinstance(
        analyzer, Histogram
    )


def _count_stats_capable(a) -> bool:
    """True when the analyzer is a pure function of the count
    distribution (the ``group_count_stats`` fast path — group values
    never decode to host). Gated on an explicit override (not hasattr,
    which every subclass inherits): a subclass that only implements
    compute_from_frequencies falls back to the frequency table instead
    of having its NotImplementedError swallowed into a failure
    metric. Shared by the per-set pass and the round-19 fusion
    pre-pass so both pick the same finalize shape per set."""
    from deequ_tpu.analyzers.grouping import (
        ScanShareableFrequencyBasedAnalyzer as _SSF,
    )

    return (
        isinstance(a, _SSF)
        and type(a).compute_from_count_stats
        is not _SSF.compute_from_count_stats
    )


def _hll_presence_riders(
    data, scanning, partners, aggregate_with, save_states_with
) -> list:
    """The analyzers of ``scanning`` that may leave the fused scan and take
    their answer from ONE count of their column's codes in the own pass of
    a resident table (``segment.resident_top_k``): a where-free
    ``ApproxCountDistinct`` (its registers from the entries present) or
    ``DataType`` (its five counts from the classes of the entries) of a
    string column, no state provider (a state that is merged or saved goes
    the scan's way), no stream, and at least TWO consumers of that count in
    the run, among those and the Histograms of ``partners`` on the device
    top-N path (no binning). Whether the table is resident is the batch's
    to find out: a rider that comes back without its answer returns to
    the scan. One consumer alone stays on the scan: the count is not free
    then (a bincount of 22.6-84.8 ms a column against the 113 ms of
    per-row gather and fold it would save, PERF.md section 6, PR 32); with
    two it replaces two per-row gathers, or rides a count a Histogram
    makes anyway."""
    if (
        aggregate_with is not None
        or save_states_with is not None
        or getattr(data, "is_streaming", False)
    ):
        return []
    riders = [
        a for a in scanning
        if isinstance(a, (ApproxCountDistinct, DataType)) and a.where is None
        and data[a.column].dtype == DType.STRING
    ]
    if not riders:
        return riders
    consumers: Dict[str, int] = {}
    for a in riders + [
        h for h in partners
        if isinstance(h, Histogram)
        and h.takes_top_k_path(data, aggregate_with, save_states_with)
    ]:
        consumers[a.column] = consumers.get(a.column, 0) + 1
    return [a for a in riders if consumers[a.column] >= 2]


class _EarlyCountStats:
    """The count statistics of the grouping sets a persist()ed table's
    planes serve (``segment.dispatch_resident_count_stats``), dispatched
    once, ahead of the fused scan, and fetched by the per-set pass: so the
    run's device work queues back to back and the host waits on the
    device once at its end, not once a set. Only where the per-set pass
    takes the count-statistics path (no state provider, every analyzer
    count-capable, no stream); a set that fails to dispatch goes the
    per-set way, which surfaces the failure per analyzer."""

    def __init__(self, data, by_grouping, aggregate_with, save_states_with):
        self.pending: Dict[Tuple[str, ...], object] = {}
        self._sets = (
            []
            if aggregate_with is not None
            or save_states_with is not None
            or getattr(data, "is_streaming", False)
            else [
                key for key, analyzers in by_grouping.items()
                if all(_count_stats_capable(a) for a in analyzers)
            ]
        )
        self._data = data

    def dispatch(self) -> None:
        from deequ_tpu.ops.segment import dispatch_resident_count_stats

        sets, self._sets = self._sets, []
        for key in sets:
            try:
                pending = dispatch_resident_count_stats(self._data, list(key))
            except Exception:  # noqa: BLE001 — the per-set pass re-runs it
                continue
            if pending is not None:
                self.pending[key] = pending


def _release_spill(folder) -> None:
    """Free a fold's temp spill directory when its ``result()`` will never
    run (failed fold / aborted pass) — one copy of the private-attribute
    poke instead of one per call site."""
    store = getattr(folder, "_spill_store", None)
    if store is not None:
        store.release()


def _put_metrics(ctx, pairs, aggregate_with, save_states_with):
    """``ctx`` with the metric of every ``(analyzer, state)`` pair."""
    metrics = metrics_from_states(pairs, aggregate_with, save_states_with)
    ctx.metric_map.update(zip((a for a, _ in pairs), metrics))
    return ctx


def _save_or_append_result(metrics_repository, result_key, ctx) -> None:
    """Append ctx's metrics into the repository entry for result_key — the
    ONE copy of the load-combine-save sequence every runner path shares."""
    if metrics_repository is None or result_key is None:
        return
    from deequ_tpu.repository import AnalysisResult

    with seam("repository"):
        existing = metrics_repository.load_by_key(result_key)
        combined = (
            (existing.analyzer_context + ctx) if existing is not None else ctx
        )
        metrics_repository.save(AnalysisResult(result_key, combined))


class AnalysisRunner:
    """Entry points for computing metrics (reference AnalysisRunner.scala)."""

    @staticmethod
    def on_data(data: ColumnarTable) -> "AnalysisRunBuilder":
        from deequ_tpu.analyzers.builder import AnalysisRunBuilder

        return AnalysisRunBuilder(data)

    @staticmethod
    def do_analysis_run(
        data: ColumnarTable,
        analyzers: Sequence[Analyzer],
        aggregate_with=None,
        save_states_with=None,
        metrics_repository=None,
        reuse_existing_results_for_key=None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key=None,
        group_memory_budget=None,
        checkpoint=None,
        on_batch_error: str = "fail",
        retry_policy=None,
        on_device_error: str = "fail",
        device_deadline=None,
        shard_deadline=None,
    ) -> AnalyzerContext:
        """``group_memory_budget`` (bytes; also settable per-table via
        ``StreamingTable.with_group_memory_budget`` or the
        DEEQU_TPU_GROUP_MEMORY_BUDGET env var) bounds the host RSS of
        grouping-state accumulation: past the budget, frequency deltas
        spill to disk as sorted runs and merge back streaming at finalize
        (deequ_tpu/spill). None = unbounded, the historical behavior.

        Resilience (streaming tables only; deequ_tpu/resilience):
        ``checkpoint`` (a StreamCheckpointer or a directory path)
        periodically persists the per-analyzer fold states so a killed run
        resumes from its last checkpointed batch index with bit-identical
        metrics; ``on_batch_error="skip"`` quarantines batches whose reads
        keep failing past retries (indices reported on the context) instead
        of failing the run; ``retry_policy`` overrides the batch-read
        RetryPolicy (default: the table's, else the process default).

        Device faults (ops/device_policy.py + scan_engine.run_scan):
        ``on_device_error="fallback"`` lets fused scans whose accelerator
        OOMs below the bisection floor, fails to compile, is lost, or
        hangs re-run on the CPU backend instead of failing their
        analyzers (``"fail"``, the default, turns the typed exception
        into failure metrics per the shared-scan rule); device OOMs
        bisect the chunk size either way. ``device_deadline`` (seconds)
        arms the compute watchdog around blocking device calls. A
        streaming run with ``on_device_error="fallback"`` routes through
        the resilient batch loop so each batch's scan gets the full
        bisect/fallback policy."""
        if not analyzers:
            return AnalyzerContext.empty()

        # run-level governance: when the env vars (DEEQU_TPU_RUN_DEADLINE
        # / DEEQU_TPU_RUN_ATTEMPTS) arm a budget and no ambient one is
        # installed (the VerificationSuite entry point installs its own),
        # arm it HERE, for the whole analysis — otherwise every per-batch
        # run_scan of a streaming run would resolve the env vars into a
        # FRESH per-scan budget and the stream would pay per batch again
        from deequ_tpu.resilience.governance import (
            current_run_budget,
            resolve_run_policy,
            run_budget_scope,
        )

        if current_run_budget() is None:
            run_policy = resolve_run_policy()
            if run_policy is not None:
                with run_budget_scope(run_policy.arm()):
                    return AnalysisRunner.do_analysis_run(
                        data,
                        analyzers,
                        aggregate_with=aggregate_with,
                        save_states_with=save_states_with,
                        metrics_repository=metrics_repository,
                        reuse_existing_results_for_key=(
                            reuse_existing_results_for_key
                        ),
                        fail_if_results_missing=fail_if_results_missing,
                        save_or_append_results_with_key=(
                            save_or_append_results_with_key
                        ),
                        group_memory_budget=group_memory_budget,
                        checkpoint=checkpoint,
                        on_batch_error=on_batch_error,
                        retry_policy=retry_policy,
                        on_device_error=on_device_error,
                        device_deadline=device_deadline,
                        shard_deadline=shard_deadline,
                    )

        with seam("plan", what="partition analyzers"):
            analyzers = list(analyzers)

            # an explicit retry policy must cover EVERY streaming path, not
            # just the resilient branch: wrap the handle so the fused scan,
            # grouping folds, and own-pass loops all read through it (the
            # resilient loop's exhaustion handling recognizes the wrapper's
            # RetryExhaustedException, so retries never multiply)
            if retry_policy is not None and hasattr(data, "with_retry"):
                data = data.with_retry(retry_policy)

            # (1) repository reuse (reference L116-134)
            results_loaded = AnalyzerContext.empty()
            if metrics_repository is not None and reuse_existing_results_for_key is not None:
                existing = metrics_repository.load_by_key(reuse_existing_results_for_key)
                if existing is not None:
                    loaded = {
                        a: m
                        for a, m in existing.analyzer_context.metric_map.items()
                        if a in analyzers
                    }
                    results_loaded = AnalyzerContext(loaded)
            remaining = [a for a in analyzers if a not in results_loaded.metric_map]
            if fail_if_results_missing and remaining:
                raise ReusingNotPossibleResultsMissingException(
                    "Could not find all necessary results in the MetricsRepository, "
                    f"the calculation of the metrics for these analyzers would be "
                    f"needed: {', '.join(str(a) for a in remaining)}"
                )

            # (2) precondition partition (reference L137-145)
            passed: List[Analyzer] = []
            failure_ctx = AnalyzerContext.empty()
            for analyzer in remaining:
                exc = find_first_failing(data.schema, analyzer.preconditions())
                if exc is None:
                    passed.append(analyzer)
                else:
                    failure_ctx.metric_map[analyzer] = analyzer.to_failure_metric(exc)

            # (3) split (reference L148-153)
            grouping = [a for a in passed if _is_grouping_shared(a)]
            scanning = [
                a
                for a in passed
                if isinstance(a, ScanShareableAnalyzer) and not _is_grouping_shared(a)
            ]
            own_pass = [a for a in passed if a not in grouping and a not in scanning]

            # grouping analyzers share one frequency fold per distinct sorted
            # grouping-column set — ONE partition rule for both the resilient
            # branch below and step (5)
            by_grouping: Dict[Tuple[str, ...], List[FrequencyBasedAnalyzer]] = {}
            for analyzer in grouping:
                key = tuple(sorted(analyzer.group_columns))
                by_grouping.setdefault(key, []).append(analyzer)

        # resilient streaming pass: checkpoint/resume and batch quarantine
        # need per-batch fold state on the host, so ALL analyzers share one
        # batch loop (fused per-batch scans for the scan-shareable set)
        if getattr(data, "is_streaming", False) and (
            checkpoint is not None
            or on_batch_error != "fail"
            or on_device_error != "fail"
        ):
            resilient_ctx = AnalysisRunner._run_streaming_resilient(
                data, scanning, own_pass, by_grouping,
                aggregate_with, save_states_with,
                group_memory_budget=group_memory_budget,
                checkpoint=checkpoint, on_batch_error=on_batch_error,
                retry_policy=retry_policy,
                on_device_error=on_device_error,
                device_deadline=device_deadline,
                shard_deadline=shard_deadline,
            )
            result = results_loaded + failure_ctx + resilient_ctx
            _save_or_append_result(
                metrics_repository, save_or_append_results_with_key, result
            )
            return result

        # budgeted in-memory table: frequency-shaped own-pass states
        # (Histogram) are O(#distinct) like the shared grouping path —
        # slice the rows into budget-sized batches and take the
        # spilling stream fold, same as _run_grouping_analyzers does
        streaming = getattr(data, "is_streaming", False)
        spillable: list = []
        if own_pass and not streaming:
            from deequ_tpu.spill import budget_batch_rows, resolve_group_budget

            budget = resolve_group_budget(data, group_memory_budget)
            if budget is not None:
                batch_rows = budget_batch_rows(budget)
                if data.num_rows > batch_rows:
                    spillable = [
                        a for a in own_pass
                        if isinstance(a, FrequencyBasedAnalyzer)
                    ]
        one_by_one = [a for a in own_pass if a not in spillable]

        # a persist()ed table's Histograms: one dispatch, one fetch, and
        # BEFORE the fused scan: the where-free ApproxCountDistinct and
        # DataType of a string column whose count has a second consumer
        # in the run take their answers from the batch's counts and leave
        # the scan (they return to it when the batch brought nothing for
        # them)
        own_ctx = AnalyzerContext.empty()
        served: Dict[Analyzer, Metric] = {}
        # the count statistics the resident planes serve are dispatched
        # behind the Histograms' batch (else here) and fetched in (5): the
        # device works through them while the host decodes the batch and
        # plans the scan, which queues behind them
        early = _EarlyCountStats(
            data, by_grouping, aggregate_with, save_states_with
        )
        with seam("plan", what="presence riders"):
            riders = _hll_presence_riders(
                data, scanning, one_by_one, aggregate_with, save_states_with,
            )
        if riders or (one_by_one and not streaming):
            from deequ_tpu.analyzers.grouping import resident_histograms

            histograms, registers, types = resident_histograms(
                data, one_by_one, aggregate_with, save_states_with,
                registers_of=[
                    a.column for a in riders
                    if isinstance(a, ApproxCountDistinct)
                ],
                types_of=[
                    a.column for a in riders if isinstance(a, DataType)
                ],
                while_in_flight=early.dispatch,
            )
            own_ctx.metric_map.update(histograms)
            pairs = [
                (a, a.state_from_present_registers(registers[a.column]))
                for a in riders
                if isinstance(a, ApproxCountDistinct) and a.column in registers
            ] + [
                (a, a.state_from_scan_result({"counts": types[a.column]}))
                for a in riders
                if isinstance(a, DataType) and a.column in types
            ]
            if pairs:
                served = _put_metrics(
                    AnalyzerContext.empty(), pairs, None, None
                ).metric_map
        early.dispatch()

        # (4) one fused scan for all shareable analyzers (reference L289-336)
        scan_ctx = AnalysisRunner._run_scanning_analyzers(
            data,
            [a for a in scanning if a not in served] if served else scanning,
            aggregate_with, save_states_with,
            on_device_error=on_device_error, device_deadline=device_deadline,
            shard_deadline=shard_deadline,
        )
        if served:  # in the order the scan would have answered
            answered = {**scan_ctx.metric_map, **served}
            scan_ctx.metric_map = {
                a: answered[a] for a in scanning if a in answered
            }

        # own-pass analyzers (KLL extra pass analogue, reference L155-160);
        # on a stream they share ONE batch loop — N analyzers must not cost
        # N full storage reads
        if own_pass and streaming:
            own_ctx += AnalysisRunner._run_own_pass_streaming(
                data, own_pass, aggregate_with, save_states_with,
                group_memory_budget=group_memory_budget,
            )
        elif own_pass:
            if spillable:
                from deequ_tpu.data.streaming import stream_table

                own_ctx += AnalysisRunner._run_own_pass_streaming(
                    stream_table(data, batch_rows), spillable,
                    aggregate_with, save_states_with,
                    group_memory_budget=budget,
                )
            for analyzer in one_by_one:
                if analyzer not in own_ctx.metric_map:
                    own_ctx.metric_map[analyzer] = analyzer.calculate(
                        data, aggregate_with, save_states_with
                    )

        # (5) grouping analyzers share one frequency table per distinct
        # sorted grouping-column set (reference L175-190; partition built
        # above, shared with the resilient branch). The plan optimizer
        # (round 19) first tries to FUSE the dense sets into one device
        # dispatch; sets it computed skip their per-set pass, sets it
        # skipped (sparse/streaming/budgeted/faulted) run exactly as
        # before.
        group_ctx = AnalyzerContext.empty()
        fused_states = AnalysisRunner._fuse_grouping_sets(
            data, by_grouping, aggregate_with, save_states_with,
            group_memory_budget,
        )
        for group_key, group_analyzers in by_grouping.items():
            group_ctx += AnalysisRunner._run_grouping_analyzers(
                data, list(group_key), group_analyzers, aggregate_with,
                save_states_with, group_memory_budget=group_memory_budget,
                precomputed=fused_states.get(group_key),
                pending=early.pending.get(group_key),
            )

        result = (
            results_loaded + failure_ctx + scan_ctx + own_ctx + group_ctx
        )

        # (6) save to repository (reference L192-202)
        _save_or_append_result(
            metrics_repository, save_or_append_results_with_key, result
        )

        return result

    @staticmethod
    def _coalesce_scan_ops(ops):
        """Merge ops that share a batch_hint kind/params into one vectorized
        op (currently: N same-parameter where-free KLL sorts -> one vmapped
        batched sort, the dominant cost of wide quantile profiles).

        The scalar stat ops are NOT merged here. Round 4 tried it by
        STACKING per-column slices into a new (K, n) array and measured it
        slower (181 ms per-column against 256 ms batched, on a link and a
        benchmark that are gone): the stack was a copy on top of the
        copies the slices already were. What that comment could not see:
        a column sliced out of the packed (C, n) plane is itself a
        re-layout copy (one sublane in eight of every tile, written back
        to HBM as 1-D arrays: ~24 of the 46 ms of a 10M x 20 suite on the
        v5e, PERF.md PR 27). The batching that pays needs no stack: the
        planner (ops/scan_plan.py) routes where-free one-column
        statistics onto scan_engine.PlaneStats, which reduces the planes
        where they lie, along their rows, and the ops stay one per
        analyzer with their own leaves. Nothing to merge at this level.

        Returns (exec_ops, plan) where plan[i] = (exec_index, extractor or
        None) for scannable[i]."""
        from deequ_tpu.analyzers.sketches import (
            _kll_multi_extract,
            _kll_multi_scan_op,
        )
        from deequ_tpu.ops.scan_engine import ScanOp

        groups: Dict[Tuple, List[int]] = {}
        for i, op in enumerate(ops):
            hint = op.batch_hint
            if hint is not None and hint[0] == "kll":
                groups.setdefault(hint[:2], []).append(i)

        mergeable = {
            key: idxs for key, idxs in groups.items() if len(idxs) >= 2
        }
        if not mergeable:
            return list(ops), [(i, None) for i in range(len(ops))]

        exec_ops: List[ScanOp] = []
        plan: List[Optional[Tuple[int, Optional[callable]]]] = [None] * len(ops)
        merged_members = {i for idxs in mergeable.values() for i in idxs}
        for i, op in enumerate(ops):
            if i in merged_members:
                continue
            plan[i] = (len(exec_ops), None)
            exec_ops.append(op)
        for (kind, sketch_size), idxs in sorted(mergeable.items()):
            columns = tuple(ops[i].batch_hint[2] for i in idxs)
            K = len(idxs)
            exec_idx = len(exec_ops)
            merged = _kll_multi_scan_op(columns, sketch_size)
            merged.cache_key = ("kll_batch", sketch_size, columns)
            exec_ops.append(merged)
            for j, i in enumerate(idxs):
                plan[i] = (
                    exec_idx,
                    (lambda result, j=j, K=K: _kll_multi_extract(result, j, K)),
                )
        return exec_ops, plan

    @staticmethod
    def _build_scan_ops(data: ColumnarTable, analyzers):
        """Per-analyzer ScanOp construction with failure isolation: a
        malformed op (e.g. a bad where expression) fails only its analyzer.
        Returns (ops, scannable, op_failures) — analyzers are hashable
        value objects, so each op's cache_key is its analyzer, keying the
        traced-program cache for repeated runs (scan_engine). Shared by
        the serial path and the pipelined group path
        (analyzers/incremental.py) so op policy cannot drift between them."""
        ops = []
        scannable = []
        op_failures = {}
        for analyzer in analyzers:
            try:
                op = analyzer.scan_op(data)
                op.cache_key = analyzer
                ops.append(op)
                scannable.append(analyzer)
            except Exception as e:  # noqa: BLE001
                op_failures[analyzer] = wrap_if_necessary(e)
        return ops, scannable, op_failures

    @staticmethod
    def _dispatch_scanning_analyzers(
        data: ColumnarTable,
        analyzers: Sequence[ScanShareableAnalyzer],
        defer: bool = False,
        on_device_error: str = "fail",
        device_deadline=None,
        shard_deadline=None,
    ):
        """Build + dispatch the fused scan. Returns (ctx_with_failures,
        scannable, plan, scan) where scan is the results list (or a
        DeferredScan when defer=True), or None when nothing scanned."""
        ctx = AnalyzerContext.empty()
        if not analyzers:
            return ctx, [], [], None
        with seam("plan", what="scan ops"):
            ops, scannable, op_failures = AnalysisRunner._build_scan_ops(
                data, analyzers
            )
        for analyzer, err in op_failures.items():
            ctx.metric_map[analyzer] = analyzer.to_failure_metric(err)
        if not scannable:
            return ctx, [], [], None
        try:
            with seam("plan", what="coalesce scan ops"):
                exec_ops, plan = AnalysisRunner._coalesce_scan_ops(ops)
            scan = run_scan(
                data, exec_ops, defer=defer,
                on_device_error=on_device_error,
                device_deadline=device_deadline,
                shard_deadline=shard_deadline,
            )
        except PlanLintError:
            # a static contract violation is a PROGRAMMING error caught
            # pre-dispatch (planner drift, mis-tagged fold leaf), not
            # data: the error-mode contract is that it RAISES typed
            # through VerificationSuite (verification.py docstring)
            # instead of masquerading as per-analyzer failure metrics
            raise
        except RunBudgetExhaustedException:
            # run-budget exhaustion is a RUN-level outcome, not one
            # analyzer's: the caller decides (streaming loop: finalize a
            # partial result; in-memory: _run_scanning_analyzers records
            # the unverified range; "raise" mode: propagate typed)
            raise
        except Exception as e:  # noqa: BLE001 — a failure inside the shared
            # scan maps onto every participating analyzer (reference L320-323)
            wrapped = wrap_if_necessary(e)
            for a in scannable:
                ctx.metric_map[a] = a.to_failure_metric(wrapped)
            return ctx, [], [], None
        return ctx, scannable, plan, scan

    @staticmethod
    def _finalize_scanning_analyzers(
        ctx: AnalyzerContext,
        scannable,
        plan,
        results,
        aggregate_with=None,
        save_states_with=None,
    ) -> AnalyzerContext:
        metrics = [None] * len(scannable)
        pairs, slots = [], []
        with seam("evaluate", what="states from scan results"):
            for i, (analyzer, (exec_idx, extract)) in enumerate(
                zip(scannable, plan)
            ):
                try:
                    result = results[exec_idx]
                    if extract is not None:
                        result = extract(result)
                    pairs.append(
                        (analyzer, analyzer.state_from_scan_result(result))
                    )
                    slots.append(i)
                except Exception as e:  # noqa: BLE001
                    metrics[i] = analyzer.to_failure_metric(
                        wrap_if_necessary(e)
                    )
        for i, metric in zip(
            slots, metrics_from_states(pairs, aggregate_with, save_states_with)
        ):
            metrics[i] = metric
        ctx.metric_map.update(zip(scannable, metrics))
        return ctx

    @staticmethod
    def _run_scanning_analyzers(
        data: ColumnarTable,
        analyzers: Sequence[ScanShareableAnalyzer],
        aggregate_with=None,
        save_states_with=None,
        on_device_error: str = "fail",
        device_deadline=None,
        shard_deadline=None,
    ) -> AnalyzerContext:
        try:
            ctx, scannable, plan, scan = (
                AnalysisRunner._dispatch_scanning_analyzers(
                    data, analyzers,
                    on_device_error=on_device_error,
                    device_deadline=device_deadline,
                    shard_deadline=shard_deadline,
                )
            )
        except RunBudgetExhaustedException as e:
            if not e.degraded:
                raise
            # graceful degradation (on_budget_exhausted="degrade"): the
            # fused scan could not finish within the run budget, so NONE
            # of these rows were verified by this pass — report the exact
            # range on the PR-5 partial-result surface and turn the typed
            # exception into failure metrics (failure-as-data), letting
            # the run complete instead of raising mid-ladder
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            try:
                total = int(data.num_rows or 0)
            except Exception:  # noqa: BLE001 — count-less streaming source
                total = 0
            if total > 0:
                SCAN_STATS.record_unverified(
                    0, total, reason=str(e), kind="budget_exhausted"
                )
            else:
                SCAN_STATS.record_degradation(
                    "budget_exhausted", reason=str(e)
                )
            return AnalyzerContext(
                {a: a.to_failure_metric(e) for a in analyzers}
            )
        if scan is None:
            return ctx
        return AnalysisRunner._finalize_scanning_analyzers(
            ctx, scannable, plan, scan, aggregate_with, save_states_with
        )

    @staticmethod
    def _run_own_pass_streaming(
        data,
        analyzers: Sequence[Analyzer],
        aggregate_with=None,
        save_states_with=None,
        group_memory_budget=None,
    ) -> AnalyzerContext:
        """Fold every own-pass analyzer's monoid state over ONE shared pass
        of the stream (reading the columns any of them needs), instead of
        one full storage scan per analyzer. An analyzer whose per-batch
        update raises drops out with a failure metric; the others keep
        folding. Frequency-shaped states (Histogram) spill to disk under a
        group memory budget like the shared-grouping path."""
        from deequ_tpu.analyzers.base import StreamStateFolder
        from deequ_tpu.spill import resolve_group_budget

        budget = resolve_group_budget(data, group_memory_budget)

        columns: Optional[set] = set()
        for a in analyzers:
            cols = a._stream_columns()
            if cols is None:
                columns = None
                break
            columns.update(cols)

        def make_folder(a: Analyzer) -> StreamStateFolder:
            if budget is not None and isinstance(a, FrequencyBasedAnalyzer):
                from deequ_tpu.spill import SpillingFrequencyStore

                return StreamStateFolder(
                    spill_store=SpillingFrequencyStore(
                        tuple(a.group_columns), budget
                    ),
                    # Histogram states are np.unique-label-sorted; shared
                    # grouping states don't come through this path
                    assume_canonical=True,
                )
            return StreamStateFolder()

        # tree fold per analyzer (see StreamStateFolder: a linear chain
        # re-merges the full growing state per batch)
        folders: Dict[Analyzer, StreamStateFolder] = {
            a: make_folder(a) for a in analyzers
        }
        failed: Dict[Analyzer, Exception] = {}
        try:
            for batch in data.batches(
                columns=sorted(columns) if columns is not None else None
            ):
                for a in analyzers:
                    if a in failed:
                        continue
                    try:
                        folders[a].add(a.compute_state_from(batch))
                    except PlanLintError:
                        raise  # static contract violation: typed, never a metric
                    except Exception as e:  # noqa: BLE001
                        failed[a] = e
        except PlanLintError:
            # typed through every surface (plan_lint="error" contract);
            # still release spill stores so temp dirs don't outlive us
            for f in folders.values():
                _release_spill(f)
            raise
        except Exception as e:  # noqa: BLE001 — a source/read error fails
            # every analyzer of the pass (the shared-scan failure rule);
            # release any spill stores so temp dirs don't outlive the run
            for f in folders.values():
                _release_spill(f)
            wrapped = wrap_if_necessary(e)
            return AnalyzerContext(
                {a: a.to_failure_metric(wrapped) for a in analyzers}
            )

        ctx = AnalyzerContext.empty()
        pairs = []
        for a in analyzers:
            if a in failed:
                ctx.metric_map[a] = a.to_failure_metric(
                    wrap_if_necessary(failed[a])
                )
                # a failed fold's result() never runs: free its spill dir
                _release_spill(folders[a])
            else:
                pairs.append((a, folders[a].result()))
        _put_metrics(ctx, pairs, aggregate_with, save_states_with)
        return ctx

    @staticmethod
    def _run_streaming_resilient(
        data,
        scanning: Sequence[ScanShareableAnalyzer],
        own_pass: Sequence[Analyzer],
        by_grouping: Dict[Tuple[str, ...], List],
        aggregate_with=None,
        save_states_with=None,
        group_memory_budget=None,
        checkpoint=None,
        on_batch_error: str = "fail",
        retry_policy=None,
        on_device_error: str = "fail",
        device_deadline=None,
        shard_deadline=None,
    ) -> AnalyzerContext:
        """One resilient batch loop over the stream for EVERY analyzer
        class (scan-shareable / own-pass / grouping), with host-resident
        fold state so it can checkpoint and quarantine
        (deequ_tpu/resilience):

        - batch reads run through ``resilient_batches`` — transient errors
          retry with backoff + reopen-at-batch; exhausted retries either
          fail the pass (the shared-scan failure rule) or, with
          ``on_batch_error="skip"``, quarantine the batch index (counted
          on the context, reported on VerificationResult);
        - scan-shareable analyzers still fuse into ONE device pass per
          batch (`_dispatch_scanning_analyzers` on the in-memory batch) —
          their states fold as host monoids, which is what makes them
          checkpointable via states/serde;
        - every ``checkpoint.every_batches`` folded batches the fold
          stacks persist atomically+checksummed; on start, the newest
          valid checkpoint with a matching run fingerprint restores the
          stacks and the loop resumes at its batch index. The stacks ARE
          the fold state, so resumed metrics are bit-identical to an
          uninterrupted checkpointed run.

        Trade-off vs the non-resilient paths: per-batch monoid folds
        instead of the device-resident pipelined partials — checkpointable
        state costs some scan-engine pipelining (not measured on the
        chip)."""
        from deequ_tpu.analyzers.base import StreamStateFolder
        from deequ_tpu.ops.segment import group_counts_state
        from deequ_tpu.resilience.checkpoint import (
            StreamCheckpoint,
            StreamCheckpointer,
            run_fingerprint,
        )
        from deequ_tpu.resilience.retry import (
            resilient_batches,
            resolve_retry_policy,
        )

        if isinstance(checkpoint, str):
            checkpoint = StreamCheckpointer(checkpoint)
        policy = resolve_retry_policy(data, retry_policy)

        # duplicate equal analyzers must fold ONCE (the repr-keyed folders
        # collapse them; folding per list entry would double their counts)
        scanning = list(dict.fromkeys(scanning))
        own_pass = list(dict.fromkeys(own_pass))
        by_grouping = {
            g: list(dict.fromkeys(group_analyzers))
            for g, group_analyzers in by_grouping.items()
        }
        per_analyzer = scanning + own_pass

        # group memory budget: quarantine-only runs spill frequency folds
        # to disk exactly like the non-resilient paths; a checkpointed run
        # cannot (mid-store spill state is not serializable), which must
        # be LOUD, not a silent OOM cliff
        from deequ_tpu.spill import resolve_group_budget

        budget = resolve_group_budget(data, group_memory_budget)
        if budget is not None and checkpoint is not None:
            # ONE warn() per run: this method runs once per analysis run,
            # never per batch. No filter overrides here — the typed
            # category lets users suppress (filterwarnings ignore) or
            # escalate (-W error) it; display dedup across runs is their
            # filter policy, not ours.
            import warnings

            warnings.warn(
                "group_memory_budget is ignored for checkpointed "
                "streaming runs: spilled frequency state cannot be "
                "checkpointed; frequency folds stay in host RAM",
                GroupBudgetIgnoredWarning,
                stacklevel=2,
            )
            budget = None
        spill_stores: List = []

        def make_folder(spill_columns=None) -> StreamStateFolder:
            if budget is not None and spill_columns is not None:
                from deequ_tpu.spill import SpillingFrequencyStore

                store = SpillingFrequencyStore(tuple(spill_columns), budget)
                spill_stores.append(store)
                return StreamStateFolder(
                    spill_store=store, assume_canonical=True
                )
            return StreamStateFolder()

        keys = {a: f"analyzer::{a!r}" for a in per_analyzer}
        group_keys = {g: "group::" + ",".join(g) for g in by_grouping}
        folders: Dict[str, StreamStateFolder] = {}
        for a in scanning:
            folders[keys[a]] = make_folder()
        for a in own_pass:
            folders[keys[a]] = make_folder(
                # Histogram-style frequency states spill under the budget;
                # their states are np.unique-label-sorted (canonical)
                tuple(a.group_columns)
                if isinstance(a, FrequencyBasedAnalyzer)
                else None
            )
        for g in by_grouping:
            folders[group_keys[g]] = make_folder(g)

        # column pruning: union of every fold's needs (None = full width)
        columns: Optional[set] = set()
        for a in per_analyzer:
            cols = a._stream_columns()
            if cols is None:
                columns = None
                break
            columns.update(cols)
        if columns is not None:
            for g in by_grouping:
                columns.update(g)
            if not columns and len(data.schema.column_names):
                # row-count-only workloads (a lone Size()) prune to ZERO
                # columns, and a zero-column batch cannot carry its row
                # count — read one column so batches keep their geometry
                columns.add(data.schema.column_names[0])

        # fingerprint: fold keys + batch geometry + whatever identity the
        # source exposes (file paths, metadata row count) — a checkpoint
        # from a run over different data must not resume this one
        batch_rows = getattr(data, "preferred_batch_rows", None)
        src = getattr(data, "source", None)
        # wrappers (RetryingBatchSource, fault/test doubles) follow the
        # ``.inner`` convention — walk the chain so the underlying file
        # identity isn't hidden by a retry layer
        src_id = None
        probe, depth = src, 0
        while probe is not None and src_id is None and depth < 8:
            src_id = getattr(probe, "paths", None) or getattr(probe, "path", None)
            probe = getattr(probe, "inner", None)
            depth += 1
        try:
            known_rows = src.num_rows if src is not None else None
        except Exception:  # noqa: BLE001 — identity is best-effort
            known_rows = None
        fingerprint = run_fingerprint(
            sorted(folders), (batch_rows, src_id, known_rows)
        )

        # exact batch count, when knowable: lets the iterator tell an
        # unreadable batch from a failing END-OF-STREAM probe. Gated to
        # row-sliced sources — variable-geometry readers (parquet row
        # groups) can yield MORE batches than ceil(rows/batch_rows), and
        # an over-tight bound would silently truncate on a late error
        from deequ_tpu.data.source import TableBatchSource

        innermost, depth = src, 0
        while hasattr(innermost, "inner") and depth < 8:
            innermost = innermost.inner
            depth += 1
        max_batches = None
        if (
            isinstance(innermost, TableBatchSource)
            and known_rows is not None
            and batch_rows
        ):
            max_batches = max(
                (known_rows + batch_rows - 1) // batch_rows, 1
            )

        start = 0
        skipped: List[int] = []
        failed: Dict[Analyzer, Metric] = {}
        failed_groups: Dict[Tuple[str, ...], Exception] = {}
        if checkpoint is not None:
            recovered = checkpoint.load_latest(fingerprint)
            if recovered is not None:
                start = recovered.batch_index
                skipped = list(recovered.skipped)
                for key, stack in recovered.stacks.items():
                    if key in folders:
                        folders[key]._stack = list(stack)
                # failures are STICKY across resume: reviving an analyzer
                # that dropped out before the checkpoint would report a
                # success metric computed over a gap of batches
                key_to_analyzer = {k: a for a, k in keys.items()}
                key_to_group = {k: g for g, k in group_keys.items()}
                for key, msg in recovered.failed.items():
                    exc = MetricCalculationRuntimeException(
                        f"{msg} (failed before the checkpoint at batch "
                        f"{recovered.batch_index}; kept failed on resume)"
                    )
                    if key in key_to_analyzer:
                        a = key_to_analyzer[key]
                        failed[a] = a.to_failure_metric(exc)
                    elif key in key_to_group:
                        failed_groups[key_to_group[key]] = exc
        read_cols = sorted(columns) if columns is not None else None

        # deferred per-batch fused scans: each batch's scan dispatches
        # immediately (and, with device-foldable ops, folds its chunk
        # partials ON device), but the device->host fetch is batched —
        # ONE fetch_deferred round trip at each checkpoint boundary (or
        # every `drain_every` batches without one) instead of a fetch
        # per batch. Fold order stays strictly batch order, so the fold
        # stacks — and therefore checkpointed/resumed metrics — are
        # bit-identical to the eager per-batch loop.
        drain_every = (
            checkpoint.every_batches if checkpoint is not None else 8
        )
        pending: List[Tuple] = []  # (scannable, plan, DeferredScan)

        def drain_pending() -> None:
            if not pending:
                return
            from deequ_tpu.ops.scan_engine import fetch_deferred

            entries = list(pending)
            pending.clear()
            # one coalesced fetch; per-scan failures isolate (a failed
            # batch fails ITS analyzers at result(), siblings fold on).
            # A fault of the FETCH itself (typed device error surfacing
            # at the round trip) is scoped to the pending batches' scans
            # — own-pass/grouping folds and later batches keep going,
            # matching the shared-scan failure rule's blast radius.
            try:
                fetch_deferred([scan for (_, _, scan) in entries])
            except Exception as e:  # noqa: BLE001
                wrapped = wrap_if_necessary(e)
                for scannable, _, _ in entries:
                    for a in scannable:
                        if a not in failed:
                            failed[a] = a.to_failure_metric(wrapped)
                return
            for scannable, plan, scan in entries:
                try:
                    results = scan.result()
                except Exception as e:  # noqa: BLE001
                    wrapped = wrap_if_necessary(e)
                    for a in scannable:
                        if a not in failed:
                            failed[a] = a.to_failure_metric(wrapped)
                    continue
                for a, (exec_idx, extract) in zip(scannable, plan):
                    if a in failed:
                        continue
                    try:
                        r = results[exec_idx]
                        if extract is not None:
                            r = extract(r)
                        folders[keys[a]].add(a.state_from_scan_result(r))
                    except Exception as e:  # noqa: BLE001
                        failed[a] = a.to_failure_metric(
                            wrap_if_necessary(e)
                        )

        def fold_batch(batch) -> None:
            alive_scan = [a for a in scanning if a not in failed]
            if alive_scan:
                # ops rebuild per batch by design: scan_op(batch) may bake
                # batch-local state (string dictionaries); the expensive
                # part — the traced device program — is reused across
                # batches via each op's analyzer cache_key (scan_engine)
                sctx, scannable, plan, results = (
                    AnalysisRunner._dispatch_scanning_analyzers(
                        batch, alive_scan, defer=True,
                        on_device_error=on_device_error,
                        device_deadline=device_deadline,
                        shard_deadline=shard_deadline,
                    )
                )
                failed.update(sctx.metric_map)
                if results is not None:
                    pending.append((scannable, plan, results))
            for a in own_pass:
                if a in failed:
                    continue
                try:
                    folders[keys[a]].add(a.compute_state_from(batch))
                except PlanLintError:
                    raise  # static contract violation: typed, never a metric
                except RunBudgetExhaustedException:
                    raise  # run-level outcome: the loop degrades/raises
                except Exception as e:  # noqa: BLE001
                    failed[a] = a.to_failure_metric(wrap_if_necessary(e))
            for g in by_grouping:
                if g in failed_groups:
                    continue
                try:
                    folders[group_keys[g]].add(
                        group_counts_state(
                            batch, list(g),
                            canonicalize=folders[group_keys[g]]._spill_store
                            is not None,
                        )
                    )
                except RunBudgetExhaustedException:
                    raise  # run-level outcome: the loop degrades/raises
                except Exception as e:  # noqa: BLE001
                    failed_groups[g] = wrap_if_necessary(e)

        got_any = start > 0
        last_seen_idx = start - 1
        try:
            for idx, batch in resilient_batches(
                lambda i: data.batches_from(i, columns=read_cols),
                policy,
                on_batch_error=on_batch_error,
                quarantined=skipped,
                start=start,
                max_batches=max_batches,
            ):
                got_any = True
                # counted only AFTER the fold: if fold_batch dies
                # mid-batch (e.g. the per-batch scan exhausts the run
                # budget), batch idx is NOT verified and the degrade
                # handler's boundary must start at it
                fold_batch(batch)
                last_seen_idx = idx
                n_done = idx + 1
                ckpt_due = checkpoint is not None and checkpoint.due(n_done)
                if ckpt_due or len(pending) >= drain_every:
                    drain_pending()
                if ckpt_due:
                    failed_msgs = {
                        keys[a]: str(getattr(m.value, "exception", m.value))
                        for a, m in failed.items()
                    }
                    failed_msgs.update(
                        {group_keys[g]: str(e) for g, e in failed_groups.items()}
                    )
                    checkpoint.save(
                        fingerprint,
                        StreamCheckpoint(
                            n_done,
                            list(skipped),
                            {k: list(f._stack) for k, f in folders.items()},
                            failed_msgs,
                        ),
                    )
            if not got_any and not skipped:
                # empty stream: fold one empty batch so counting analyzers
                # emit identity metrics (Size=0), matching the fused
                # streaming engine's all-padding chunk
                from deequ_tpu.data.streaming import _empty_table

                schema = (
                    data.schema
                    if read_cols is None
                    else Schema([data.schema[c] for c in read_cols])
                )
                fold_batch(_empty_table(schema))
            drain_pending()  # tail batches since the last boundary
        except RunBudgetExhaustedException as e:
            if not e.degraded:
                for store in spill_stores:
                    store.release()
                raise
            # graceful degradation (on_budget_exhausted="degrade"): the
            # composed ladder ran out of run budget mid-stream. The fold
            # stacks hold every batch verified SO FAR — finalize them
            # into a PARTIAL result and report the rows never reached as
            # an exact unverified range (the PR-5 surface) instead of
            # failing the whole run or burning more attempts.
            try:
                # best-effort: scans dispatched before exhaustion can
                # still materialize without new ladder attempts; any
                # failure in here already maps to per-analyzer failure
                # metrics inside drain_pending
                drain_pending()
            except Exception:  # noqa: BLE001 — degrade must not re-fail
                pending.clear()
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            boundary_idx = max([last_seen_idx] + list(skipped)) + 1
            row0 = None
            if batch_rows and known_rows is not None:
                row0 = min(boundary_idx * int(batch_rows), int(known_rows))
            if row0 is not None and row0 < int(known_rows):
                SCAN_STATS.record_unverified(
                    row0, int(known_rows), reason=str(e),
                    kind="budget_exhausted",
                )
            else:
                SCAN_STATS.record_degradation(
                    "budget_exhausted",
                    reason=str(e),
                    batches_verified=boundary_idx,
                )
        except Exception as e:  # noqa: BLE001 — a read failure past
            # retries fails every analyzer of the pass (shared-scan rule);
            # checkpoints written so far remain for the resume, but temp
            # spill directories must not outlive the failed run
            for store in spill_stores:
                store.release()
            wrapped = wrap_if_necessary(e)
            ctx = AnalyzerContext(
                {a: a.to_failure_metric(wrapped) for a in per_analyzer}
            )
            for g, group_analyzers in by_grouping.items():
                for a in group_analyzers:
                    ctx.metric_map[a] = a.to_failure_metric(wrapped)
            ctx.skipped_batches = list(skipped)
            return ctx

        ctx = AnalyzerContext.empty()
        pairs = []
        for a in per_analyzer:
            if a in failed:
                ctx.metric_map[a] = failed[a]
                # a failed fold's result() never runs: free its spill
                # directory now instead of waiting on GC finalizers
                _release_spill(folders[keys[a]])
            else:
                pairs.append((a, folders[keys[a]].result()))
        for g, group_analyzers in by_grouping.items():
            if g in failed_groups:
                for a in group_analyzers:
                    ctx.metric_map[a] = a.to_failure_metric(failed_groups[g])
                _release_spill(folders[group_keys[g]])
            else:
                merged = folders[group_keys[g]].result()
                pairs.extend((a, merged) for a in group_analyzers)
        _put_metrics(ctx, pairs, aggregate_with, save_states_with)
        ctx.skipped_batches = list(skipped)
        if checkpoint is not None:
            # the run completed: a later run of this directory must start
            # fresh, not resume past its own data
            checkpoint.clear()
        return ctx

    @staticmethod
    def _fuse_grouping_sets(
        data,
        by_grouping,
        aggregate_with,
        save_states_with,
        group_memory_budget,
    ) -> Dict[Tuple[str, ...], object]:
        """Cross-pass fusion pre-pass (the round-19 plan optimizer): hand
        every in-memory grouping set to ``ops.segment.fused_group_counts``
        in one call so the dense ones ride a SINGLE device dispatch.
        Returns ``{group_key: state}`` for the sets it computed; anything
        absent runs the ordinary per-set pass (which also owns the
        per-set failure-metric wrapping — fusion never converts a set
        failure into a whole-run failure)."""
        from deequ_tpu.ops.scan_plan import plan_fusion_enabled

        if not plan_fusion_enabled():
            return {}
        if len(by_grouping) < 2 or getattr(data, "is_streaming", False):
            return {}
        from deequ_tpu.spill import resolve_group_budget

        if resolve_group_budget(data, group_memory_budget) is not None:
            # budgeted runs batch/spill per set — fusion's one-vector
            # dispatch would defeat the memory bound
            return {}
        from deequ_tpu.ops.segment import GroupRequest, fused_group_counts

        keys = list(by_grouping)
        requests = []
        for g in keys:
            stats_mode = (
                aggregate_with is None
                and save_states_with is None
                and all(_count_stats_capable(a) for a in by_grouping[g])
            )
            requests.append(
                GroupRequest(tuple(g), "stats" if stats_mode else "freq")
            )
        try:
            computed = fused_group_counts(data, requests)
        except Exception:  # noqa: BLE001
            # a fault escaping the fused path's own ladder falls back to
            # the per-set passes, which surface it per analyzer
            return {}
        return {keys[i]: state for i, state in computed.items()}

    @staticmethod
    def _run_grouping_analyzers(
        data: ColumnarTable,
        grouping_columns: List[str],
        analyzers: Sequence[FrequencyBasedAnalyzer],
        aggregate_with=None,
        save_states_with=None,
        group_memory_budget=None,
        precomputed=None,
        pending=None,
    ) -> AnalyzerContext:
        """``pending``: this set's count statistics, already dispatched
        (``_EarlyCountStats``)."""
        from deequ_tpu.ops.segment import group_count_stats, group_counts_state
        from deequ_tpu.spill import resolve_group_budget

        budget = resolve_group_budget(data, group_memory_budget)

        # out-of-core: fold the frequency monoid per batch (the same
        # outer-join-sum merge used for incremental states,
        # GroupingAnalyzers.scala:127-147) as a TREE — see
        # StreamStateFolder for why a linear chain is ruinous here. Under
        # a group memory budget the fold routes through the spill store:
        # per-batch states emit as canonical sorted deltas, the tail
        # spills to sorted runs past the budget, and metric math streams
        # the k-way merge at finalize (deequ_tpu/spill). The count-stats
        # fast path needs global counts, so it does not apply batchwise.
        if getattr(data, "is_streaming", False):
            from deequ_tpu.analyzers.base import StreamStateFolder

            merged: Optional[State] = None
            store = None
            try:
                if budget is not None:
                    from deequ_tpu.spill import SpillingFrequencyStore

                    store = SpillingFrequencyStore(
                        tuple(grouping_columns), budget
                    )
                folder = StreamStateFolder(
                    spill_store=store, assume_canonical=store is not None
                )
                for batch in data.batches(columns=grouping_columns):
                    folder.add(
                        group_counts_state(
                            batch, grouping_columns,
                            canonicalize=store is not None,
                        )
                    )
                merged = folder.result()
            except Exception as e:  # noqa: BLE001
                # a failed fold must not leak its temp spill directory
                # (the context-manager contract, spill/store.py)
                if store is not None:
                    store.release()
                wrapped = wrap_if_necessary(e)
                return AnalyzerContext(
                    {a: a.to_failure_metric(wrapped) for a in analyzers}
                )
            return _put_metrics(
                AnalyzerContext.empty(), [(a, merged) for a in analyzers],
                aggregate_with, save_states_with,
            )

        # count-stats fast path: when nobody needs the materialized
        # frequency table (no state persistence/merge, and every analyzer
        # is a pure function of the count distribution), the grouping runs
        # entirely as device aggregates — group values never decode to a
        # host dict. For high-cardinality groupings this removes the
        # O(#groups) host materialization. Gated on an explicit override
        # (not hasattr, which every subclass inherits): a subclass that only
        # implements compute_from_frequencies falls back to the frequency
        # table instead of having its NotImplementedError swallowed into a
        # failure metric.
        if (
            aggregate_with is None
            and save_states_with is None
            and all(_count_stats_capable(a) for a in analyzers)
        ):
            try:
                if precomputed is not None:
                    stats = precomputed
                elif pending is not None:
                    stats = pending.result()
                else:
                    stats = group_count_stats(data, grouping_columns)
            except Exception as e:  # noqa: BLE001
                wrapped = wrap_if_necessary(e)
                return AnalyzerContext(
                    {a: a.to_failure_metric(wrapped) for a in analyzers}
                )
            return AnalyzerContext(
                {a: a.metric_from_count_stats(stats) for a in analyzers}
            )

        # budgeted in-memory table about to MATERIALIZE its frequency
        # table (state persistence or a non-count-stats analyzer): slice
        # the rows into batches sized to the budget and take the spilling
        # fold above — the in-RAM grouping state stays budget-bounded
        if budget is not None:
            from deequ_tpu.data.streaming import stream_table
            from deequ_tpu.spill import budget_batch_rows

            batch_rows = budget_batch_rows(budget)
            if data.num_rows > batch_rows:
                return AnalysisRunner._run_grouping_analyzers(
                    stream_table(data, batch_rows), grouping_columns,
                    analyzers, aggregate_with, save_states_with,
                    group_memory_budget=budget,
                )

        try:
            state: Optional[State] = (
                precomputed
                if precomputed is not None
                else group_counts_state(data, grouping_columns)
            )
        except Exception as e:  # noqa: BLE001
            wrapped = wrap_if_necessary(e)
            return AnalyzerContext(
                {a: a.to_failure_metric(wrapped) for a in analyzers}
            )
        return _put_metrics(
            AnalyzerContext.empty(), [(a, state) for a in analyzers],
            aggregate_with, save_states_with,
        )

    @staticmethod
    def run_on_aggregated_states(
        schema: Schema,
        analyzers: Sequence[Analyzer],
        state_loaders: Sequence,
        save_states_with=None,
        metrics_repository=None,
        save_or_append_results_with_key=None,
    ) -> AnalyzerContext:
        """Compute metrics purely from persisted states — no data scan
        (reference AnalysisRunner.scala:385-460)."""
        if not analyzers or not state_loaders:
            return AnalyzerContext.empty()

        passed: List[Analyzer] = []
        ctx = AnalyzerContext.empty()
        for analyzer in analyzers:
            exc = find_first_failing(schema, analyzer.preconditions())
            if exc is None:
                passed.append(analyzer)
            else:
                ctx.metric_map[analyzer] = analyzer.to_failure_metric(exc)

        for analyzer in passed:
            merged: Optional[State] = None
            try:
                for loader in state_loaders:
                    merged = merge_states(merged, loader.load(analyzer))
                if save_states_with is not None and merged is not None:
                    save_states_with.persist(analyzer, merged)
                ctx.metric_map[analyzer] = analyzer.compute_metric_from(merged)
            except Exception as e:  # noqa: BLE001
                ctx.metric_map[analyzer] = analyzer.to_failure_metric(
                    wrap_if_necessary(e)
                )

        _save_or_append_result(
            metrics_repository, save_or_append_results_with_key, ctx
        )
        return ctx
