"""VerificationSuite — the flagship entry point (reference layer L7,
VerificationSuite.scala, VerificationRunBuilder.scala, VerificationResult.scala).

    result = (VerificationSuite.on_data(table)
              .add_check(Check(CheckLevel.ERROR, "tests")
                         .is_complete("id")
                         .has_size(lambda n: n >= 100))
              .run())
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from deequ_tpu.analyzers.base import Analyzer
from deequ_tpu.analyzers.runner import AnalysisRunner, AnalyzerContext
from deequ_tpu.checks import Check, CheckLevel, CheckResult, CheckStatus
from deequ_tpu.constraints import ConstraintStatus
from deequ_tpu.data.table import ColumnarTable, Schema
from deequ_tpu.metrics import Metric
from deequ_tpu.obs.recorder import RUN_IDS, seam


@dataclass
class VerificationResult:
    """(reference VerificationResult.scala:33-119)

    ``skipped_batches`` lists the stream batch indices quarantined under
    ``on_batch_error="skip"`` — the run's metrics exclude those rows, and
    the omission is REPORTED here rather than silently dropped.

    Degradation is reported the same way:

    - ``device_events`` — the degradation decisions this run's scans made
      (OOM chunk bisections, watchdog timeouts, CPU fallbacks; the
      structured rows ``ScanStats.record_degradation`` logs);
    - ``fallback_backend`` — set (e.g. ``"cpu"``) when any scan of this
      run completed on the fallback backend instead of the accelerator;
    - ``retry_stats`` — aggregate RetryPolicy telemetry for the run
      (invocations, attempts, retries, total backoff sleep, exhaustions,
      last exception) — retries are no longer invisible to callers;
    - ``scan_stats`` — fused-scan transport telemetry for the run
      (``scan_passes``, ``device_fetches``, ``bytes_fetched``,
      ``drain_wait_seconds``; ``bytes_packed`` and, of them,
      ``staging_bytes_reused``: planes served from the staging pool,
      docs/ingest.md): the observable for the
      one-fetch-per-scan contract — for a grouping-free run,
      ``device_fetches`` exceeding ``scan_passes`` means per-chunk round
      trips somewhere (a non-device-foldable op keeps the host
      fold); grouping passes add their own bounded O(G)
      materializations.

    Mesh faults get the same reported-never-silent treatment:

    - ``mesh_events`` — the mesh-level degradation decisions of this run
      (``mesh_reshard`` / ``mesh_quarantine`` / ``mesh_straggler`` /
      ``stale_residency_evicted`` / ``peer_lost`` rows, a filtered view
      of ``device_events``);
    - ``resharded`` — True when any scan of this run completed on a
      SHRUNKEN mesh after losing chip(s); the metrics are bit-identical
      to a healthy run on that smaller mesh, but throughput was degraded;
    - ``unverified_row_ranges`` — [start, stop) global row ranges a
      degraded multi-host run (``on_peer_loss="degrade"``) completed
      WITHOUT verifying: the lost hosts' shards. Non-empty means the
      run's metrics cover a strict subset of the dataset — check statuses
      hold only for the verified rows.

    Static analysis rides the same reporting surface:

    - ``plan_lints`` — the plan-lint finding rows
      (deequ_tpu/lint/plan_lint.py) this run's scans produced when the
      lint is armed (``DEEQU_TPU_PLAN_LINT=warn|error``): each row is
      ``{rule, severity, message, location}``. Empty on a healthy run —
      ``"error"`` mode raises typed ``PlanLintError`` pre-dispatch
      instead of completing with error findings.

    Run-level governance (resilience/governance.py) reports here too:

    - ``run_budget`` — the armed RunBudget's ledger snapshot (attempts
      charged per ladder rung, elapsed wall, the exhaustion reason if
      any); empty when the run was ungoverned. A budget-exhausted run
      under ``on_budget_exhausted="degrade"`` completes as a PARTIAL
      result: the analyzers whose scans could not finish carry typed
      ``RunBudgetExhaustedException`` failure metrics and the rows never
      verified land on ``unverified_row_ranges`` (kind
      ``budget_exhausted`` in ``device_events``).

    Flight-recorder tracing (deequ_tpu/obs; armed via
    ``with_tracing()`` / ``do_verification_run(trace=...)`` /
    ``DEEQU_TPU_TRACE=1``, off by default):

    - ``run_trace`` — the compact per-phase wall breakdown of the run's
      recording (span/event counts, per-phase wall seconds — the
      dispatch/drain phase sums reconcile with
      ``scan_stats``'s ``dispatch_seconds``/``drain_wait_seconds``);
      empty when the run was untraced;
    - ``trace_recorder`` — the :class:`~deequ_tpu.obs.FlightRecorder`
      itself (export with ``deequ_tpu.obs.write_chrome_trace``); None
      when untraced."""

    status: CheckStatus
    check_results: Dict[Check, CheckResult]
    metrics: Dict[Analyzer, Metric]
    skipped_batches: List[int] = field(default_factory=list)
    device_events: List[dict] = field(default_factory=list)
    fallback_backend: Optional[str] = None
    retry_stats: Dict[str, object] = field(default_factory=dict)
    scan_stats: Dict[str, object] = field(default_factory=dict)
    mesh_events: List[dict] = field(default_factory=list)
    resharded: bool = False
    unverified_row_ranges: List[tuple] = field(default_factory=list)
    plan_lints: List[dict] = field(default_factory=list)
    run_budget: Dict[str, object] = field(default_factory=dict)
    run_trace: Dict[str, object] = field(default_factory=dict)
    trace_recorder: Optional[object] = field(
        default=None, repr=False, compare=False
    )

    @staticmethod
    def success_metrics_as_rows(
        result: "VerificationResult",
        for_analyzers: Optional[Sequence[Analyzer]] = None,
    ) -> List[dict]:
        ctx = AnalyzerContext(result.metrics)
        return AnalyzerContext.success_metrics_as_rows(ctx, for_analyzers)

    @staticmethod
    def success_metrics_as_json(
        result: "VerificationResult",
        for_analyzers: Optional[Sequence[Analyzer]] = None,
    ) -> str:
        return json.dumps(VerificationResult.success_metrics_as_rows(result, for_analyzers))

    @staticmethod
    def check_results_as_rows(result: "VerificationResult") -> List[dict]:
        rows = []
        for check, check_result in result.check_results.items():
            for cr in check_result.constraint_results:
                rows.append(
                    {
                        "check": check.description,
                        "check_level": check.level.value,
                        "check_status": check_result.status.value,
                        "constraint": str(cr.constraint),
                        "constraint_status": cr.status.value,
                        "constraint_message": cr.message or "",
                    }
                )
        return rows

    @staticmethod
    def check_results_as_json(result: "VerificationResult") -> str:
        return json.dumps(VerificationResult.check_results_as_rows(result))


#: degradation-event kinds that describe MESH-level decisions (surfaced
#: separately on VerificationResult.mesh_events)
_MESH_EVENT_KINDS = frozenset(
    (
        "mesh_reshard",
        "mesh_quarantine",
        "mesh_straggler",
        "stale_residency_evicted",
        "peer_lost",
    )
)


def _dedup_analyzers(analyzers: Sequence[Analyzer]) -> List[Analyzer]:
    """Order-preserving de-dup (reference unions into a Set)."""
    seen = set()
    unique = []
    for a in analyzers:
        if a not in seen:
            seen.add(a)
            unique.append(a)
    return unique


def _save_or_append(metrics_repository, result_key, ctx: AnalyzerContext) -> None:
    """Append ctx's metrics into the repository entry for result_key
    (reference saveOrAppendResult, VerificationSuite.scala:174-193)."""
    from deequ_tpu.repository import AnalysisResult

    existing = metrics_repository.load_by_key(result_key)
    combined = (
        (existing.analyzer_context + ctx) if existing is not None else ctx
    )
    metrics_repository.save(AnalysisResult(result_key, combined))


class VerificationSuite:
    """(reference VerificationSuite.scala:49-315)"""

    @staticmethod
    def on_data(data: ColumnarTable) -> "VerificationRunBuilder":
        return VerificationRunBuilder(data)

    @staticmethod
    def run(
        data: ColumnarTable,
        checks: Sequence[Check],
        required_analyzers: Sequence[Analyzer] = (),
    ) -> VerificationResult:
        return VerificationSuite.do_verification_run(data, checks, required_analyzers)

    @staticmethod
    def do_verification_run(
        data: ColumnarTable,
        checks: Sequence[Check],
        required_analyzers: Sequence[Analyzer] = (),
        aggregate_with=None,
        save_states_with=None,
        metrics_repository=None,
        reuse_existing_results_for_key=None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key=None,
        save_check_results_json_path: Optional[str] = None,
        save_success_metrics_json_path: Optional[str] = None,
        overwrite_output_files: bool = False,
        group_memory_budget: Optional[int] = None,
        checkpoint=None,
        on_batch_error: str = "fail",
        retry_policy=None,
        on_device_error: str = "fail",
        device_deadline: Optional[float] = None,
        shard_deadline: Optional[float] = None,
        on_peer_loss: Optional[str] = None,
        peer_timeout: Optional[float] = None,
        run_deadline: Optional[float] = None,
        max_total_attempts: Optional[int] = None,
        on_budget_exhausted: Optional[str] = None,
        trace=None,
    ) -> VerificationResult:
        """Resilience knobs (streaming tables; deequ_tpu/resilience):
        ``checkpoint`` (StreamCheckpointer or directory path) makes the
        run resumable after a crash; ``on_batch_error="skip"`` quarantines
        unreadable batches (reported on the result) instead of failing the
        run; ``retry_policy`` overrides the batch-read RetryPolicy.

        Device-fault knobs (any table; ops/device_policy.py):
        ``on_device_error="fallback"`` re-runs scans the accelerator
        cannot complete (compile failure, device loss, hang, OOM below
        the bisection floor) on the CPU backend; ``device_deadline``
        (seconds) arms the compute watchdog that converts a hung device
        call into a typed ``DeviceHangException``. Degradations taken are
        reported on ``result.device_events`` / ``result.fallback_backend``
        and retry telemetry on ``result.retry_stats``.

        Mesh-fault knobs (multi-chip meshes): chip-attributable faults
        always reshard onto the largest healthy device subset (the
        reshard -> bisect -> CPU-fallback ladder; reported on
        ``result.mesh_events`` / ``result.resharded``);
        ``shard_deadline`` (seconds) arms the per-shard straggler
        watchdog on mesh dispatches.

        Multi-host knobs: ``on_peer_loss`` (None = no peer check) runs
        ``parallel.distributed.check_peers`` INSIDE the run, before the
        analysis — ``"fail"`` raises a typed ``PeerLostException`` when a
        peer process stopped responding; ``"degrade"`` completes on the
        surviving hosts and reports the lost hosts' row ranges on
        ``result.unverified_row_ranges`` / ``result.mesh_events``.
        ``peer_timeout`` overrides the heartbeat/barrier timeout.

        Run-governance knobs (resilience/governance.py):
        ``run_deadline`` (wall seconds) / ``max_total_attempts`` arm ONE
        fault budget for the whole run — every rung of the composed
        resilience ladder (I/O retries, OOM bisections, encoded
        demotions, mesh reshards, CPU fallbacks, across every per-batch
        scan of a streaming run) charges it. On exhaustion,
        ``on_budget_exhausted="degrade"`` (default) completes with a
        PARTIAL result — failure metrics for the analyzers whose scans
        could not finish, exact ``unverified_row_ranges`` for the rows
        never verified — while ``"raise"`` propagates a typed
        ``RunBudgetExhaustedException``. The ledger lands on
        ``result.run_budget``.

        Tracing knob (deequ_tpu/obs): ``trace`` arms the flight
        recorder for THIS run — a
        :class:`~deequ_tpu.obs.FlightRecorder`, ``True`` (the
        env-armed global recorder, else a fresh run-scoped one), or
        ``False`` (suppress an env-armed one). Never process-wide: one
        traced run leaves later runs disarmed. Every engine seam of the run records typed spans/events;
        the per-phase summary lands on ``result.run_trace`` and the
        recorder on ``result.trace_recorder`` (export via
        ``deequ_tpu.obs.write_chrome_trace``). Also armable
        process-wide via ``DEEQU_TPU_TRACE=1``."""
        from deequ_tpu.obs.recorder import (
            current_recorder,
            maybe_arm_from_env,
            recording_scope,
            resolve_recorder,
        )
        from deequ_tpu.ops.scan_engine import SCAN_STATS
        from deequ_tpu.resilience.governance import (
            current_run_budget,
            resolve_run_policy,
            run_budget_scope,
        )
        from deequ_tpu.resilience.retry import RETRY_TELEMETRY

        retry_before = RETRY_TELEMETRY.snapshot()
        events_before = len(SCAN_STATS.degradation_events)
        fallback_before = SCAN_STATS.fallback_scans
        unverified_before = len(SCAN_STATS.unverified_row_ranges)
        lints_before = len(SCAN_STATS.plan_lints)
        scan_before = {
            k: getattr(SCAN_STATS, k)
            for k in (
                "scan_passes",
                "device_fetches",
                "bytes_fetched",
                "drain_wait_seconds",
                "bytes_packed",
                "staging_bytes_reused",
                "budget_charges",
                "budget_exhaustions",
            )
        }

        # run-level governance: arm ONE fault budget for the whole run
        # (unless the caller already installed an ambient one) and make
        # it the scope every charge site inside resolves — I/O retries,
        # ladder rungs, and every per-batch scan of a streaming run all
        # draw on this single ledger
        budget = current_run_budget()
        armed_here = None
        if budget is None:
            run_policy = resolve_run_policy(
                run_deadline, max_total_attempts, on_budget_exhausted
            )
            if run_policy is not None:
                budget = armed_here = run_policy.arm()

        # flight recorder: explicit ``trace`` argument > the caller's
        # ambient scope > the DEEQU_TPU_TRACE-armed global recorder. A
        # traced run records everything under the ``run`` root seam;
        # the summary lands on result.run_trace below.
        maybe_arm_from_env()
        recorder = (
            resolve_recorder(trace) if trace is not None
            else current_recorder()
        )
        # run_trace must be a per-RUN delta even on a shared/env-armed
        # recorder that outlives this run: summarize from here on
        import time as _time

        trace_since = _time.monotonic() if recorder is not None else None
        trace_dropped0 = recorder.dropped if recorder is not None else 0

        from contextlib import ExitStack

        with ExitStack() as _scopes:
            if trace is not None:
                _scopes.enter_context(recording_scope(recorder))
            # the root seam: every seam of this run opens under it and
            # carries its run_id (peer check + analysis + evaluation +
            # the repository append)
            _scopes.enter_context(seam("run", run_id=next(RUN_IDS)))
            if armed_here is not None:
                _scopes.enter_context(run_budget_scope(budget))
            with seam("plan"):
                analyzers = list(required_analyzers)
                for check in checks:
                    analyzers.extend(check.required_analyzers())
                unique_analyzers = _dedup_analyzers(analyzers)
            # the peer check runs INSIDE the run (after the telemetry
            # baseline capture) so a degraded outcome lands on THIS
            # result's unverified_row_ranges/mesh_events delta
            if on_peer_loss is not None:
                from deequ_tpu.parallel.distributed import (
                    DEFAULT_PEER_TIMEOUT,
                    check_peers,
                )

                # a count-less streaming source (StreamingTable.num_rows
                # RAISES when the source doesn't know) still gets the peer
                # check — the lost hosts just can't be mapped to row ranges
                try:
                    total_rows = int(data.num_rows or 0)
                except (AttributeError, TypeError):
                    total_rows = 0
                check_peers(
                    total_rows,
                    timeout=(
                        DEFAULT_PEER_TIMEOUT
                        if peer_timeout is None
                        else peer_timeout
                    ),
                    on_peer_loss=on_peer_loss,
                )

            analysis_context = AnalysisRunner.do_analysis_run(
                data,
                unique_analyzers,
                aggregate_with=aggregate_with,
                save_states_with=save_states_with,
                metrics_repository=metrics_repository,
                reuse_existing_results_for_key=reuse_existing_results_for_key,
                fail_if_results_missing=fail_if_results_missing,
                group_memory_budget=group_memory_budget,
                checkpoint=checkpoint,
                on_batch_error=on_batch_error,
                retry_policy=retry_policy,
                on_device_error=on_device_error,
                device_deadline=device_deadline,
                shard_deadline=shard_deadline,
            )

            # evaluate BEFORE appending the new result: anomaly
            # constraints query the repository history, which must not
            # yet contain this run (reference VerificationSuite.scala
            # evaluates at L263-281, then saves at L174-193)
            with seam("evaluate"):
                result = VerificationSuite._evaluate(
                    checks, analysis_context
                )
            if (
                metrics_repository is not None
                and save_or_append_results_with_key is not None
            ):
                with seam("repository"):
                    _save_or_append(
                        metrics_repository,
                        save_or_append_results_with_key,
                        analysis_context,
                    )
        # degradation + retry telemetry taken DURING this run (deltas
        # against the process-wide counters)
        result.device_events = [
            dict(e) for e in SCAN_STATS.degradation_events[events_before:]
        ]
        # mesh-level partial-result semantics: the mesh/peer rows of the
        # event delta, whether any scan completed on a shrunken mesh, and
        # the row ranges a degraded multi-host run left unverified
        result.mesh_events = [
            e for e in result.device_events
            if e.get("kind") in _MESH_EVENT_KINDS
        ]
        result.resharded = any(
            e.get("kind") == "mesh_reshard" for e in result.mesh_events
        )
        result.unverified_row_ranges = [
            tuple(r)
            for r in SCAN_STATS.unverified_row_ranges[unverified_before:]
        ]
        result.plan_lints = [
            dict(f) for f in SCAN_STATS.plan_lints[lints_before:]
        ]
        if SCAN_STATS.fallback_scans > fallback_before:
            result.fallback_backend = SCAN_STATS.fallback_backend
        if budget is not None:
            result.run_budget = budget.snapshot()
        if recorder is not None:
            result.run_trace = recorder.summary(
                since=trace_since, dropped_baseline=trace_dropped0
            )
            result.trace_recorder = recorder
        result.retry_stats = RETRY_TELEMETRY.delta_since(retry_before)
        result.scan_stats = {
            k: round(getattr(SCAN_STATS, k) - v, 6)
            if isinstance(v, float)
            else getattr(SCAN_STATS, k) - v
            for k, v in scan_before.items()
        }

        VerificationSuite._save_json_outputs(
            result,
            save_check_results_json_path,
            save_success_metrics_json_path,
            overwrite_output_files,
        )
        return result

    @staticmethod
    def run_on_aggregated_states(
        schema: Schema,
        checks: Sequence[Check],
        state_loaders: Sequence,
        required_analyzers: Sequence[Analyzer] = (),
        save_states_with=None,
        metrics_repository=None,
        save_or_append_results_with_key=None,
    ) -> VerificationResult:
        """Verification purely from persisted states — no data scan
        (reference VerificationSuite.scala:208-229)."""
        analyzers = list(required_analyzers)
        for check in checks:
            analyzers.extend(check.required_analyzers())
        unique_analyzers = _dedup_analyzers(analyzers)
        ctx = AnalysisRunner.run_on_aggregated_states(
            schema,
            unique_analyzers,
            state_loaders,
            save_states_with=save_states_with,
            metrics_repository=metrics_repository,
            save_or_append_results_with_key=save_or_append_results_with_key,
        )
        return VerificationSuite._evaluate(checks, ctx)

    @staticmethod
    def is_check_applicable_to_data(check: Check, schema: Schema):
        """Dry-run a check against random data matching the schema
        (reference VerificationSuite.scala:238-248)."""
        from deequ_tpu.applicability import Applicability

        return Applicability.is_check_applicable(check, schema)

    @staticmethod
    def are_analyzers_applicable_to_data(
        analyzers: Sequence[Analyzer], schema: Schema
    ):
        """(reference VerificationSuite.scala:251-261)"""
        from deequ_tpu.applicability import Applicability

        return Applicability.are_analyzers_applicable(analyzers, schema)

    @staticmethod
    def _evaluate(
        checks: Sequence[Check], analysis_context: AnalyzerContext
    ) -> VerificationResult:
        """(reference VerificationSuite.scala:263-281)"""
        check_results = {c: c.evaluate(analysis_context) for c in checks}
        if not check_results:
            status = CheckStatus.SUCCESS
        else:
            status = max(
                (r.status for r in check_results.values()),
                key=lambda s: s.severity,
            )
        return VerificationResult(
            status,
            check_results,
            dict(analysis_context.metric_map),
            list(getattr(analysis_context, "skipped_batches", ())),
        )

    @staticmethod
    def _save_json_outputs(
        result: VerificationResult,
        check_results_path: Optional[str],
        success_metrics_path: Optional[str],
        overwrite: bool,
    ) -> None:
        for path, payload in (
            (check_results_path, lambda: VerificationResult.check_results_as_json(result)),
            (success_metrics_path, lambda: VerificationResult.success_metrics_as_json(result)),
        ):
            if path is None:
                continue
            if os.path.exists(path) and not overwrite:
                continue
            with open(path, "w") as f:
                f.write(payload())


class IncrementalVerificationStream:
    """Pipelined incremental VERIFICATION — the flagship incremental
    monitoring loop (reference VerificationSuite.scala:208-229: per
    arriving batch, merge states, evaluate checks, append results),
    overlapped via the micro-batched scan pipeline
    (analyzers/incremental.py:IncrementalAnalysisStream).

    Check evaluation, repository appends, and anomaly-check assertions
    happen at drain time in strict submission order — an
    ``is_newest_point_non_anomalous`` check sees exactly the history a
    serial loop would (each batch's result is appended AFTER its own
    evaluation), so anomaly-gated monitoring works pipelined.

    Usage::

        stream = IncrementalVerificationStream(
            checks=[check], aggregate_with=states,
            save_states_with=states, metrics_repository=repo,
        )
        for key, batch in arriving:
            for done_key, result in stream.submit(batch, result_key=key):
                ...
        for done_key, result in stream.close():
            ...
    """

    def __init__(
        self,
        checks: Sequence[Check],
        required_analyzers: Sequence[Analyzer] = (),
        aggregate_with=None,
        save_states_with=None,
        metrics_repository=None,
        window: int = 8,
    ):
        from deequ_tpu.analyzers.incremental import IncrementalAnalysisStream

        self.checks = list(checks)
        analyzers = list(required_analyzers)
        for check in self.checks:
            analyzers.extend(check.required_analyzers())
        unique = _dedup_analyzers(analyzers)
        self.metrics_repository = metrics_repository
        self._stream = IncrementalAnalysisStream(
            unique,
            aggregate_with=aggregate_with,
            save_states_with=save_states_with,
            window=window,
        )

    def _finalize(self, drained):
        out = []
        for result_key, ctx in drained:
            # evaluate BEFORE appending (anomaly constraints must not see
            # their own run in the history — reference ordering)
            with seam("evaluate"):
                result = VerificationSuite._evaluate(self.checks, ctx)
            if self.metrics_repository is not None and result_key is not None:
                with seam("repository"):
                    _save_or_append(self.metrics_repository, result_key, ctx)
            out.append((result_key, result))
        return out

    def submit(self, data: ColumnarTable, result_key=None):
        """Dispatch one batch; returns finalized (result_key,
        VerificationResult) pairs for batches drained now."""
        return self._finalize(self._stream.submit(data, tag=result_key))

    def close(self):
        """Drain everything still in flight (FIFO)."""
        return self._finalize(self._stream.close())


@dataclass(frozen=True)
class AnomalyCheckConfig:
    """(reference VerificationRunBuilder.scala:336-341)"""

    level: CheckLevel
    description: str
    with_tag_values: dict = field(default_factory=dict)
    after_date: Optional[int] = None
    before_date: Optional[int] = None


class VerificationRunBuilder:
    """Fluent configuration (reference VerificationRunBuilder.scala:28-182)."""

    def __init__(self, data: ColumnarTable):
        self._data = data
        self._checks: List[Check] = []
        self._required_analyzers: List[Analyzer] = []
        self._aggregate_with = None
        self._save_states_with = None
        self._metrics_repository = None
        self._reuse_key = None
        self._fail_if_results_missing = False
        self._save_key = None
        self._check_results_path: Optional[str] = None
        self._success_metrics_path: Optional[str] = None
        self._overwrite_output_files = False
        self._group_memory_budget: Optional[int] = None
        self._checkpoint = None
        self._on_batch_error = "fail"
        self._retry_policy = None
        self._on_device_error = "fail"
        self._device_deadline: Optional[float] = None
        self._shard_deadline: Optional[float] = None
        self._on_peer_loss: Optional[str] = None
        self._peer_timeout: Optional[float] = None
        self._run_deadline: Optional[float] = None
        self._max_total_attempts: Optional[int] = None
        self._on_budget_exhausted: Optional[str] = None
        self._trace = None

    def add_check(self, check: Check) -> "VerificationRunBuilder":
        self._checks.append(check)
        return self

    def add_checks(self, checks: Sequence[Check]) -> "VerificationRunBuilder":
        self._checks.extend(checks)
        return self

    def add_required_analyzer(self, analyzer: Analyzer) -> "VerificationRunBuilder":
        self._required_analyzers.append(analyzer)
        return self

    def add_required_analyzers(self, analyzers) -> "VerificationRunBuilder":
        self._required_analyzers.extend(analyzers)
        return self

    def aggregate_with(self, state_loader) -> "VerificationRunBuilder":
        self._aggregate_with = state_loader
        return self

    def save_states_with(self, state_persister) -> "VerificationRunBuilder":
        self._save_states_with = state_persister
        return self

    def with_group_memory_budget(self, budget_bytes: int) -> "VerificationRunBuilder":
        """Bound the host RSS of grouping-state accumulation (bytes):
        past the budget, frequency tables spill to disk as sorted runs and
        stream back at finalize (deequ_tpu/spill), so uniqueness-style
        checks on high-cardinality columns degrade gracefully instead of
        OOMing. Surfaced in ScanStats (spill_runs, spill_bytes_written,
        peak_group_state_bytes)."""
        self._group_memory_budget = int(budget_bytes)
        return self

    def with_checkpoint(
        self, checkpoint, every_batches: Optional[int] = None
    ) -> "VerificationRunBuilder":
        """Make a streaming run resumable: every ``every_batches`` folded
        batches the per-analyzer fold states persist (atomic +
        checksummed) to ``checkpoint`` (a resilience.StreamCheckpointer or
        a directory path); a rerun after a crash resumes from the last
        valid checkpoint and yields bit-identical metrics
        (docs/resilience.md)."""
        from deequ_tpu.resilience.checkpoint import StreamCheckpointer

        if isinstance(checkpoint, str):
            checkpoint = StreamCheckpointer(
                checkpoint, every_batches=every_batches or 8
            )
        elif every_batches is not None:
            checkpoint.every_batches = int(every_batches)
        self._checkpoint = checkpoint
        return self

    def on_batch_error(self, policy: str) -> "VerificationRunBuilder":
        """Streaming batch-read failure policy: ``"fail"`` (default — a
        batch whose reads exhaust retries fails the run's analyzers) or
        ``"skip"`` (quarantine the batch; its index lands on
        ``VerificationResult.skipped_batches``)."""
        if policy not in ("fail", "skip"):
            raise ValueError(
                f"on_batch_error must be 'fail' or 'skip', got {policy!r}"
            )
        self._on_batch_error = policy
        return self

    def with_retry_policy(self, policy) -> "VerificationRunBuilder":
        """Override the RetryPolicy for this run's batch reads
        (resilience/retry.py; default: the table's policy, else the
        process default)."""
        self._retry_policy = policy
        return self

    def on_device_error(self, policy: str) -> "VerificationRunBuilder":
        """Device-fault policy for this run's fused scans, mirroring
        ``on_batch_error``: ``"fail"`` (default — a scan the accelerator
        cannot complete fails its analyzers with a TYPED
        ``Device*Exception`` failure metric) or ``"fallback"`` (the scan
        re-runs on the CPU backend; states are backend-agnostic monoids,
        so metrics match the accelerator's). Device OOMs bisect the chunk
        size under either policy. Degradations land on
        ``VerificationResult.device_events``."""
        if policy not in ("fail", "fallback"):
            raise ValueError(
                f"on_device_error must be 'fail' or 'fallback', "
                f"got {policy!r}"
            )
        self._on_device_error = policy
        return self

    def with_device_deadline(self, seconds: float) -> "VerificationRunBuilder":
        """Arm the compute watchdog: any blocking device call of this run
        (dispatch, drain) exceeding ``seconds`` raises a typed
        ``DeviceHangException`` — which ``on_device_error="fallback"``
        then converts into a CPU re-run — instead of hanging the run
        forever. Also settable process-wide via the
        ``DEEQU_TPU_DEVICE_DEADLINE`` env var."""
        self._device_deadline = float(seconds)
        return self

    def with_shard_deadline(self, seconds: float) -> "VerificationRunBuilder":
        """Arm the per-shard straggler watchdog on multi-chip mesh
        dispatches: a chip stalling a collective past ``seconds`` raises
        a typed ``DeviceHangException`` (recorded as a ``mesh_straggler``
        event on ``result.mesh_events``) instead of freezing the whole
        mesh. Single-device scans are unaffected. Also settable
        process-wide via the ``DEEQU_TPU_SHARD_DEADLINE`` env var."""
        self._shard_deadline = float(seconds)
        return self

    def on_peer_loss(
        self, policy: str, timeout: Optional[float] = None
    ) -> "VerificationRunBuilder":
        """Multi-host peer-loss policy, checked INSIDE the run (no-op on
        single-host): ``"fail"`` raises a typed ``PeerLostException``
        when a peer process stopped responding (heartbeat + barrier over
        jax.distributed); ``"degrade"`` completes the run on the
        surviving hosts and reports the lost hosts' ``host_row_range``
        slices on ``result.unverified_row_ranges`` — partial coverage is
        reported, never silent. ``timeout`` (seconds) overrides the
        probe's heartbeat/barrier deadline."""
        if policy not in ("fail", "degrade"):
            raise ValueError(
                f"on_peer_loss must be 'fail' or 'degrade', got {policy!r}"
            )
        self._on_peer_loss = policy
        if timeout is not None:
            self._peer_timeout = float(timeout)
        return self

    def with_run_budget(
        self,
        run_deadline: Optional[float] = None,
        max_total_attempts: Optional[int] = None,
        on_budget_exhausted: str = "degrade",
    ) -> "VerificationRunBuilder":
        """Arm ONE run-level fault budget for this run
        (resilience/governance.py): ``run_deadline`` bounds the run's
        wall clock, ``max_total_attempts`` bounds the failure-driven
        attempts of the COMPOSED resilience ladder — I/O retries, OOM
        bisections, encoded demotions, mesh reshards, and CPU fallbacks
        all charge this single ledger (a streaming run's per-batch scans
        included), where previously each rung only bounded itself. On
        exhaustion ``"degrade"`` (default) completes with a partial
        result — failure metrics plus exact
        ``result.unverified_row_ranges`` — and ``"raise"`` propagates a
        typed ``RunBudgetExhaustedException``. Also settable
        process-wide via ``DEEQU_TPU_RUN_DEADLINE`` /
        ``DEEQU_TPU_RUN_ATTEMPTS``. The spent ledger is reported on
        ``result.run_budget``."""
        if run_deadline is None and max_total_attempts is None:
            raise ValueError(
                "with_run_budget needs run_deadline and/or "
                "max_total_attempts"
            )
        if on_budget_exhausted not in ("degrade", "raise"):
            raise ValueError(
                f"on_budget_exhausted must be 'degrade' or 'raise', "
                f"got {on_budget_exhausted!r}"
            )
        self._run_deadline = (
            float(run_deadline) if run_deadline is not None else None
        )
        self._max_total_attempts = (
            int(max_total_attempts) if max_total_attempts is not None
            else None
        )
        self._on_budget_exhausted = on_budget_exhausted
        return self

    def with_tracing(
        self, recorder=None, capacity: Optional[int] = None
    ) -> "VerificationRunBuilder":
        """Arm the flight recorder (deequ_tpu/obs) for this run: every
        engine seam — program trace, plan lint, staging, dispatch,
        drain, fault-ladder rungs, budget charges — records typed
        spans/events. Pass a :class:`~deequ_tpu.obs.FlightRecorder` to
        share one across runs, or let this create a fresh one
        (``capacity`` bounds its ring buffer). The per-phase summary
        lands on ``result.run_trace`` and the recorder on
        ``result.trace_recorder`` — export with
        ``deequ_tpu.obs.write_chrome_trace(result.trace_recorder,
        path)``. Tracing is otherwise OFF; also armable process-wide
        via ``DEEQU_TPU_TRACE=1``."""
        from deequ_tpu.obs.recorder import FlightRecorder

        if recorder is None:
            recorder = (
                FlightRecorder(capacity=capacity)
                if capacity is not None
                else FlightRecorder()
            )
        elif capacity is not None:
            raise ValueError(
                "pass either an existing recorder or a capacity, not both"
            )
        self._trace = recorder
        return self

    def save_check_results_json_to_path(self, path: str) -> "VerificationRunBuilder":
        self._check_results_path = path
        return self

    def save_success_metrics_json_to_path(self, path: str) -> "VerificationRunBuilder":
        self._success_metrics_path = path
        return self

    def overwrite_previous_files(self, overwrite: bool) -> "VerificationRunBuilder":
        # reference has a self-assignment bug here (VerificationRunBuilder.
        # scala:287); we implement the intended behavior
        self._overwrite_output_files = overwrite
        return self

    def use_repository(self, repository) -> "VerificationRunBuilderWithRepository":
        return VerificationRunBuilderWithRepository(self, repository)

    def run(self) -> VerificationResult:
        return VerificationSuite.do_verification_run(
            self._data,
            self._checks,
            self._required_analyzers,
            aggregate_with=self._aggregate_with,
            save_states_with=self._save_states_with,
            metrics_repository=self._metrics_repository,
            reuse_existing_results_for_key=self._reuse_key,
            fail_if_results_missing=self._fail_if_results_missing,
            save_or_append_results_with_key=self._save_key,
            save_check_results_json_path=self._check_results_path,
            save_success_metrics_json_path=self._success_metrics_path,
            overwrite_output_files=self._overwrite_output_files,
            group_memory_budget=self._group_memory_budget,
            checkpoint=self._checkpoint,
            on_batch_error=self._on_batch_error,
            retry_policy=self._retry_policy,
            on_device_error=self._on_device_error,
            device_deadline=self._device_deadline,
            shard_deadline=self._shard_deadline,
            on_peer_loss=self._on_peer_loss,
            peer_timeout=self._peer_timeout,
            run_deadline=self._run_deadline,
            max_total_attempts=self._max_total_attempts,
            on_budget_exhausted=self._on_budget_exhausted,
            trace=self._trace,
        )


class VerificationRunBuilderWithRepository(VerificationRunBuilder):
    """(reference VerificationRunBuilder.scala:184-244)"""

    def __init__(self, base: VerificationRunBuilder, repository):
        super().__init__(base._data)
        self.__dict__.update(base.__dict__)
        self._metrics_repository = repository

    def reuse_existing_results_for_key(
        self, result_key, fail_if_results_missing: bool = False
    ) -> "VerificationRunBuilderWithRepository":
        self._reuse_key = result_key
        self._fail_if_results_missing = fail_if_results_missing
        return self

    def save_or_append_result(self, result_key) -> "VerificationRunBuilderWithRepository":
        self._save_key = result_key
        return self

    def add_anomaly_check(
        self,
        anomaly_detection_strategy,
        analyzer: Analyzer,
        anomaly_check_config: Optional[AnomalyCheckConfig] = None,
    ) -> "VerificationRunBuilderWithRepository":
        """(reference VerificationRunBuilder.scala:227-243)"""
        config = anomaly_check_config or AnomalyCheckConfig(
            CheckLevel.WARNING,
            f"Anomaly check for {analyzer!r}",
        )
        check = Check(config.level, config.description).is_newest_point_non_anomalous(
            self._metrics_repository,
            anomaly_detection_strategy,
            analyzer,
            config.with_tag_values,
            config.after_date,
            config.before_date,
        )
        self._checks.append(check)
        return self
