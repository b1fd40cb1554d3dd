"""Pipelined incremental analysis — deequ's signature workflow, overlapped.

The reference's incremental loop (VerificationSuite.scala:208-229, the
partitioned-update example) processes arriving batches strictly serially:
scan batch N, merge states, evaluate, then start batch N+1. On TPU the
scan is microseconds of device compute; the loop is bound by per-batch
dispatch/fetch round trips (~1 ms each on the v5e host, chip_smoke.py,
PR 21).

``IncrementalAnalysisStream`` amortizes those round trips by
MICRO-BATCHING: up to ``window`` arriving batches pack into one
(K, chunk) buffer stack and run as ONE vmapped fused program with ONE
fetch (ops/scan_engine.py:run_scan_group) — per-batch results are
bit-identical to K separate scans (same pure per-chunk function, vmapped).
Workloads the group path cannot take (string columns, multi-chunk
batches, an active device mesh, mixed schemas) fall back to per-batch
deferred scans that still overlap dispatch with the previous group's
drain.

Host-side finalization (monoid state merge via ``aggregate_with``/
``save_states_with``, metric evaluation) happens at drain time in strict
submission order, so incremental state chains remain exactly equal to the
serial path (tests/test_incremental.py::test_pipelined_stream_equals_serial).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

from deequ_tpu.analyzers.base import (
    Analyzer,
    ScanShareableAnalyzer,
    find_first_failing,
)
from deequ_tpu.analyzers.runner import AnalysisRunner, AnalyzerContext


class _Submission:
    __slots__ = ("tag", "data", "ctx", "scanning", "non_scan")

    def __init__(self, tag, data, ctx, scanning, non_scan):
        self.tag = tag
        self.data = data
        self.ctx = ctx  # precondition-failure metrics
        self.scanning = scanning
        self.non_scan = non_scan


class IncrementalAnalysisStream:
    """Sliding-window micro-batch pipeline over arriving batches.

    Usage::

        stream = IncrementalAnalysisStream(
            analyzers, aggregate_with=states, save_states_with=states,
            window=8,
        )
        for key, batch in batches:
            for done_key, ctx in stream.submit(batch, tag=key):
                repository.save(AnalysisResult(done_key, ctx))
        for done_key, ctx in stream.close():
            repository.save(AnalysisResult(done_key, ctx))

    ``window`` is the micro-batch group size; host memory stays bounded
    by ~2 x window x batch (one group filling, one in flight).
    """

    def __init__(
        self,
        analyzers: Sequence[Analyzer],
        aggregate_with=None,
        save_states_with=None,
        window: int = 8,
    ):
        self.analyzers = list(analyzers)
        self.aggregate_with = aggregate_with
        self.save_states_with = save_states_with
        self.window = max(1, int(window))
        self._buffer: List[_Submission] = []
        # dispatched groups: (entries, scannable, plan, scan_handle, kind)
        # kind: "group" (DeferredGroupScan), "per-batch" (list of
        # per-entry (ctx, scannable, plan, DeferredScan))
        self._groups: List[Tuple] = []

    def submit(self, data, tag: Any = None) -> List[Tuple[Any, AnalyzerContext]]:
        """Buffer one batch; dispatch a group when the window fills.
        Returns finalized (tag, ctx) pairs for any drained batches."""
        from deequ_tpu.analyzers.runner import _is_grouping_shared

        passed: List[Analyzer] = []
        failure_ctx = AnalyzerContext.empty()
        for analyzer in self.analyzers:
            exc = find_first_failing(data.schema, analyzer.preconditions())
            if exc is None:
                passed.append(analyzer)
            else:
                failure_ctx.metric_map[analyzer] = analyzer.to_failure_metric(
                    exc
                )
        scanning = [
            a
            for a in passed
            if isinstance(a, ScanShareableAnalyzer)
            and not _is_grouping_shared(a)
        ]
        non_scan = [a for a in passed if a not in scanning]
        self._buffer.append(
            _Submission(tag, data, failure_ctx, scanning, non_scan)
        )

        out: List[Tuple[Any, AnalyzerContext]] = []
        if len(self._buffer) >= self.window:
            self._dispatch_buffered()
            # keep at most one group in flight behind the one just
            # dispatched: drain older groups now
            while len(self._groups) > 1:
                out.extend(self._drain_oldest_group())
        return out

    def close(self) -> List[Tuple[Any, AnalyzerContext]]:
        """Dispatch any buffered batches and drain everything (FIFO)."""
        if self._buffer:
            self._dispatch_buffered()
        out: List[Tuple[Any, AnalyzerContext]] = []
        while self._groups:
            out.extend(self._drain_oldest_group())
        return out

    # -- internals ----------------------------------------------------------

    def _dispatch_buffered(self) -> None:
        from deequ_tpu.exceptions import wrap_if_necessary
        from deequ_tpu.ops.scan_engine import group_scannable, run_scan_group
        from deequ_tpu.parallel.mesh import current_mesh

        entries = self._buffer
        self._buffer = []

        # the fast path needs every entry to share one scanning-analyzer
        # set (ops are built once, from the first table)
        same_scanning = all(
            e.scanning == entries[0].scanning for e in entries
        )
        if same_scanning and entries[0].scanning and len(entries) > 1:
            first = entries[0]
            ops, scannable, op_fail = AnalysisRunner._build_scan_ops(
                first.data, first.scanning
            )
            tables = [e.data for e in entries]
            shared_layout = (
                group_scannable(tables, ops, current_mesh())
                if scannable
                else False
            )
            if shared_layout:
                try:
                    exec_ops, plan = AnalysisRunner._coalesce_scan_ops(ops)
                    scan = run_scan_group(
                        tables, exec_ops, defer=True, layout=shared_layout
                    )
                except Exception as e:  # noqa: BLE001 — dispatch failure
                    # maps onto every scanning analyzer of every entry
                    wrapped = wrap_if_necessary(e)
                    for entry in entries:
                        for a in scannable:
                            entry.ctx.metric_map[a] = a.to_failure_metric(
                                wrapped
                            )
                        for a, err in op_fail.items():
                            entry.ctx.metric_map[a] = a.to_failure_metric(err)
                    self._groups.append((entries, [], [], None, "group"))
                    return
                for entry in entries:
                    for a, err in op_fail.items():
                        entry.ctx.metric_map[a] = a.to_failure_metric(err)
                self._groups.append(
                    (entries, scannable, plan, scan, "group")
                )
                return

        # fallback: per-batch deferred scans (still pipelined); streaming
        # tables cannot defer (their scan pipelines internally and folds
        # eagerly) so they run synchronously here
        per_batch = []
        for entry in entries:
            ctx, scannable, plan, scan = (
                AnalysisRunner._dispatch_scanning_analyzers(
                    entry.data, entry.scanning,
                    defer=not getattr(entry.data, "is_streaming", False),
                )
            )
            entry.ctx += ctx
            per_batch.append((scannable, plan, scan))
        self._groups.append((entries, None, None, per_batch, "per-batch"))

    def _drain_oldest_group(self) -> List[Tuple[Any, AnalyzerContext]]:
        from deequ_tpu.exceptions import wrap_if_necessary

        entries, scannable, plan, scan, kind = self._groups.pop(0)
        out: List[Tuple[Any, AnalyzerContext]] = []
        if kind == "group":
            results_per_table: Optional[list] = None
            if scan is not None:
                try:
                    results_per_table = scan.results()
                except Exception as e:  # noqa: BLE001
                    wrapped = wrap_if_necessary(e)
                    for entry in entries:
                        for a in scannable:
                            entry.ctx.metric_map[a] = a.to_failure_metric(
                                wrapped
                            )
            for k, entry in enumerate(entries):
                ctx = entry.ctx
                if results_per_table is not None:
                    ctx = AnalysisRunner._finalize_scanning_analyzers(
                        ctx, scannable, plan, results_per_table[k],
                        self.aggregate_with, self.save_states_with,
                    )
                out.append((entry.tag, self._finish_entry(entry, ctx)))
        else:
            # one coalesced fetch for all the group's per-batch deferred
            # scans (fetch_deferred): result() below is then free
            from deequ_tpu.ops.scan_engine import DeferredScan, fetch_deferred

            deferreds = [
                e_scan
                for (_, _, e_scan) in scan
                if isinstance(e_scan, DeferredScan)
            ]
            try:
                fetch_deferred(deferreds)
            except Exception:  # noqa: BLE001 — surfaced per scan below
                pass
            for entry, (e_scannable, e_plan, e_scan) in zip(entries, scan):
                ctx = entry.ctx
                if e_scan is not None:
                    try:
                        results = (
                            e_scan.result()
                            if hasattr(e_scan, "result")
                            else e_scan
                        )
                    except Exception as e:  # noqa: BLE001
                        wrapped = wrap_if_necessary(e)
                        for a in e_scannable:
                            ctx.metric_map[a] = a.to_failure_metric(wrapped)
                        results = None
                    if results is not None:
                        ctx = AnalysisRunner._finalize_scanning_analyzers(
                            ctx, e_scannable, e_plan, results,
                            self.aggregate_with, self.save_states_with,
                        )
                out.append((entry.tag, self._finish_entry(entry, ctx)))
        return out

    def _finish_entry(self, entry: _Submission, ctx) -> AnalyzerContext:
        if entry.non_scan:
            # grouping/own-pass analyzers run their own passes at drain
            # time; order stays strictly FIFO so state chains match the
            # serial path
            ctx += AnalysisRunner.do_analysis_run(
                entry.data, entry.non_scan,
                aggregate_with=self.aggregate_with,
                save_states_with=self.save_states_with,
            )
        return ctx
