"""The window fold axis: every open pane advances in ONE dispatch per batch.

A :class:`WindowedStream` turns the one-shot fused scan into continuous
windowed verification over an unbounded stream. Each arriving batch is
staged once and run through a single jitted pane program whose output is
the (W, leaves) block of per-pane monoid partials — sliding/tumbling
event-time windows are an extra fold DIMENSION of the device program
(the window fold axis, TiLT arXiv:2301.12030; Flare arXiv:1703.08219
motivates keeping advancement inside the one-dispatch/one-fetch
contract), never W host loops. Fold-tag semantics are preserved per
pane (sum/min/max leaves, exactly the scan engine's
``KNOWN_FOLD_TAGS`` subset), so per-window metrics are bit-identical to
a one-shot run over the same rows: pane leaves feed the analyzers' own
``state_from_scan_result`` / ``compute_metric_from`` path, and checks
evaluate through ``VerificationSuite._evaluate``.

Watermark + late data: the per-stream watermark is monotone
(``max(watermark, max_event_time - lag)``); window closes are fenced by
it, and rows older than it route by the typed policy — ``drop`` (counted
on ``ScanStats.late_rows``), ``side_output`` (batch-aligned row ranges
quarantined on the partial-result surface via
``ScanStats.record_unverified``), ``refuse`` (typed
:class:`~deequ_tpu.exceptions.LateDataException`; the batch is refused
atomically, state unchanged).

Crash safety: pane accumulators + watermark + the emitted-window ledger
persist through :class:`~deequ_tpu.windows.state.WindowStateStore`
(checksummed, atomic, versioned). The close fence (``closed_through``)
is persisted BEFORE a close emits, so a SIGKILL'd stream resumed from
any snapshot re-emits NOTHING: replayed closes at or below the fence
are suppressed (counted, never re-observed by the repository/monitor) —
window-close alerts are exactly-once through double resume. When the
state store itself is refusing writes, the engine keeps emitting
(availability) and COUNTS the unpersisted fence advance
(``state_save_failures``) — degraded resumability is reported, never
silent.

The pane program is cached module-wide by (analyzer signature, window
geometry, batch/pane shape) — a thousand streams with the same shape
share ONE trace — and lints under the ``plan-window-refeed`` rule
(lint/plan_lint.py) when DEEQU_TPU_PLAN_LINT is armed, with the window
signature folded into the lint memo key.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.exceptions import LateDataException
from deequ_tpu.windows.spec import (
    WatermarkPolicy,
    WindowSpec,
    resolve_watermark_policy,
    resolve_window_spec,
)
from deequ_tpu.windows.state import (
    WindowState,
    WindowStateStore,
    stream_fingerprint,
)

_POS_INF = float("inf")
_NEG_INF = float("-inf")

#: host-side merge per fold tag (tiny per-pane scalars; the association
#: is the running left fold itself, so checkpoint/resume is bit-identical)
_MERGE: Dict[str, Callable[[float, float], float]] = {
    "sum": lambda a, b: a + b,
    "min": min,
    "max": max,
}


class _WindowStats:
    """Process-global windowed-verification counters (the obs registry's
    ``windows`` section reads these through; bench asserts the
    one-dispatch-per-batch contract on ``pane_dispatches``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        # one per processed batch, regardless of open-pane count — the
        # O(1)-dispatches observable behind config 13
        self.pane_dispatches = 0
        self.panes_opened = 0
        self.panes_closed = 0
        # emitted closes vs closes a resumed replay suppressed (the
        # exactly-once pair) vs closes the brownout shed typed
        self.closes_emitted = 0
        self.closes_suppressed = 0
        self.window_sheds = 0
        self.late_rows = 0
        self.side_output_ranges = 0
        self.refused_batches = 0
        self.stream_resumes = 0
        self.programs_built = 0
        self.state_saves = 0
        self.state_save_failures = 0

    @property
    def open_panes(self) -> int:
        return self.panes_opened - self.panes_closed

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                k: v for k, v in self.__dict__.items()
                if not k.startswith("_")
            }
        snap["open_panes"] = self.open_panes
        return snap

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + int(by))


WINDOW_STATS = _WindowStats()


# -- pane-op derivation ------------------------------------------------------

#: analyzer families with EXACT pane folds (order-insensitive monoid
#: merges over sum/min/max leaves): anything else would break the
#: bit-identical-to-one-shot contract and is refused typed at
#: registration, never silently approximated
SUPPORTED_ANALYZERS = (
    "Size", "Completeness", "Sum", "Minimum", "Maximum", "Mean",
)


def pane_signature(analyzers: Sequence[Any]) -> Tuple[Tuple[str, Optional[str]], ...]:
    """The pane program's identity for one analyzer set: (family, column)
    per analyzer. Raises typed ValueError for unsupported families or
    filtered (``where=``) analyzers — a stream never starts with an
    analyzer its pane fold cannot reproduce bit-identically."""
    sig = []
    for a in analyzers:
        kind = type(a).__name__
        if kind not in SUPPORTED_ANALYZERS:
            raise ValueError(
                f"analyzer {a} is not supported on the window fold axis: "
                f"pane folds are exact only for {list(SUPPORTED_ANALYZERS)}"
            )
        if getattr(a, "where", None) is not None:
            raise ValueError(
                f"analyzer {a} carries a where= filter; filtered pane "
                "folds are not supported on the window fold axis"
            )
        sig.append((kind, getattr(a, "column", None)))
    return tuple(sig)


def _leaf_plans(sig) -> List[Tuple[int, Optional[str], str, Dict[str, str]]]:
    """Per-analyzer leaf layout: (index, column, family, {leaf: fold tag})."""
    plans = []
    for i, (kind, col) in enumerate(sig):
        if kind == "Size":
            tags = {"n": "sum"}
        elif kind == "Completeness":
            tags = {"matches": "sum", "count": "sum"}
        elif kind == "Sum":
            tags = {"sum": "sum", "n": "sum"}
        elif kind == "Minimum":
            tags = {"value": "min", "n": "sum"}
        elif kind == "Maximum":
            tags = {"value": "max", "n": "sum"}
        else:  # Mean
            tags = {"sum": "sum", "count": "sum"}
        plans.append((i, col, kind, tags))
    return plans


def leaf_tags(sig) -> Dict[str, str]:
    """Flat leaf key ("<i>:<name>") -> fold tag for one signature."""
    out: Dict[str, str] = {}
    for i, _col, _kind, tags in _leaf_plans(sig):
        for name, tag in tags.items():
            out[f"{i}:{name}"] = tag
    return out


def _data_columns(sig) -> Tuple[str, ...]:
    return tuple(sorted({col for _kind, col in sig if col is not None}))


def _make_step(sig, size_s: float, data_cols: Tuple[str, ...]):
    """Build the UNJITTED pane step: flat args -> {leaf key: (W,) f64}
    plus the late-row census. One call advances EVERY open pane."""
    import jax.numpy as jnp

    plans = _leaf_plans(sig)
    k = len(data_cols)

    def step(times, starts, fence, *flat):
        data = dict(zip(data_cols, flat[:k]))
        valid = dict(zip(data_cols, flat[k:]))
        live = times >= fence
        within = (
            (times[None, :] >= starts[:, None])
            & (times[None, :] < starts[:, None] + size_s)
        )
        member = within & live[None, :]
        out = {}
        for i, col, kind, _tags in plans:
            if kind == "Size":
                out[f"{i}:n"] = jnp.sum(member, axis=1, dtype=jnp.float64)
                continue
            ok = member & valid[col][None, :]
            if kind == "Completeness":
                out[f"{i}:matches"] = jnp.sum(ok, axis=1, dtype=jnp.float64)
                out[f"{i}:count"] = jnp.sum(member, axis=1, dtype=jnp.float64)
            elif kind in ("Sum", "Mean"):
                total = jnp.sum(
                    jnp.where(ok, data[col][None, :], 0.0), axis=1,
                    dtype=jnp.float64,
                )
                if kind == "Sum":
                    out[f"{i}:sum"] = total
                    out[f"{i}:n"] = jnp.sum(ok, axis=1, dtype=jnp.float64)
                else:
                    out[f"{i}:sum"] = total
                    out[f"{i}:count"] = jnp.sum(ok, axis=1, dtype=jnp.float64)
            elif kind == "Minimum":
                out[f"{i}:value"] = jnp.min(
                    jnp.where(ok, data[col][None, :], _POS_INF), axis=1
                )
                out[f"{i}:n"] = jnp.sum(ok, axis=1, dtype=jnp.float64)
            else:  # Maximum
                out[f"{i}:value"] = jnp.max(
                    jnp.where(ok, data[col][None, :], _NEG_INF), axis=1
                )
                out[f"{i}:n"] = jnp.sum(ok, axis=1, dtype=jnp.float64)
        out["__late__"] = jnp.sum(times < fence, dtype=jnp.float64)
        return out

    return step


# the module-wide pane-program cache: streams sharing an analyzer
# signature + geometry share ONE trace (a ~1k-stream fleet pays one
# compile, the config-13 premise)
_PROGRAM_LOCK = threading.Lock()
_PROGRAM_CACHE: Dict[tuple, Any] = {}


def clear_program_cache() -> None:
    with _PROGRAM_LOCK:
        _PROGRAM_CACHE.clear()


def _pane_program(
    sig,
    spec: WindowSpec,
    policy: WatermarkPolicy,
    n: int,
    w: int,
):
    """The jitted pane step for (signature, geometry, batch rows, pane
    bucket) — built once, linted once (plan-window-refeed) when the plan
    lint is armed, then shared across every stream with this shape."""
    import jax

    data_cols = _data_columns(sig)
    key = (sig, spec.signature(), policy.signature(), n, w)
    with _PROGRAM_LOCK:
        prog = _PROGRAM_CACHE.get(key)
    if prog is not None:
        return prog

    step = _make_step(sig, spec.size_s, data_cols)
    jitted = jax.jit(step)

    from deequ_tpu.lint.plan_lint import plan_lint_mode

    mode = plan_lint_mode(None)
    if mode != "off":
        from deequ_tpu.lint.plan_lint import enforce_plan_lint, lint_plan_cached
        from deequ_tpu.ops.scan_engine import SCAN_STATS
        from deequ_tpu.ops.scan_plan import plan_windowed_scan

        tags = leaf_tags(sig)
        plan_ir = plan_windowed_scan(
            fold_tags=tuple(tags[k] for k in sorted(tags)),
            panes=w,
            window_spec=spec.signature(),
            watermark_policy=policy.signature(),
        )
        f64 = np.float64
        avals = [
            jax.ShapeDtypeStruct((n,), f64),   # times
            jax.ShapeDtypeStruct((w,), f64),   # pane starts
            jax.ShapeDtypeStruct((), f64),     # watermark fence
        ]
        avals += [jax.ShapeDtypeStruct((n,), f64) for _ in data_cols]
        avals += [jax.ShapeDtypeStruct((n,), np.bool_) for _ in data_cols]
        # the memo key carries the window signature: the same analyzer
        # set under a different geometry lints fresh (plan-window-refeed
        # checks the declared spec itself)
        memo_key = ("windowed", sig, spec.signature(), policy.signature(), n, w)
        findings, traced = lint_plan_cached(plan_ir, step, tuple(avals), memo_key)
        if traced:
            SCAN_STATS.plan_lint_traces += 1
        if findings:
            SCAN_STATS.plan_lints.extend(f.as_dict() for f in findings)
        enforce_plan_lint(findings, mode)

    with _PROGRAM_LOCK:
        existing = _PROGRAM_CACHE.get(key)
        if existing is not None:
            return existing
        _PROGRAM_CACHE[key] = jitted
        WINDOW_STATS.programs_built += 1
    return jitted


def _fetch_leaves(out) -> Dict[str, np.ndarray]:
    """The ONE device->host materialization per batch (the windowed
    analogue of the scan engine's one-fetch contract) — charged to the
    fetch telemetry via ``SCAN_STATS.record_fetch``."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    host = {k: np.asarray(v) for k, v in out.items()}
    SCAN_STATS.record_fetch(sum(a.nbytes for a in host.values()))
    return host


# -- the stream --------------------------------------------------------------


@dataclass(frozen=True)
class WindowClose:
    """One pane leaving the open set. Exactly one of the flags explains
    what happened: ``emitted`` (verdict delivered), ``suppressed`` (a
    resumed replay hit the exactly-once fence), ``shed`` (the brownout
    dropped a late close, typed)."""

    stream: str
    start: float
    end: float
    emitted: bool
    suppressed: bool
    shed: bool
    result: Optional[Any]  # VerificationResult when emitted


class WindowedStream:
    """Continuous windowed verification over one unbounded stream.

    Feed host batches (``{column: np.ndarray}``; float columns use NaN
    for nulls, the event-time column must be finite) through
    :meth:`process_batch`; each call is one device dispatch and returns
    the :class:`WindowClose` records the advancing watermark produced.
    Construct with the same ``state_dir`` after a SIGKILL and the stream
    resumes mid-window bit-identically from the newest valid snapshot
    (re-feed batches from :attr:`next_batch_index`).
    """

    def __init__(
        self,
        stream_id: str,
        analyzers: Sequence[Any],
        checks: Sequence[Any] = (),
        spec: Optional[WindowSpec] = None,
        policy: Optional[WatermarkPolicy] = None,
        time_column: Optional[str] = None,
        state_dir: Optional[str] = None,
        checkpoint_every: int = 4,
        batch_rows: Optional[int] = None,
        repository=None,
        monitor=None,
        slo=None,
        should_shed: Optional[Callable[[Any, float], bool]] = None,
        budget=None,
        retry=None,
    ):
        if not analyzers:
            raise ValueError("a windowed stream needs at least one analyzer")
        if checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        self.stream_id = str(stream_id)
        self.analyzers = tuple(analyzers)
        self.checks = tuple(checks)
        self.spec = resolve_window_spec(spec, time_column or "ts")
        if time_column is not None and self.spec.time_column != time_column:
            raise ValueError(
                f"time_column {time_column!r} conflicts with "
                f"spec.time_column {self.spec.time_column!r}"
            )
        self.policy = resolve_watermark_policy(policy)
        self.sig = pane_signature(self.analyzers)
        self._tags = leaf_tags(self.sig)
        self.checkpoint_every = int(checkpoint_every)
        self.batch_rows = batch_rows
        self.repository = repository
        self.monitor = monitor
        self.slo = slo
        self.should_shed = should_shed
        self.budget = budget
        self.fingerprint = stream_fingerprint(
            self.stream_id,
            [f"{k}:{c}" for k, c in self.sig],
            self.spec.signature(),
            self.policy.signature(),
            batch_rows,
        )
        self._state = WindowState()
        self._rows_seen = 0
        self.resumed = False
        self._store = None
        if state_dir is not None:
            self._store = WindowStateStore(state_dir, retry=retry)
            recovered = self._store.load_latest(self.fingerprint)
            if recovered is not None:
                self._state = recovered
                self._rows_seen = recovered.batch_index * (batch_rows or 0)
                self.resumed = True
                WINDOW_STATS.inc("stream_resumes")

    # -- introspection ----------------------------------------------------

    @property
    def next_batch_index(self) -> int:
        """First batch index NOT yet folded — a resumed driver re-feeds
        the stream from here."""
        return self._state.batch_index

    @property
    def watermark(self) -> float:
        return self._state.watermark

    @property
    def closed_through(self) -> float:
        return self._state.closed_through

    @property
    def open_panes(self) -> List[float]:
        return sorted(self._state.panes)

    @property
    def emitted_windows(self) -> List[float]:
        return list(self._state.emitted)

    @property
    def late_rows(self) -> int:
        return self._state.late_rows

    @property
    def side_ranges(self) -> List[Tuple[int, int]]:
        return list(self._state.side_ranges)

    @property
    def sheds(self) -> List[Tuple[float, str]]:
        return list(self._state.shed)

    # -- the batch step ---------------------------------------------------

    def process_batch(
        self, batch: Dict[str, Any], row_start: Optional[int] = None
    ) -> List[WindowClose]:
        """Fold one batch (ONE device dispatch across every open pane),
        advance the watermark, and close every pane it fences off."""
        times = self._event_times(batch)
        n = times.shape[0]
        start_row = self._rows_seen if row_start is None else int(row_start)
        fence = self._state.watermark

        late = int(np.sum(times < fence))
        if late:
            self._route_late(times, fence, late, start_row, n)

        starts = self._pane_starts(times, fence)
        if starts:
            leaves = self._dispatch(batch, times, starts, fence)
            self._merge(starts, leaves)
        self._rows_seen = start_row + n
        self._state.batch_index += 1

        if n:
            batch_max = float(np.max(times))
            advanced = max(self._state.watermark, batch_max - self.policy.lag_s)
            self._state.watermark = advanced
        closes = self._close_ready(self._state.watermark)

        if self._store is not None and not closes:
            # close paths already persisted the fence; otherwise honor
            # the periodic cadence
            if self._state.batch_index % self.checkpoint_every == 0:
                self._save()
        return closes

    def flush(self) -> List[WindowClose]:
        """End-of-stream: close every remaining open pane (the watermark
        jumps to +inf). Unbounded streams never call this."""
        self._state.watermark = _POS_INF
        return self._close_ready(_POS_INF)

    # -- internals --------------------------------------------------------

    def _event_times(self, batch) -> np.ndarray:
        col = self.spec.time_column
        if col not in batch:
            raise ValueError(
                f"stream {self.stream_id!r}: batch is missing the event-time "
                f"column {col!r}"
            )
        times = np.array(batch[col], dtype=np.float64, copy=False)  # deequ-lint: ignore[host-fetch] -- host batch input, no device round trip
        if times.ndim != 1:
            raise ValueError("event-time column must be 1-D")
        if times.size and not np.all(np.isfinite(times)):
            raise ValueError(
                f"stream {self.stream_id!r}: event-time column {col!r} has "
                "non-finite entries; every row needs a valid event time"
            )
        return times

    def _route_late(self, times, fence, late, start_row, n) -> None:
        from deequ_tpu.ops.scan_engine import SCAN_STATS

        policy = self.policy.late_policy
        if policy == "refuse":
            WINDOW_STATS.inc("refused_batches")
            oldest = float(np.min(times[times < fence]))
            raise LateDataException(
                f"stream {self.stream_id!r}: {late} row(s) behind the "
                f"watermark {fence} (oldest event time {oldest}) under the "
                "'refuse' late policy; the batch was refused atomically",
                stream=self.stream_id, late_rows=late,
                watermark=fence, oldest_event_time=oldest,
            )
        self._state.late_rows += late
        WINDOW_STATS.inc("late_rows", late)
        SCAN_STATS.record_late_rows(late)
        if policy == "side_output":
            # batch-aligned quarantine on the partial-result surface:
            # the range is REPORTED (unverified_row_ranges), never silent
            self._state.side_ranges.append((start_row, start_row + n))
            WINDOW_STATS.inc("side_output_ranges")
            SCAN_STATS.record_unverified(
                start_row, start_row + n,
                reason=f"stream {self.stream_id}: {late} late row(s) "
                       f"behind watermark {fence}",
                kind="late_side_output",
            )

    def _pane_starts(self, times, fence) -> List[float]:
        live = times[times >= fence]
        needed = set(self._state.panes)
        if live.size:
            slide = self.spec.slide_s
            size = self.spec.size_s
            newest = np.floor(live / slide) * slide
            covers = max(1, int(math.ceil(size / slide)))
            for j in range(covers):
                cand = newest - j * slide
                ok = cand + size > live
                for s in np.unique(cand[ok]):
                    needed.add(float(s))
        return sorted(needed)

    def _dispatch(self, batch, times, starts, fence) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        w = len(starts)
        bucket = 1 << max(0, (w - 1).bit_length())
        prog = _pane_program(self.sig, self.spec, self.policy, times.shape[0], bucket)
        starts_arr = np.full(bucket, _POS_INF, dtype=np.float64)
        starts_arr[:w] = starts
        data_cols = _data_columns(self.sig)
        flat = []
        valids = []
        for col in data_cols:
            if col not in batch:
                raise ValueError(
                    f"stream {self.stream_id!r}: batch is missing column {col!r}"
                )
            arr = np.array(batch[col], dtype=np.float64, copy=False)  # deequ-lint: ignore[host-fetch] -- host batch input, no device round trip
            flat.append(jnp.asarray(arr))
            valids.append(jnp.asarray(~np.isnan(arr)))
        out = prog(
            jnp.asarray(times), jnp.asarray(starts_arr),
            jnp.asarray(np.float64(fence)), *flat, *valids,
        )
        WINDOW_STATS.inc("pane_dispatches")
        return _fetch_leaves(out)

    def _merge(self, starts, leaves) -> None:
        for j, start in enumerate(starts):
            acc = self._state.panes.get(start)
            if acc is None:
                acc = {}
                self._state.panes[start] = acc
                WINDOW_STATS.inc("panes_opened")
            for key, tag in self._tags.items():
                val = float(leaves[key][j])
                if key in acc:
                    acc[key] = _MERGE[tag](acc[key], val)
                else:
                    acc[key] = val

    def _close_ready(self, watermark) -> List[WindowClose]:
        ready = [
            s for s in sorted(self._state.panes)
            if s + self.spec.size_s <= watermark
        ]
        if not ready:
            return []
        closes: List[WindowClose] = []
        pending: List[Tuple[float, float, Optional[Dict[str, float]], str]] = []
        for start in ready:
            end = start + self.spec.size_s
            leaves = self._state.panes.pop(start)
            WINDOW_STATS.inc("panes_closed")
            if end <= self._state.closed_through:
                # the exactly-once fence: a resumed replay rebuilt a pane
                # whose close already emitted — suppress, re-emit NOTHING
                WINDOW_STATS.inc("closes_suppressed")
                closes.append(WindowClose(
                    self.stream_id, start, end,
                    emitted=False, suppressed=True, shed=False, result=None,
                ))
                continue
            lateness = watermark - end
            if self._shed_close(lateness):
                from deequ_tpu.resilience.governance import try_charge

                cls = getattr(self.slo, "cls", "standard")
                self._state.shed.append((end, cls))
                self._state.closed_through = end
                WINDOW_STATS.inc("window_sheds")
                try_charge(
                    self.budget, "window_shed",
                    stream=self.stream_id, window_end=end, slo_class=cls,
                )
                closes.append(WindowClose(
                    self.stream_id, start, end,
                    emitted=False, suppressed=False, shed=True, result=None,
                ))
                continue
            pending.append((start, end, leaves, "emit"))
            self._state.closed_through = end
        # persist the advanced fence BEFORE any emit: a crash past this
        # save replays with every pending close suppressed (exactly-once);
        # a failed save is counted — emission proceeds (availability) with
        # resumability degraded, reported on state_save_failures
        self._save()
        for start, end, leaves, _ in pending:
            result = self._evaluate(leaves)
            self._state.emitted.append(end)
            WINDOW_STATS.inc("closes_emitted")
            self._observe(start, end, result)
            closes.append(WindowClose(
                self.stream_id, start, end,
                emitted=True, suppressed=False, shed=False, result=result,
            ))
        if pending:
            # capture the emitted ledger too (best-effort; the fence
            # already fenced duplicates)
            self._save()
        return closes

    def _shed_close(self, lateness_s: float) -> bool:
        if self.should_shed is None:
            return False
        return bool(self.should_shed(self.slo, lateness_s))

    def _evaluate(self, leaves: Dict[str, float]):
        from deequ_tpu.analyzers.runner import AnalyzerContext
        from deequ_tpu.verification import VerificationSuite

        plans = _leaf_plans(self.sig)
        metric_map = {}
        for i, analyzer in enumerate(self.analyzers):
            _i, _col, _kind, tags = plans[i]
            result = {name: leaves[f"{i}:{name}"] for name in tags}
            state = analyzer.state_from_scan_result(result)
            metric_map[analyzer] = analyzer.compute_metric_from(state)
        ctx = AnalyzerContext(metric_map)
        return VerificationSuite._evaluate(self.checks, ctx)

    def _observe(self, start: float, end: float, result) -> None:
        if self.repository is not None:
            from deequ_tpu.analyzers.runner import AnalyzerContext
            from deequ_tpu.repository.base import AnalysisResult, ResultKey

            key = ResultKey(
                int(round(end * 1000.0)),
                {
                    "stream": self.stream_id,
                    "window_start": repr(start),
                    "window_end": repr(end),
                },
            )
            self.repository.save(
                AnalysisResult(key, AnalyzerContext(dict(result.metrics)))
            )
        if self.monitor is not None:
            try:
                self.monitor.observe_verification(self.stream_id, result)
            # deequ-lint: ignore[bare-except] -- monitoring is observation, never outcome: a watch-rule error must not fail a window close that already emitted; the error is counted on MONITOR_STATS
            except Exception:  # noqa: BLE001
                from deequ_tpu.repository.monitor import MONITOR_STATS

                MONITOR_STATS.monitor_errors += 1

    def _save(self) -> None:
        if self._store is None:
            return
        ok = self._store.save(self.fingerprint, self._state)
        WINDOW_STATS.inc("state_saves" if ok else "state_save_failures")


def drive(stream: WindowedStream, batches, flush: bool = False) -> List[WindowClose]:
    """Advance ``stream`` over ``batches`` (an iterable of host batch
    dicts), skipping every batch a resumed stream already folded."""
    closes: List[WindowClose] = []
    skip = stream.next_batch_index
    for i, batch in enumerate(batches):
        if i < skip:
            continue
        closes.extend(stream.process_batch(batch))
    if flush:
        closes.extend(stream.flush())
    return closes
