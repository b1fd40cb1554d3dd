"""The seam primitive and the run flight recorder.

:class:`seam` is the ONE way the engine takes a duration. ``with
seam("pack", bytes=n):`` at a layer boundary does three things:

1. **Counter, always on.** The seam's EXCLUSIVE seconds (a per-thread
   stack: while a child seam is open the parent's clock stands still)
   add to ``SCAN_STATS.seam_<name>_seconds`` and 1 to
   ``seam_<name>_count`` on ``time.perf_counter()`` — a dot in a seam's
   name is an underscore in its field. The four device-wait seams also
   feed the two older fields with what those always held:
   ``dispatch_seconds`` = ``stage`` + ``dispatch``,
   ``drain_wait_seconds`` = ``drain`` + ``fetch``; ``fetch.copy`` (the
   copy out, nested in ``fetch`` behind the wait) feeds ``fetch``'s
   seconds and ``drain_wait_seconds`` as well as its own, so both keep
   what they held. The enclosing seams (``run``, ``scan_attempt``)
   count their own exclusive seconds like any other (time under no
   named seam) and add their whole duration to ``run_seconds`` /
   ``scan_seconds``: over one thread, the exclusive seconds of all
   seams under a root sum to ``run_seconds``. Only caller threads
   count: the engine's worker threads (prefetch reader, watchdog pool)
   mark themselves with :func:`worker_seams` and emit spans only — the
   caller's wait for them is a seam of its own.

   **The feed gauge.** Per caller thread, the device computations it
   has dispatched (:func:`device_fed`) and does not yet know to be
   ready (:func:`device_ready`). Every stretch of a seam's exclusive
   time that begins with the gauge at zero — the host had given the
   device nothing — is *unfed* time: it adds to
   ``SCAN_STATS.unfed_seconds`` beside the seam's own seconds and rides
   the recorder span as ``unfed_s``.
2. **Span on the profiler's clock.** It enters
   ``jax.profiler.TraceAnnotation("deequ.<name>", **args)``: inside any
   profiler session the seam lands in the ``.xplane.pb`` beside the
   device's events; outside one it costs a flag check.
3. **Record, when armed.** If :func:`current_recorder` is not None the
   seam opens a :class:`FlightRecorder` span (parent, track, args).

Spans of one verification run share ``run_id`` and spans of one scan
``scan_id``: an enclosing seam given either hands it down to every seam
opened under it (args of 2 and 3). docs/observability.md lists every
seam, its layer, its counter and the metric that reads it.

The flight recorder is the timeline the seams (and the instant events:
each fault-ladder rung, budget charges, serve submit→resolve) land on: a
typed :class:`SpanRecord` into a ring-buffer-bounded recorder when one
is armed, nothing (one module-global integer check) when none is.

Design constraints, in order:

1. **Disarmed is cheap.** Recording is OFF by default; the disarmed
   path of a seam is two clock reads, the profiler's flag and
   ``current_recorder()`` returning ``None`` after reading one
   module-global counter — no lock, no recorder allocation. What that
   costs on the chip is measured, not asserted: PERF.md §6 (PR 24).
2. **Bounded.** Records land in a ring buffer (``capacity`` spans); a
   saturated recorder drops the OLDEST records and counts the drops —
   a long-lived traced service degrades to a rolling window, never to
   unbounded host memory.
3. **Host-side only.** Spans are emitted at host seams, never inside a
   jitted/traced function — an emission inside traced code would be a
   host callback baked into the program (the ``span-in-jit`` repo-lint
   rule enforces this, same class as ``jit-impure``).
4. **Thread-aware.** Each record carries its thread (``track``) and its
   parent span on that thread; the engine seams that run work on worker
   threads (``_governed_attempt``'s watchdog worker, the prefetch
   reader, the serve worker) re-enter :func:`recording_scope` with the
   caller's span as the seeded parent, so cross-thread work stays
   parented in the exported trace.

Arming (three doors, mirroring the run-budget pattern):

- ``run_scan(trace=recorder)`` / ``run_scan(trace=True)`` — one scan;
- ``VerificationRunBuilder.with_tracing(...)`` /
  ``do_verification_run(trace=...)`` — one verification run, summary on
  ``VerificationResult.run_trace``;
- ``DEEQU_TPU_TRACE=1`` (envcfg registry) — arms a process-global
  recorder (capacity from ``DEEQU_TPU_TRACE_CAPACITY``) the engine entry
  points pick up ambiently.

Export: :mod:`deequ_tpu.obs.export` renders a recording as
Chrome-trace/Perfetto JSON (one track per thread, nested spans, instant
events for faults/charges); ``summary()`` is the compact per-phase wall
breakdown that lands on ``VerificationResult.run_trace``.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

#: default ring capacity — ~64k records is minutes of traced serving
#: traffic at a few hundred spans/suite, a few MB of host memory
DEFAULT_CAPACITY = 1 << 16

_SPAN_IDS = itertools.count(1)


@dataclass
class SpanRecord:
    """One typed timeline record. ``kind`` is ``"span"`` (has a
    duration) or ``"instant"`` (a point event: a fault-ladder rung, a
    budget charge). Times are ``time.monotonic()`` seconds; ``track``
    is the emitting thread's name (one export track per thread, plus
    synthetic per-tenant tracks for serve submit→resolve spans)."""

    name: str
    kind: str
    t_start: float
    track: str
    span_id: int
    parent_id: Optional[int] = None
    t_end: Optional[float] = None
    args: Dict[str, Any] = field(default_factory=dict)
    #: True when the recording stopped while the span was still open
    #: (kill-and-resume, a crashed run): the export closes it at the
    #: recording's end and marks it so the truncation is visible
    truncated: bool = False

    @property
    def duration(self) -> Optional[float]:
        if self.t_end is None:
            return None
        return self.t_end - self.t_start


class _OpenSpan:
    """Context manager for one in-flight span (returned by
    :meth:`FlightRecorder.span`)."""

    __slots__ = ("rec", "record")

    def __init__(self, rec: "FlightRecorder", record: SpanRecord):
        self.rec = rec
        self.record = record

    def __enter__(self) -> SpanRecord:
        return self.record

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.record.args["error"] = type(exc).__name__
        self.rec._close(self.record)


class FlightRecorder:
    """Ring-buffer-bounded span/event recorder (see module doc).

    Thread-safe: records may be emitted from any thread; each thread
    keeps its own span stack (parenting is per-track, matching how the
    trace renders). ``records()`` returns closed records in completion
    order; open spans are visible via ``open_spans()`` and exported as
    truncated."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._open: Dict[int, SpanRecord] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.dropped = 0
        self.started = time.monotonic()

    # -- emission --------------------------------------------------------

    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def current_span_id(self) -> Optional[int]:
        """This thread's innermost open span (the parent a worker-thread
        scope should seed with)."""
        stack = self._stack()
        return stack[-1] if stack else None

    def span(self, name: str, **args) -> _OpenSpan:
        """Open one span on this thread::

            with rec.span("scan_attempt", attempt=0, chunk=4096):
                ...

        Nested spans parent to the innermost open span on the same
        thread."""
        stack = self._stack()
        record = SpanRecord(
            name=name,
            kind="span",
            t_start=time.monotonic(),
            track=threading.current_thread().name,
            span_id=next(_SPAN_IDS),
            parent_id=stack[-1] if stack else None,
            args=args,
        )
        stack.append(record.span_id)
        with self._lock:
            self._open[record.span_id] = record
        return _OpenSpan(self, record)

    def _close(self, record: SpanRecord) -> None:
        record.t_end = time.monotonic()
        stack = self._stack()
        if stack and stack[-1] == record.span_id:
            stack.pop()
        elif record.span_id in stack:  # defensive: out-of-order close
            stack.remove(record.span_id)
        with self._lock:
            self._open.pop(record.span_id, None)
            self._append(record)

    def event(self, name: str, **args) -> SpanRecord:
        """One instant event (a fault-ladder rung firing, a budget
        charge), parented to this thread's innermost open span."""
        stack = self._stack()
        record = SpanRecord(
            name=name,
            kind="instant",
            t_start=time.monotonic(),
            t_end=None,
            track=threading.current_thread().name,
            span_id=next(_SPAN_IDS),
            parent_id=stack[-1] if stack else None,
            args=args,
        )
        with self._lock:
            self._append(record)
        return record

    def record_span(
        self,
        name: str,
        t_start: float,
        t_end: float,
        track: Optional[str] = None,
        **args,
    ) -> SpanRecord:
        """Record a RETROACTIVE span with explicit monotonic bounds —
        the serve layer's submit→resolve spans are measured on the
        future (submit on the caller thread, resolve on the worker) and
        recorded whole once resolved, on a synthetic per-tenant
        track."""
        record = SpanRecord(
            name=name,
            kind="span",
            t_start=float(t_start),
            t_end=float(t_end),
            track=(
                track
                if track is not None
                else threading.current_thread().name
            ),
            span_id=next(_SPAN_IDS),
            args=args,
        )
        with self._lock:
            self._append(record)
        return record

    def _append(self, record: SpanRecord) -> None:
        # caller holds self._lock
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(record)

    # -- reading ---------------------------------------------------------

    def records(self) -> List[SpanRecord]:
        """Closed records, completion order (point-in-time copy of the
        ring)."""
        with self._lock:
            return list(self._ring)

    def open_spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._open.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._open.clear()
            self.dropped = 0
            self.started = time.monotonic()

    def summary(
        self,
        since: Optional[float] = None,
        dropped_baseline: int = 0,
    ) -> dict:
        """Compact per-phase wall breakdown — the
        ``VerificationResult.run_trace`` payload. Spans aggregate by
        name (count + total wall seconds); instant events aggregate by
        name (count). The dispatch/fetch phase sums reconcile with
        ``ScanStats.dispatch_seconds`` / ``drain_wait_seconds`` — one
        :class:`seam` writes both.

        ``since`` (a ``time.monotonic()`` stamp) restricts the summary
        to records STARTED at or after it — a shared or env-armed
        global recorder outlives any one run, and a per-run breakdown
        must be a delta, not the recorder's lifetime (the same
        discipline ``result.scan_stats`` / ``retry_stats`` follow).
        ``dropped_baseline`` (the recorder's ``dropped`` captured at
        run start) makes the drop count a delta too; ``open`` counts
        only spans opened in the window. A phase whose seams counted
        unfed time (the ``unfed_s`` a counting seam leaves among its
        span's args: exclusive seconds with nothing dispatched to the
        device) carries the sum as ``unfed_seconds``."""
        phases: Dict[str, dict] = {}
        events: Dict[str, int] = {}
        for r in self.records():
            if since is not None and r.t_start < since:
                continue
            if r.kind == "span":
                row = phases.setdefault(
                    r.name, {"count": 0, "wall_seconds": 0.0}
                )
                row["count"] += 1
                if r.duration is not None:
                    row["wall_seconds"] += r.duration
                unfed = r.args.get("unfed_s")
                if unfed:
                    row["unfed_seconds"] = (
                        row.get("unfed_seconds", 0.0) + unfed
                    )
            else:
                events[r.name] = events.get(r.name, 0) + 1
        for row in phases.values():
            for key in ("wall_seconds", "unfed_seconds"):
                if key in row:
                    row[key] = round(row[key], 6)
        open_spans = [
            s for s in self.open_spans()
            if since is None or s.t_start >= since
        ]
        return {
            "spans": sum(p["count"] for p in phases.values()),
            "events": sum(events.values()),
            "dropped": max(self.dropped - dropped_baseline, 0),
            "open": len(open_spans),
            "phases": phases,
            "event_counts": events,
        }


# -- ambient arming ----------------------------------------------------------

# Same shape as the run budget's ambient slot (resilience/governance.py):
# thread-local so concurrent traced runs don't interleave parent stacks,
# with the engine's worker-thread seams re-entering the scope explicitly.
# `_armed` is the disarmed fast path: a plain module-global integer read
# decides "no recorder anywhere" without touching the thread-local.
_AMBIENT = threading.local()
_GLOBAL: Optional[FlightRecorder] = None
_armed = 0
# arm/disarm transitions are rare (scope entries, global install) but
# happen on worker threads too (prefetch reader, watchdog, serve
# worker); CPython's `_armed += 1` is LOAD/ADD/STORE and a lost update
# would silently disarm live tracing — serialize the WRITES. The hot
# READ in current_recorder stays lock-free: a momentarily stale value
# only costs one thread-local lookup.
_ARM_LOCK = threading.Lock()


def current_recorder() -> Optional[FlightRecorder]:
    """The recorder the emitting seam should use: this thread's ambient
    scope first, else the process-global (env-armed) recorder, else
    None. The disarmed path is one integer check."""
    if not _armed:
        return None
    rec = getattr(_AMBIENT, "recorder", None)
    if rec is not None:
        return rec
    if getattr(_AMBIENT, "suppressed", False):
        return None
    return _GLOBAL


@contextmanager
def recording_scope(
    recorder: Optional[FlightRecorder], parent: Optional[int] = None
) -> Iterator[Optional[FlightRecorder]]:
    """Install ``recorder`` as this thread's ambient recorder for the
    block. ``parent`` seeds the thread's span stack (pass the caller
    thread's ``current_span_id()`` when re-entering on a worker thread
    so cross-thread work stays parented). ``recorder=None`` SUPPRESSES
    tracing inside the block (the A/B hatch: a disarmed leg must not
    pick up the env-global recorder)."""
    global _armed
    prev = getattr(_AMBIENT, "recorder", None)
    prev_sup = getattr(_AMBIENT, "suppressed", False)
    _AMBIENT.recorder = recorder
    _AMBIENT.suppressed = recorder is None
    seeded = False
    if recorder is not None and parent is not None:
        stack = recorder._stack()
        stack.append(parent)
        seeded = True
    with _ARM_LOCK:
        _armed += 1
    try:
        yield recorder
    finally:
        with _ARM_LOCK:
            _armed -= 1
        if seeded:
            stack = recorder._stack()
            if parent in stack:
                stack.remove(parent)
        _AMBIENT.recorder = prev
        _AMBIENT.suppressed = prev_sup


def install_global_recorder(
    recorder: Optional[FlightRecorder],
) -> Optional[FlightRecorder]:
    """Install (or, with None, remove) the process-global recorder —
    what ``DEEQU_TPU_TRACE=1`` arms. Returns the previous one."""
    global _GLOBAL, _armed
    with _ARM_LOCK:
        previous = _GLOBAL
        if previous is not None:
            _armed -= 1
        _GLOBAL = recorder
        if recorder is not None:
            _armed += 1
    return previous


def global_recorder() -> Optional[FlightRecorder]:
    return _GLOBAL


def maybe_arm_from_env() -> Optional[FlightRecorder]:
    """Lazily arm the process-global recorder when ``DEEQU_TPU_TRACE=1``
    (envcfg registry; ``DEEQU_TPU_TRACE_CAPACITY`` sizes the ring).
    Called by the engine entry points (``run_scan``,
    ``do_verification_run``, ``VerificationService``); idempotent and
    cheap when the flag is off."""
    global _GLOBAL, _armed
    if _GLOBAL is not None:
        return _GLOBAL
    from deequ_tpu.envcfg import env_value

    if not env_value("DEEQU_TPU_TRACE"):
        return None
    capacity = env_value("DEEQU_TPU_TRACE_CAPACITY") or DEFAULT_CAPACITY
    # re-check under the lock: two entry points racing on first use
    # (service ctor + run_scan) must not each install a recorder — the
    # loser's already-emitted records would vanish from the exported
    # global trace
    with _ARM_LOCK:
        if _GLOBAL is None:
            _GLOBAL = FlightRecorder(capacity=capacity)
            _armed += 1
    return _GLOBAL


def resolve_recorder(trace=None) -> Optional[FlightRecorder]:
    """Argument resolution for ``run_scan(trace=...)`` /
    ``do_verification_run(trace=...)``: an explicit recorder wins;
    ``True`` means "the env-armed global recorder, else a fresh
    anonymous one SCOPED to this call" — it must NOT install anything
    process-wide (a single ``trace=True`` call would otherwise leave
    every later run armed, breaking the off-by-default contract);
    ``None`` defers to the ambient/env arming; ``False`` suppresses
    tracing for this call. For entry points that cannot hand the
    records back (``run_scan``), pass a recorder you hold — the
    verification surface returns the anonymous one on
    ``result.trace_recorder``."""
    if trace is None or trace is False:
        return None
    if isinstance(trace, FlightRecorder):
        return trace
    if trace is True:
        rec = maybe_arm_from_env()
        return rec if rec is not None else FlightRecorder()
    raise ValueError(
        f"trace must be a FlightRecorder, True, False or None, "
        f"got {trace!r}"
    )


# -- the seam primitive ------------------------------------------------------

#: every counted seam, in pipeline order (docs/observability.md has the
#: table: layer, where it is emitted, the metric that reads it)
SEAM_NAMES = (
    "run", "scan_attempt",
    "plan", "build", "pack", "stage", "dispatch", "drain", "fetch",
    "fetch.copy",
    "states", "evaluate", "sketch_fold", "grouping", "repository",
    "persist.pack", "persist.stage", "grouping.host",
)
#: seams whose exclusive seconds ALSO feed older ScanStats fields, with
#: exactly what those fields always held (``fetch.copy`` is the part of
#: a fetch behind its wait: ``seam_fetch_seconds`` keeps the whole)
_LEGACY_FIELDS = {
    "stage": ("dispatch_seconds",),
    "dispatch": ("dispatch_seconds",),
    "drain": ("drain_wait_seconds",),
    "fetch": ("drain_wait_seconds",),
    "fetch.copy": ("seam_fetch_seconds", "drain_wait_seconds"),
}
#: enclosing seams: their exclusive seconds are the time under no named
#: seam; the value is the field their WHOLE duration adds to
_ENCLOSING = {"run": "run_seconds", "scan_attempt": "scan_seconds"}
#: the ids an enclosing seam hands down to the seams under it
_ID_ARGS = ("run_id", "scan_id")
_UNSET = object()

RUN_IDS = itertools.count(1)


def seam_fields(name: str):
    """``(seconds_field, count_field)`` of a counted seam."""
    stem = "seam_" + name.replace(".", "_")
    return stem + "_seconds", stem + "_count"


#: name -> (seconds field, count field, older fields fed, wall field)
_COUNTED = {
    name: seam_fields(name) + (
        _LEGACY_FIELDS.get(name, ()), _ENCLOSING.get(name),
    )
    for name in SEAM_NAMES
}

# the counters' home (``SCAN_STATS``): bound by ops/scan_engine.py when it
# creates the singleton — this module is imported by it, not the reverse
_STATS = None
_SEAM_TLS = threading.local()


class _ThreadSeams:
    """One thread's seam state: the innermost open counted seam, whether
    the thread is an engine worker (spans only), the ids handed down by
    the enclosing seams, and the feed gauge: how many device
    computations the thread has dispatched (``fed``) and how many of
    them it knows to be ready (``ready``)."""

    __slots__ = ("open", "worker", "ids", "fed", "ready")

    def __init__(self):
        self.open = None
        self.worker = False
        self.ids = None
        self.fed = 0
        self.ready = 0

    def cut(self, unfed: bool) -> None:
        """The gauge leaves or reaches zero: close the open seam's
        stretch here, as the unfed or fed time it was."""
        seam_ = self.open
        if seam_ is not None:
            now = time.perf_counter()
            stretch = now - seam_._resumed
            seam_._own += stretch
            if unfed:
                seam_._unfed += stretch
            seam_._resumed = now


def _thread_seams() -> _ThreadSeams:
    try:
        return _SEAM_TLS.state
    except AttributeError:
        state = _SEAM_TLS.state = _ThreadSeams()
        return state


def bind_seam_counters(stats) -> None:
    """Name the object whose ``seam_*`` fields the seams add to."""
    global _STATS
    _STATS = stats


def seam_ids() -> Dict[str, Any]:
    """The ``run_id`` / ``scan_id`` in force on this thread (what a
    worker-thread scope should be seeded with)."""
    return dict(_thread_seams().ids or {})


# -- the feed gauge ----------------------------------------------------------


def device_fed() -> None:
    """This thread enqueued one device computation (a step, a fold
    merge, an own-pass kernel; a transfer is none: the device computes
    nothing during a put)."""
    state = _thread_seams()
    if not state.worker:
        if state.fed == state.ready:
            state.cut(unfed=True)
        state.fed += 1


def fed_mark():
    """The newest dispatch of this thread, for the :func:`device_ready`
    of a result fetched later (a deferred scan keeps it)."""
    state = _thread_seams()
    return state, state.fed


def device_ready(mark=True) -> None:
    """A wait on this thread proved the dispatch ``mark`` stands for (a
    :func:`fed_mark`; True: the thread's newest) ready, and every one
    before it: the device runs them in order. Where that is the newest,
    the gauge is at zero from here. A mark taken on another thread says
    nothing here."""
    state = _thread_seams()
    if state.worker:
        return
    if mark is True:
        upto = state.fed
    elif mark[0] is state:
        upto = mark[1]
    else:
        return
    if upto > state.ready:
        if upto == state.fed:
            state.cut(unfed=False)
        state.ready = upto


def feed_gauge() -> int:
    """Device computations this thread has dispatched and does not yet
    know to be ready."""
    state = _thread_seams()
    return state.fed - state.ready


@contextmanager
def worker_seams(ids: Optional[Dict[str, Any]] = None) -> Iterator[None]:
    """Mark this thread as an engine WORKER for the block: its seams
    are spans only (the caller thread's wait for the worker is already a
    counted seam, so counting here would count the time twice). ``ids``
    seeds the caller's ``run_id`` / ``scan_id``."""
    state = _thread_seams()
    prev = (state.worker, state.ids)
    state.worker = True
    if ids:
        state.ids = ids
    try:
        yield
    finally:
        state.worker, state.ids = prev


class seam:
    """One layer-boundary duration (see the module doc): exclusive
    seconds into ``SCAN_STATS``, a ``deequ.<name>`` annotation on the
    profiler's clock, a recorder span when armed. Host-side only — a
    seam inside traced code is a host callback (``span-in-jit``)."""

    __slots__ = ("name", "args", "_state", "_t0", "_resumed", "_own",
                 "_unfed", "_outer", "_annotation", "_span", "_ids_before")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "seam":
        state = self._state = _thread_seams()
        self._annotation = None
        if _armed or TraceAnnotation.is_enabled():
            self._enter_spans(state)
        if not state.worker:
            now = time.perf_counter()
            self._outer = outer = state.open
            if outer is not None:
                stretch = now - outer._resumed
                outer._own += stretch
                if state.fed == state.ready:
                    outer._unfed += stretch
            state.open = self
            self._own = self._unfed = 0.0
            self._t0 = self._resumed = now
        return self

    def _enter_spans(self, state: _ThreadSeams) -> None:
        """The profiler annotation and the recorder span, with the ids
        of the enclosing seams among their args."""
        name, args = self.name, self.args
        ids = state.ids
        self._ids_before = _UNSET
        if name in _ENCLOSING:
            mine = {k: args[k] for k in _ID_ARGS if k in args}
            if mine:
                self._ids_before = ids
                state.ids = {**(ids or {}), **mine}
        if ids:
            args = {**ids, **args}
        rec = current_recorder()
        self._span = rec.span(name, **args) if rec is not None else None
        self._annotation = TraceAnnotation("deequ." + name, **args)
        self._annotation.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        state = self._state
        unfed = 0.0
        if not state.worker:
            now = time.perf_counter()
            outer = self._outer
            state.open = outer
            if outer is not None:
                outer._resumed = now
            stretch = now - self._resumed
            own = self._own + stretch
            unfed = self._unfed
            if state.fed == state.ready:
                unfed += stretch
            stats = _STATS
            if stats is not None:
                # KeyError: no such seam
                seconds, count, older, wall = _COUNTED[self.name]
                setattr(stats, seconds, getattr(stats, seconds) + own)
                setattr(stats, count, getattr(stats, count) + 1)
                for name in older:
                    setattr(stats, name, getattr(stats, name) + own)
                if wall is not None:
                    setattr(stats, wall,
                            getattr(stats, wall) + (now - self._t0))
                if unfed:
                    stats.unfed_seconds += unfed
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            if self._span is not None:
                if unfed:
                    self._span.record.args["unfed_s"] = unfed
                self._span.__exit__(exc_type, exc, tb)
            if self._ids_before is not _UNSET:
                state.ids = self._ids_before
