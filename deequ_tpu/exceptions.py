"""Exception hierarchy for metric calculation failures.

Mirrors the reference semantics (analyzers/runners/MetricCalculationException.scala:19-78):
failures during metric computation are *data* — they are captured inside
``Metric.value`` rather than aborting a run.

Device faults are part of the same taxonomy: the scan engine classifies
raw ``jaxlib``/``XlaRuntimeError`` failures at its three device
boundaries (pack/transfer, trace/compile, execute) into the typed
``Device*Exception`` family below, so callers — and the degradation
policies (chunk bisection, CPU fallback, watchdog; ops/scan_engine.py) —
never have to pattern-match runtime strings.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple


class MetricCalculationException(Exception):
    """Base class for anything that goes wrong while computing a metric."""


class EnvConfigError(ValueError):
    """A malformed ``DEEQU_TPU_*`` environment variable
    (deequ_tpu/envcfg.py — the consolidated registry every switch parses
    through). Subclasses ``ValueError`` so pre-registry callers that
    caught validation errors keep working; carries the variable name,
    the offending raw value, and what would have been accepted, so a
    deployment misconfiguration reads as exactly that instead of a
    stack trace into whichever module happened to parse it first."""

    def __init__(self, name: str, raw: str, expected: str):
        super().__init__(f"{name} must be {expected}, got {raw!r}")
        self.name = name
        self.raw = raw
        self.expected = expected


class MetricCalculationRuntimeException(MetricCalculationException):
    """Runtime failure during state/metric computation."""


class MetricCalculationPreconditionException(MetricCalculationException):
    """A precondition on the input schema was violated."""


class NoSuchColumnException(MetricCalculationPreconditionException):
    def __init__(self, column: str):
        super().__init__(f"Input data does not include column {column}!")
        self.column = column


class WrongColumnTypeException(MetricCalculationPreconditionException):
    pass


class NoColumnsSpecifiedException(MetricCalculationPreconditionException):
    pass


class NumberOfSpecifiedColumnsException(MetricCalculationPreconditionException):
    pass


class IllegalAnalyzerParameterException(MetricCalculationPreconditionException):
    def __init__(self, message: str):
        super().__init__(f"Can't execute the analysis: {message}")


class EmptyStateException(MetricCalculationRuntimeException):
    pass


class CorruptStateException(MetricCalculationRuntimeException):
    """Persisted bytes failed integrity validation (checksum mismatch,
    torn write, undecodable payload). Raised instead of the raw
    JSON/struct error so callers can distinguish 'the file is damaged'
    from 'the code is wrong' — damaged state is recoverable by
    recomputing; a struct error is a bug."""

    def __init__(self, what: str, detail: str = ""):
        msg = f"corrupt persisted state: {what}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)
        self.what = what


class ReusingNotPossibleResultsMissingException(
    MetricCalculationRuntimeException, RuntimeError
):
    """Raised when fail_if_results_missing is set and the repository lacks
    some requested analyzer results (reference AnalysisRunner.scala:552).
    Lives here so ALL failure types share one taxonomy; re-exported from
    ``analyzers.runner`` for compatibility, and still a RuntimeError for
    call sites that caught it as one before the move."""


class ServeException(MetricCalculationRuntimeException):
    """Base for serving-layer (deequ_tpu/serve) operational failures —
    conditions of the SERVICE, not of any one suite's data (those stay
    failure metrics / typed device errors as everywhere else)."""


class ServiceClosedException(ServeException):
    """A submit/resume/flush against a stopped VerificationService."""


class ControlPlaneException(MetricCalculationRuntimeException):
    """Typed failure of the closed-loop quality control plane
    (deequ_tpu/control): an illegal lifecycle transition on the
    CheckRegistry, a shadow evaluation requested outside the
    ``best_effort`` SLO class (the isolation invariant — a candidate
    check must never consume critical capacity), or a profile replay
    that cannot reconstruct a tenant's history."""


class ServiceOverloadedException(ServeException):
    """Typed backpressure: the service refused to buffer this request —
    the pending queue is at ``max_pending``, or (round 15, the admission
    tier's subclasses below) the request's SLO class ran out of budget
    or its deadline expired in-queue. The caller sheds load or retries
    after ``retry_after_s``; the service never buffers without bound.

    Structured fields (all optional — pre-round-15 raise sites carried a
    message only): ``queue_depth`` is the pending count at refusal,
    ``retry_after_s`` the service's drain-rate-derived estimate of when
    a retry could be admitted, ``slo_class`` the refused request's SLO
    class (``"critical"`` | ``"standard"`` | ``"best_effort"``)."""

    def __init__(self, message: str, queue_depth: Optional[int] = None,
                 retry_after_s: Optional[float] = None,
                 slo_class: Optional[str] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s
        self.slo_class = slo_class


class AdmissionRejectedException(ServiceOverloadedException):
    """The admission controller (deequ_tpu/serve/admission.py) refused
    this request at ``submit()``: its SLO class's queue budget is
    exhausted, the brownout ladder is shedding its class (level 1 sheds
    ``best_effort``, level 3 admits ``critical`` only), or its tenant is
    over the brownout inflight cap (level 2). ``reason`` names which
    (``"class_budget"`` | ``"brownout_best_effort"`` |
    ``"brownout_critical_only"`` | ``"tenant_inflight_cap"``);
    ``retry_after_s`` is always populated — admission rejection is
    backpressure with a schedule, not an error."""

    def __init__(self, message: str, reason: str = "class_budget",
                 queue_depth: Optional[int] = None,
                 retry_after_s: Optional[float] = None,
                 slo_class: Optional[str] = None):
        super().__init__(message, queue_depth=queue_depth,
                         retry_after_s=retry_after_s, slo_class=slo_class)
        self.reason = reason


class DeadlineExceededException(ServiceOverloadedException):
    """An ACCEPTED request's absolute SLO deadline expired before its
    dispatch: the deadline-aware queue sheds it pre-dispatch (resolved
    exactly once, typed, on its original future) instead of burning
    device time on a result whose caller already gave up — and a fleet
    failover re-dispatch sheds an expired victim the same way rather
    than replaying it stale. ``waited_s`` is how long the request sat
    accepted; ``deadline_ms`` the SLO it missed. Computation is never
    degraded — only which requests run."""

    def __init__(self, message: str, tenant=None,
                 slo_class: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 waited_s: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 retry_after_s: Optional[float] = None):
        super().__init__(message, queue_depth=queue_depth,
                         retry_after_s=retry_after_s, slo_class=slo_class)
        self.tenant = tenant
        self.deadline_ms = deadline_ms
        self.waited_s = waited_s


class LateDataException(MetricCalculationRuntimeException):
    """A windowed stream (deequ_tpu/windows) received rows whose event
    time is older than the stream's watermark under the ``refuse`` late
    policy: the caller asked for an error instead of silent exclusion.
    Under ``drop`` the rows are counted (``ScanStats.late_rows``); under
    ``side_output`` their batch-aligned row ranges are quarantined on the
    partial-result surface — this exception is the third, strictest
    routing. ``late_rows`` is how many rows in the offending batch were
    late; ``watermark`` the fence they fell behind; ``oldest_event_time``
    the worst offender's event time."""

    def __init__(self, message: str, stream: Optional[str] = None,
                 late_rows: Optional[int] = None,
                 watermark: Optional[float] = None,
                 oldest_event_time: Optional[float] = None):
        super().__init__(message)
        self.stream = stream
        self.late_rows = late_rows
        self.watermark = watermark
        self.oldest_event_time = oldest_event_time


class StaleEpochException(ServeException):
    """A fenced-out coordinator (serve/lease.py) tried to act: its lease
    epoch is older than the highest epoch the cluster has observed — a
    zombie that stalled through a lease takeover and woke up after a
    successor resumed on the same ledger. Raised at ``submit()`` when
    the on-disk lease outranks the coordinator's epoch, and sent back
    typed by workers that refuse a stale-epoch dispatch frame, so a
    split brain surfaces as a refusal instead of a double-resolution.

    ``stale_epoch`` is the refused writer's epoch; ``current_epoch``
    the highest epoch the refusing side has seen; ``holder`` names the
    current lease holder when known. Like the backpressure family, the
    fields decompose onto wire frames and reconstruct on the far side."""

    def __init__(self, message: str, stale_epoch: Optional[int] = None,
                 current_epoch: Optional[int] = None,
                 holder: Optional[str] = None):
        super().__init__(message)
        self.stale_epoch = stale_epoch
        self.current_epoch = current_epoch
        self.holder = holder


class RetryExhaustedException(MetricCalculationRuntimeException):
    """A retried I/O operation kept failing past the RetryPolicy's attempt
    budget or deadline. ``__cause__`` carries the last underlying error."""

    def __init__(self, what: str, attempts: int, cause: BaseException):
        super().__init__(
            f"{what} still failing after {attempts} attempts: {cause}"
        )
        self.attempts = attempts
        self.__cause__ = cause


class RunBudgetExhaustedException(MetricCalculationRuntimeException):
    """The run-level fault budget (resilience/governance.py) ran out
    mid-ladder: the COMPOSED retry ladder — I/O retries, OOM bisections,
    encoded demotions, mesh reshards, CPU fallbacks — charged more
    attempts than ``max_total_attempts`` allows, or the wall clock passed
    ``run_deadline``. Raised by ``RunBudget.charge`` at the first charge
    past the budget, so no rung can keep burning time after the run is
    over budget.

    ``reason`` is ``"max_total_attempts"`` or ``"run_deadline"``;
    ``ledger`` is the budget's charge snapshot (what each rung spent);
    ``degraded`` is True when the governing policy is
    ``on_budget_exhausted="degrade"`` — the verification layers then
    convert this into a PARTIAL result (failure metrics for the analyzers
    the exhausted scan could not finish, exact
    ``unverified_row_ranges`` for the rows never verified) instead of
    propagating; under ``"raise"`` it surfaces to the caller typed."""

    def __init__(self, reason: str, ledger: Optional[dict] = None,
                 degraded: bool = True, detail: str = ""):
        msg = f"run budget exhausted ({reason})"
        if detail:
            msg += f": {detail}"
        super().__init__(msg)
        self.reason = reason
        self.ledger = dict(ledger or {})
        self.degraded = bool(degraded)


class PlanLintError(MetricCalculationException):
    """A static contract violation found in a scan program BEFORE dispatch
    (deequ_tpu/lint/plan_lint.py): the traced jaxpr of a ``ScanPlan``-built
    program contradicts the contracts the plan declares — a
    selection-variant plan containing a ``sort`` primitive, a host
    callback inside a one-fetch fused program, a fold leaf whose merge
    disagrees with its registered reduction tag. Raised at trace time,
    per plan, under ``run_scan(plan_lint="error")`` /
    ``DEEQU_TPU_PLAN_LINT=error`` — the static twin of the runtime
    counter asserts (``device_sort_passes``/``device_fetches``), catching
    planner/packer drift before a single chunk dispatches.

    ``findings`` carries the structured finding rows (rule, severity,
    message) the lint pass produced."""

    def __init__(self, message: str, findings=()):
        super().__init__(message)
        self.findings = tuple(findings)


class PlanLintWarning(UserWarning):
    """A plan-lint finding surfaced in ``plan_lint="warn"`` mode (or a
    warning-severity finding in ``"error"`` mode): the scan proceeds, the
    finding is recorded on ``ScanStats.plan_lints``, and deployments can
    escalate or silence it through the standard warnings filters."""


class GroupBudgetIgnoredWarning(UserWarning):
    """``group_memory_budget`` was configured together with checkpointing:
    mid-store spill state is not serializable, so spill is disabled and
    frequency folds stay in host RAM. Emitted exactly ONCE per analysis
    run (never per batch); the typed category lets deployments suppress
    or escalate it through the standard warnings filters."""


# -- device fault taxonomy ---------------------------------------------------
#
# Spark gives the reference fault tolerance for free (lost tasks re-execute
# from lineage); JAX/XLA gives us raw RuntimeErrors with status-code
# prefixes. The scan engine classifies them ONCE, at the device boundary
# where they surfaced, into this typed family — the degradation policies
# (bisection/fallback/watchdog) and user code both dispatch on types.

#: the device boundaries where classification happens
DEVICE_BOUNDARIES = ("transfer", "trace", "execute", "fetch")


class DeviceException(MetricCalculationRuntimeException):
    """A classified device-layer (XLA/jaxlib) failure.

    ``boundary`` names where it surfaced: ``"transfer"`` (device_put /
    chunk pack), ``"trace"`` (jit trace / compile), ``"execute"``
    (dispatch / block_until_ready), or ``"fetch"`` (the device->host
    result materialization — with the on-device partial fold this is
    where ASYNC execute failures surface, since it is the scan's one
    blocking round trip).

    ``device_ids`` names the mesh members the raw error implicated (XLA
    messages often carry the failing chip: "device 3", "TPU_2", "chip
    #5"); empty when the fault is unattributable. Attribution is what
    lets the degraded-mesh policy shrink the mesh around ONE dead chip
    instead of abandoning all of them."""

    def __init__(self, message: str, boundary: str = "execute",
                 device_ids: Tuple[int, ...] = ()):
        super().__init__(message)
        self.boundary = boundary
        self.device_ids = tuple(device_ids)


class DeviceOOMException(DeviceException):
    """Device memory (HBM) exhausted — RESOURCE_EXHAUSTED / allocator
    failures. Recoverable by scanning in smaller chunks (the engine's
    adaptive chunk bisection) or by falling back to the host backend."""


class DeviceCompileException(DeviceException):
    """The fused program failed to lower/compile for the accelerator
    (INVALID_ARGUMENT / UNIMPLEMENTED / Mosaic or XLA compilation errors).
    Retrying the same program on the same backend cannot help; the CPU
    fallback re-jits it on the host backend."""


class DeviceLostException(DeviceException):
    """The accelerator died or never came up: backend initialization
    failures, device halts, DATA_LOSS / UNAVAILABLE / ABORTED / INTERNAL
    runtime states. The run can only continue on another backend."""


class DeviceHangException(DeviceException):
    """A blocking device call exceeded the compute watchdog's wall-clock
    deadline — a hung device converted into a typed, catchable failure
    (the blocked host thread is abandoned; it cannot be cancelled)."""

    def __init__(self, message: str, boundary: str = "execute",
                 deadline: Optional[float] = None):
        super().__init__(message, boundary)
        self.deadline = deadline


class MeshDegradedException(DeviceException):
    """A collective-boundary failure on a multi-chip mesh attributable to
    specific mesh members (``device_ids``): one chip's shard faulted while
    the rest of the mesh is presumed healthy. The degraded-mesh policy in
    ``run_scan`` responds by evicting residency pinned to the implicated
    devices, rebuilding the mesh over the largest healthy subset, and
    re-dispatching the same fused program — the CPU fallback is reached
    only when NO accelerator subset remains."""


class PeerLostException(DeviceException):
    """A multi-host run lost contact with one or more peer processes
    (barrier/heartbeat timeout across the DCN tier). ``lost_processes``
    names the process indices that stopped responding (empty when the
    timeout could not be attributed). With ``on_peer_loss="degrade"`` the
    surviving hosts complete the run and the lost hosts' row ranges are
    reported unverified instead of raising this."""

    def __init__(self, message: str, lost_processes: Tuple[int, ...] = (),
                 boundary: str = "execute"):
        super().__init__(message, boundary)
        self.lost_processes = tuple(lost_processes)


class WorkerLostException(DeviceException):
    """A serving-fleet worker (deequ_tpu/serve/fleet.py) died or stopped
    heartbeating: its process/thread is gone or it stalled past the
    membership timeout. ``worker_ids`` names the lost fleet members —
    the in-process analogue of ``PeerLostException``'s lost hosts. The
    fleet responds with FAILOVER, not abort: the lost worker's accepted
    requests re-dispatch onto surviving workers on their ORIGINAL
    futures (each re-dispatch charging the tenant's own run budget, kind
    ``worker_failover``); this exception reaches a caller only when no
    survivor remains or a request exhausted its failover retries."""

    def __init__(self, message: str, worker_ids: Tuple[int, ...] = (),
                 boundary: str = "execute"):
        super().__init__(message, boundary)
        self.worker_ids = tuple(worker_ids)


# message patterns per class, checked in order — OOM first (an OOM during
# compilation must bisect, not fall back), then compile, then lost
_OOM_RE = re.compile(
    r"RESOURCE_EXHAUSTED|[Oo]ut of memory|\bOOM\b|[Aa]llocation.*"
    r"(failed|exceeds)|[Ff]ailed to allocate|HBM.*exceed", re.DOTALL
)
_COMPILE_RE = re.compile(
    r"INVALID_ARGUMENT|UNIMPLEMENTED|[Cc]ompilation (failure|error)|"
    r"[Ff]ailed to compile|Mosaic|XLA can't deduce|[Ll]owering",
    re.DOTALL,
)
_PALLAS_LOWERING_RE = re.compile(r"Pallas|Mosaic")
_LOST_RE = re.compile(
    r"DATA_LOSS|UNAVAILABLE|ABORTED|INTERNAL|DEADLINE_EXCEEDED|"
    r"[Dd]evice.*(lost|halt|reset)|[Uu]nable to initialize backend|"
    r"[Ff]ailed to initialize|[Nn]o visible.*devic|TPU.*unavailable",
    re.DOTALL,
)

# device attribution: XLA/runtime messages that name the failing chip do
# so with a handful of SINGULAR shapes ("device 3", "device: 3", "TPU_2",
# "TPU:2", "chip #5", "mesh position 4", "core 1"). The word prefix keeps
# byte counts and addresses from parsing as device ids, and the prefix is
# deliberately singular-only: enumeration text in whole-backend failures
# ("visible devices: 0,1") names the SET, not a culprit, and must not
# misattribute a backend-wide loss to its first listed chip
_DEVICE_ID_RE = re.compile(
    r"(?:device|TPU|chip|core|mesh position)[ _:#]+(\d+)",
    re.IGNORECASE,
)


def implicated_devices(exception: BaseException) -> Tuple[int, ...]:
    """The device ids a raw error message names, in order, deduplicated.
    Empty when the failure is unattributable (whole-backend faults,
    allocator OOMs that don't say where)."""
    if isinstance(exception, DeviceException) and exception.device_ids:
        return exception.device_ids
    text = f"{type(exception).__name__}: {exception}"
    seen = []
    for m in _DEVICE_ID_RE.finditer(text):
        did = int(m.group(1))
        if did not in seen:
            seen.append(did)
    return tuple(seen)


def _device_error_strength(exception: BaseException) -> Optional[str]:
    """``"strong"`` when the exception TYPE is device-shaped (jaxlib
    surfaces runtime failures as XlaRuntimeError, a RuntimeError from the
    jaxlib/jax modules — checked structurally so no jaxlib import is
    needed and test doubles with the same shape classify identically);
    ``"weak"`` for plain RuntimeError/MemoryError (and the builtin
    errors the Pallas TPU lowering raises), which only classify on an
    unambiguous message pattern; None for everything else."""
    for klass in type(exception).__mro__:
        if klass.__name__ in (
            "XlaRuntimeError", "JaxRuntimeError", "InternalError"
        ):
            return "strong"
        module = getattr(klass, "__module__", "") or ""
        if module.startswith(("jaxlib", "jax.")) or module == "jax":
            return "strong"
    if isinstance(exception, (RuntimeError, MemoryError)):
        return "weak"
    # the Pallas TPU lowering refuses a kernel (block shape, unsupported
    # op) with a BUILTIN ValueError/NotImplementedError raised while jit
    # lowers the program — a compile refusal that must surface typed
    if isinstance(
        exception, (ValueError, NotImplementedError)
    ) and _PALLAS_LOWERING_RE.search(str(exception)):
        return "weak"
    return None


def classify_device_error(
    exception: BaseException, boundary: str = "execute"
) -> Optional[DeviceException]:
    """Map a raw device-layer error to its typed DeviceException, or None
    when the error is not device-shaped (logic errors must propagate
    untouched). Already-classified exceptions pass through unchanged.

    A plain RuntimeError with no recognizable status pattern stays
    unclassified even at the trace boundary — application bugs raised
    inside an op's update fn must surface as themselves, not trigger a
    pointless CPU fallback under a misleading device-fault type."""
    if isinstance(exception, DeviceException):
        return exception
    strength = _device_error_strength(exception)
    if strength is None:
        return None
    text = f"{type(exception).__name__}: {exception}"
    device_ids = implicated_devices(exception)
    klass = None
    if isinstance(exception, MemoryError) or _OOM_RE.search(text):
        klass = DeviceOOMException
    elif _COMPILE_RE.search(text):
        klass = DeviceCompileException
    elif _LOST_RE.search(text):
        # a loss the message pins on specific chips is a MESH fault — the
        # rest of the mesh is presumed healthy and the degraded-mesh
        # policy can shrink around the dead member(s); an unattributed
        # loss stays a whole-backend DeviceLostException
        klass = MeshDegradedException if device_ids else DeviceLostException
    elif boundary == "trace" and strength == "strong":
        # an unrecognized jax/jaxlib failure while tracing/compiling is a
        # compile failure by position: the program never ran
        klass = DeviceCompileException
    if klass is None:
        return None
    typed = klass(f"[{boundary}] {text}", boundary=boundary)
    typed.device_ids = device_ids
    typed.__cause__ = exception
    return typed


def wrap_if_necessary(exception: BaseException) -> MetricCalculationException:
    """Ensure an arbitrary error is a MetricCalculationException (reference L69)."""
    if isinstance(exception, MetricCalculationException):
        return exception
    wrapped = MetricCalculationRuntimeException(str(exception))
    wrapped.__cause__ = exception
    return wrapped
