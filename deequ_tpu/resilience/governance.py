"""Run-level fault governance — ONE budget for the whole composed ladder.

PRs 2/3/5/8 grew a deep resilience ladder (I/O retry -> batch quarantine
-> OOM bisection -> encoded demotion -> mesh reshard -> CPU fallback),
but every rung governs itself: each seam has its own attempt counter and
deadline, and nothing bounds what the COMPOSITION may spend. A run that
hits faults on several seams at once can legally burn minutes in nested
retries while every individual policy stays within its local budget —
exactly what a serving-scale deployment promising p99 latency (the Flare
amortization argument, arXiv:1703.08219) cannot afford.

This module is the one global ledger:

- :class:`RunPolicy` — the value object (``run_deadline`` wall seconds,
  ``max_total_attempts``, ``on_budget_exhausted``); built explicitly,
  via ``VerificationRunBuilder.with_run_budget(...)``, via
  ``run_scan(run_deadline=..., max_total_attempts=...)``, or process-wide
  through ``DEEQU_TPU_RUN_DEADLINE`` / ``DEEQU_TPU_RUN_ATTEMPTS``;
- :class:`RunBudget` — an ARMED policy (start time + charge ledger).
  Every ladder rung charges it: ``resilience/retry.py`` charges failed
  I/O tries, ``ops/scan_engine.py:run_scan`` charges bisections,
  demotions, reshards, and fallback transitions. A clean first try never
  charges — healthy runs spend nothing (``budget_charges`` stays zero;
  what governance costs a healthy run is not measured on the chip);
- :func:`run_budget_scope` — the ambient slot the charge sites resolve,
  so a streaming run's hundred per-batch scans all draw on ONE budget
  instead of paying per batch;
- :func:`fault_state_scope` — snapshot/reset/restore of the process-wide
  fault singletons (``DEVICE_HEALTH``, ``MESH_HEALTH``,
  ``RETRY_TELEMETRY``) plus the installed scan fault hook, so chaos runs
  and tests cannot leak quarantine state or counters into each other.

Exhaustion is TYPED and immediate: the first charge past the budget
raises :class:`~deequ_tpu.exceptions.RunBudgetExhaustedException`. Under
``on_budget_exhausted="degrade"`` (default) the verification layers
convert it into a partial result — failure metrics for what could not
finish plus exact ``unverified_row_ranges`` (the PR-5 partial-result
surface) — instead of raising or hanging; ``"raise"`` propagates it.
When the budget carries a wall deadline, ``run_scan`` additionally caps
the device watchdog at the REMAINING budget, so even a hung device call
terminates (typed) within ``run_deadline``.
"""

from __future__ import annotations

import copy
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Optional

from deequ_tpu.exceptions import RunBudgetExhaustedException

#: the two exhaustion policies (mirrors on_batch_error / on_device_error)
_EXHAUST_MODES = ("degrade", "raise")


def default_run_deadline() -> Optional[float]:
    """Process-wide run wall deadline (seconds) from
    ``DEEQU_TPU_RUN_DEADLINE`` (envcfg registry); unset/empty/0 disables
    it, malformed values raise typed ``EnvConfigError`` — a deployment
    that thinks it is governed must not silently run ungoverned."""
    from deequ_tpu.envcfg import env_value

    return env_value("DEEQU_TPU_RUN_DEADLINE")


def default_max_total_attempts() -> Optional[int]:
    """Process-wide attempt budget from ``DEEQU_TPU_RUN_ATTEMPTS``
    (envcfg registry); unset/empty/0 disables it, malformed values raise
    typed."""
    from deequ_tpu.envcfg import env_value

    return env_value("DEEQU_TPU_RUN_ATTEMPTS")


@dataclass(frozen=True)
class RunPolicy:
    """Run-level fault-budget policy (value object; ``arm()`` starts the
    clock). ``max_total_attempts`` bounds FAILURE-driven attempts across
    every rung of the composed ladder — a clean first try is free, the
    same accounting rule RetryTelemetry uses — and ``run_deadline``
    bounds the run's wall clock from arming."""

    run_deadline: Optional[float] = None
    max_total_attempts: Optional[int] = None
    on_budget_exhausted: str = "degrade"

    def __post_init__(self):
        if self.on_budget_exhausted not in _EXHAUST_MODES:
            raise ValueError(
                f"on_budget_exhausted must be one of {_EXHAUST_MODES}, "
                f"got {self.on_budget_exhausted!r}"
            )
        if self.run_deadline is not None and self.run_deadline <= 0:
            raise ValueError("run_deadline must be positive seconds")
        if self.max_total_attempts is not None and self.max_total_attempts < 0:
            raise ValueError("max_total_attempts must be >= 0")

    def arm(self) -> "RunBudget":
        return RunBudget(self)


class RunBudget:
    """One armed RunPolicy: the charge ledger every ladder rung draws on.

    ``charge(kind)`` is the only spending primitive — it increments the
    total and the per-kind ledger, mirrors into
    ``ScanStats.budget_charges``, and raises
    ``RunBudgetExhaustedException`` the moment the total passes
    ``max_total_attempts`` or the wall clock passes ``run_deadline``.
    Once exhausted, EVERY subsequent charge re-raises — a nested retry
    loop that catches the first raise cannot keep spending."""

    def __init__(self, policy: RunPolicy):
        self.policy = policy
        self.started = time.monotonic()
        self.attempts = 0
        self.charges: Dict[str, int] = {}
        self.exhausted_reason: Optional[str] = None

    # -- clock -----------------------------------------------------------

    def elapsed_seconds(self) -> float:
        return time.monotonic() - self.started

    def remaining_seconds(self) -> Optional[float]:
        """Wall budget left (None when no deadline is set; never
        negative). run_scan caps the device watchdog at this, so a hung
        call converts to a typed DeviceHangException before the run is
        past its deadline."""
        if self.policy.run_deadline is None:
            return None
        return max(self.policy.run_deadline - self.elapsed_seconds(), 0.0)

    # -- spending --------------------------------------------------------

    def charge(self, kind: str, **detail) -> None:
        """Spend one attempt of ``kind`` ('io_retry' | 'oom_bisect' |
        'encoded_demote' | 'mesh_reshard' | 'cpu_fallback' |
        'coalesce_retry' | 'worker_failover' | 'deadline_shed' | ...);
        raises typed when this charge exhausts the budget (or it already
        was exhausted)."""
        self.attempts += 1
        self.charges[kind] = self.charges.get(kind, 0) + 1
        try:
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            SCAN_STATS.budget_charges += 1
        except ImportError:  # charge sites can run before the engine loads
            pass
        # flight-recorder seam: every charge is an instant event on the
        # armed recording (the "which rung ate the budget" timeline);
        # disarmed cost is one integer check
        from deequ_tpu.obs.recorder import current_recorder

        rec = current_recorder()
        if rec is not None:
            rec.event(
                "budget_charge", charge_kind=kind, attempts=self.attempts,
            )
        reason = self.exhausted_reason
        if reason is None:
            cap = self.policy.max_total_attempts
            if cap is not None and self.attempts > cap:
                reason = "max_total_attempts"
            elif (
                self.policy.run_deadline is not None
                and self.elapsed_seconds() >= self.policy.run_deadline
            ):
                reason = "run_deadline"
        if reason is not None:
            self._exhaust(reason, kind, detail)

    def _exhaust(self, reason: str, kind: str, detail: dict) -> None:
        first = self.exhausted_reason is None
        self.exhausted_reason = reason
        if first:
            try:
                from deequ_tpu.ops.scan_engine import SCAN_STATS

                SCAN_STATS.budget_exhaustions += 1
            except ImportError:
                pass
        raise RunBudgetExhaustedException(
            reason,
            ledger=self.snapshot(),
            degraded=self.policy.on_budget_exhausted == "degrade",
            detail=(
                f"last charge kind={kind!r} "
                f"attempts={self.attempts}"
                + (
                    f"/{self.policy.max_total_attempts}"
                    if self.policy.max_total_attempts is not None
                    else ""
                )
                + f" elapsed={self.elapsed_seconds():.3f}s"
                + (
                    f"/{self.policy.run_deadline:g}s"
                    if self.policy.run_deadline is not None
                    else ""
                )
            ),
        )

    def snapshot(self) -> dict:
        """Point-in-time ledger copy (lands on
        ``VerificationResult.run_budget``)."""
        return {
            "run_deadline": self.policy.run_deadline,
            "max_total_attempts": self.policy.max_total_attempts,
            "on_budget_exhausted": self.policy.on_budget_exhausted,
            "attempts": self.attempts,
            "charges": dict(self.charges),
            "elapsed_seconds": round(self.elapsed_seconds(), 6),
            "exhausted": self.exhausted_reason,
        }


# -- ambient budget ----------------------------------------------------------

# THREAD-LOCAL, not a module global: concurrent governed runs (the
# serving-layer shape) must not cross-charge each other's ledgers, and a
# watchdog worker ABANDONED by _governed_attempt keeps executing — with
# a global slot its late charges would land on whatever budget a LATER
# run installed. Thread-locality means the zombie keeps charging its own
# (exhausted) ledger, which re-raises and kills it. The cost is that
# budgets don't flow into spawned threads implicitly; the two engine
# seams that run governed work on worker threads (_governed_attempt,
# _prefetch) re-install the budget explicitly via run_budget_scope.
_AMBIENT = threading.local()


def current_run_budget() -> Optional[RunBudget]:
    """This thread's ambient RunBudget (None = ungoverned)."""
    return getattr(_AMBIENT, "budget", None)


@contextmanager
def run_budget_scope(budget: Optional[RunBudget]):
    """Install ``budget`` as the ambient run budget for the block (on
    THIS thread). Every charge site inside — per-batch scans of a
    streaming run, nested retry wrappers, ladder rungs — draws on this
    one ledger. Worker threads spawned inside the block must re-enter
    the scope with the same budget (the engine's governed-attempt and
    prefetch seams do)."""
    prev = getattr(_AMBIENT, "budget", None)
    _AMBIENT.budget = budget
    try:
        yield budget
    finally:
        _AMBIENT.budget = prev


def resolve_run_policy(
    run_deadline: Optional[float] = None,
    max_total_attempts: Optional[int] = None,
    on_budget_exhausted: Optional[str] = None,
) -> Optional[RunPolicy]:
    """Arguments > env vars > ungoverned (None). The resolution every
    governed entry point (run_scan, do_verification_run) applies."""
    deadline = (
        float(run_deadline)
        if run_deadline is not None
        else default_run_deadline()
    )
    attempts = (
        int(max_total_attempts)
        if max_total_attempts is not None
        else default_max_total_attempts()
    )
    if on_budget_exhausted is not None:
        mode = on_budget_exhausted
    else:
        from deequ_tpu.envcfg import env_value

        mode = env_value("DEEQU_TPU_ON_BUDGET_EXHAUSTED")
    if deadline is None and attempts is None:
        if on_budget_exhausted is not None:
            raise ValueError(
                "on_budget_exhausted was set without a budget to govern: "
                "pass run_deadline and/or max_total_attempts"
            )
        return None
    return RunPolicy(
        run_deadline=deadline,
        max_total_attempts=attempts,
        on_budget_exhausted=mode,
    )


def charge_run_budget(kind: str, **detail) -> None:
    """Charge the ambient budget, if any (the retry layer's one-liner)."""
    budget = current_run_budget()
    if budget is not None:
        budget.charge(kind, **detail)


def try_charge(budget: Optional[RunBudget], kind: str, **detail) -> bool:
    """Charge ``budget`` (None = ungoverned, a no-op) swallowing
    exhaustion: the serving admission tier's shape — a request being
    SHED (``kind="deadline_shed"``: its in-queue deadline expired, or a
    fleet failover found it expired) is already getting a typed terminal
    outcome, so the charge is ledger bookkeeping, not control flow — an
    exhausted budget must not replace the shed's
    ``DeadlineExceededException`` with a budget error. Returns False
    when the charge exhausted (or found exhausted) the budget."""
    if budget is None:
        return True
    try:
        budget.charge(kind, **detail)
        return True
    except RunBudgetExhaustedException:
        return False


def run_budget_remaining() -> Optional[float]:
    """Ambient wall budget left, or None (no budget / no deadline) —
    backoff sleeps cap themselves at this so a retry loop cannot sleep
    past the run deadline."""
    budget = current_run_budget()
    if budget is None:
        return None
    return budget.remaining_seconds()


# -- fault-state isolation ---------------------------------------------------


@contextmanager
def fault_state_scope(reset: bool = True):
    """Isolate the process-wide fault singletons for the block.

    Snapshots ``DEVICE_HEALTH`` / ``MESH_HEALTH`` (ops/device_policy.py)
    and ``RETRY_TELEMETRY`` (resilience/retry.py), plus the installed
    scan fault hook; with ``reset=True`` (default) the hook is removed
    and each singleton starts the block fresh (``reset=False`` keeps
    the current hook and counters live and merely guarantees the
    restore). On exit everything is restored bit-for-bit — the
    snapshot is a DEEP copy, so in-place mutation of e.g.
    ``MESH_HEALTH.consecutive_faults`` inside the block cannot leak
    out. A chaos run (or a test) can quarantine chips, trip breakers,
    and exhaust retries without leaking any of it into the next run;
    this replaces the ad hoc monkeypatching the fault suites
    previously needed."""
    from deequ_tpu.ops.device_policy import (
        DEVICE_HEALTH,
        MESH_HEALTH,
        current_scan_fault_hook,
        install_scan_fault_hook,
    )
    from deequ_tpu.resilience.retry import RETRY_TELEMETRY

    singletons = (DEVICE_HEALTH, MESH_HEALTH, RETRY_TELEMETRY)
    # plain-data state (ints/floats/strs/dicts): deepcopy is safe and
    # makes the snapshot immune to in-place mutation during the block
    saved = [(obj, copy.deepcopy(obj.__dict__)) for obj in singletons]
    prev_hook = current_scan_fault_hook()
    if reset:
        install_scan_fault_hook(None)
        for obj in singletons:
            obj.reset()
    try:
        yield
    finally:
        install_scan_fault_hook(prev_hook)
        for obj, state in saved:
            obj.__dict__.clear()
            obj.__dict__.update(state)
