"""deequ_tpu.serve — the long-lived multi-tenant verification service.

The millions-of-users shape (BASELINE config 1) is many SMALL suites
arriving concurrently, not one giant scan — and per submitted run the
engine pays fixed costs that dwarf the compute at small row counts: a
trace+compile for any fresh plan, a plan-lint trace, and a dispatch +
fetch round trip (~4 fixed-latency round trips per run). Flare's
thesis (arXiv:1703.08219) is that native whole-query compilation only
wins when its cost is amortized across repeated executions; this package
is that amortization for deequ-tpu (ROADMAP item 2, closing item 5's
plan/executor split another notch):

- :mod:`plan_cache` — the COMPILED-PLAN CACHE: suites are fingerprinted
  by (schema, analyzer set — predicates included, packer layout, row
  count) and a repeat tenant reuses the built ops, the traced+compiled
  vmapped program, and the memoized plan-lint verdict. Observable as
  ``ScanStats.plan_cache_hits`` / ``plan_cache_misses``; the hard
  contract (bench + tier-1) is that a repeat suite adds ZERO traces.
- :mod:`executor` — the REQUEST COALESCER's packed executor: N pending
  tenant tables pack into ONE ``(K, n)`` buffer stack and run as ONE
  vmapped fused dispatch with ONE device->host fetch (per-tenant state
  slices unpacked on the host), so the round-trip cost is paid once per
  BATCH of runs. Members coalesce only on exact (plan, layout, row
  count) agreement — per-slice results are bit-identical to serial
  per-tenant runs (the run_scan_group construction, vmap semantics);
  the tenant axis pads to a pow2 bucket with all-invalid dummy slices
  whose inertness vmap's per-slice independence guarantees. Faults
  bisect the TENANT axis (split, retry halves) so one poison tenant is
  localized in O(log K) and degrades only its own slice.
- :mod:`service` — :class:`VerificationService`: the async
  ``submit(...) -> VerificationFuture`` API, a bounded worker loop with
  a coalescing window, per-tenant run budgets (PR 9 governance; one
  tenant's budget exhaustion never sinks a batch), tenant quarantine
  for repeat offenders, and kill-and-resume of the pending queue.
- :mod:`admission` — the OVERLOAD tier (round 15): per-tenant
  :class:`Slo` classes, typed admission control with ``retry_after_s``,
  a deadline-aware class-tiered tenant-fair queue (expired requests
  shed typed pre-dispatch), and the 3-level brownout ladder — overload
  changes WHICH requests run, never how (completed results stay
  bit-identical to an unloaded serial run).
- :mod:`pfleet` (+ :mod:`transport`, :mod:`ledger`, :mod:`pworker`) —
  the PROCESS fleet (round 17): coordinator + N worker processes
  behind a checksummed frame transport, plan warmup via shipped
  fingerprints (the joiner mints the service's own ``PlanKey``), typed
  backpressure reconstructed from wire fields, a durable accept-time
  request ledger with torn-tail recovery, real-SIGKILL worker
  failover, and coordinator kill-and-resume onto original futures.

See docs/serving.md for cache-key semantics, coalescing/padding rules,
and the isolation ladder.
"""

from deequ_tpu.serve.admission import (
    AdmissionController,
    BrownoutController,
    Slo,
    TenantFairQueue,
)
from deequ_tpu.serve.fleet import FleetConfig, VerificationFleet
from deequ_tpu.serve.ledger import RequestLedger
from deequ_tpu.serve.membership import FleetMembership, WorkerLossReport
from deequ_tpu.serve.pfleet import ProcessFleet, ProcessFleetConfig
from deequ_tpu.serve.plan_cache import PlanCache, PlanKey, ServePlan
from deequ_tpu.serve.router import ConsistentHashRouter, route_digest
from deequ_tpu.serve.service import (
    PendingWork,
    ServeConfig,
    VerificationFuture,
    VerificationService,
)

__all__ = [
    "AdmissionController",
    "BrownoutController",
    "ConsistentHashRouter",
    "FleetConfig",
    "FleetMembership",
    "PendingWork",
    "PlanCache",
    "PlanKey",
    "ProcessFleet",
    "ProcessFleetConfig",
    "RequestLedger",
    "route_digest",
    "ServePlan",
    "ServeConfig",
    "Slo",
    "TenantFairQueue",
    "VerificationFleet",
    "VerificationFuture",
    "VerificationService",
    "WorkerLossReport",
]
