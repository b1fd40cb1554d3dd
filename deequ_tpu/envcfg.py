"""The DEEQU_TPU_* environment-variable registry — ONE validated parser.

By round 9 the engine had grown eight-plus hand-rolled ``os.environ``
parsers, each with its own validation posture: the kernel switches
rejected anything but ``'' | '0' | '1'``, the scan window raised on
non-integers, the governance deadlines silently swallowed garbage into
"disabled", and nothing anywhere could LIST the switches a deployment
was actually running under. This module is the consolidation the round-10
serve switches land on instead of adding a ninth dialect:

- :class:`EnvVar` — one registered variable: name, kind, default,
  constraints, and the one-line doc the registry can print;
- :func:`env_value` — the single parse/validate path. Malformed values
  raise :class:`~deequ_tpu.exceptions.EnvConfigError` (a ``ValueError``
  subclass, so existing ``except ValueError`` validation handling keeps
  working) with the variable name, the offending value, and what would
  have been accepted;
- :func:`registry_snapshot` — {name: (raw, parsed, doc)} for every
  registered variable, the "what is this process configured as"
  observable (``python -m deequ_tpu.lint`` readers and execution
  reports can dump it).

Kinds (matching the semantics the scattered parsers had established,
now uniform):

- ``flag01`` — ``'' | '0' | '1'`` strictly; anything else raises
  (the DEEQU_TPU_SELECT_KERNEL / DEEQU_TPU_ENCODED_INGEST posture,
  now shared by every on/off switch);
- ``lenient_flag`` — any value other than ``'0'`` is on
  (DEEQU_TPU_DISABLE_NATIVE, the one variable left of this kind:
  scripts in the wild export ``=yes``; tightening it retroactively
  would flip behavior under existing deployments);
- ``int`` / ``float`` — parsed with optional ``minimum``; empty/unset
  yields the default. ``zero_disables=True`` maps 0 (and negatives) to
  None — the watchdog/deadline convention "0 means off";
- ``choice`` — one of ``choices`` or empty (default).

Variables parse STRICTLY by default: a typo like
``DEEQU_TPU_RUN_DEADLINE=5m`` is a misconfiguration the run must refuse,
not silently ignore (the pre-round-10 governance parsers disabled the
budget on garbage — a deployment that THOUGHT it was governed wasn't).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from deequ_tpu.exceptions import EnvConfigError

_KINDS = ("flag01", "lenient_flag", "int", "float", "choice", "str")


@dataclass(frozen=True)
class EnvVar:
    """One registered DEEQU_TPU_* variable (see module doc for kinds)."""

    name: str
    kind: str
    default: Any = None
    minimum: Optional[float] = None
    zero_disables: bool = False
    choices: Tuple[str, ...] = ()
    doc: str = ""

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown EnvVar kind {self.kind!r}")
        if self.kind == "choice" and not self.choices:
            raise ValueError(f"{self.name}: choice kind needs choices")


_REGISTRY: Dict[str, EnvVar] = {}


def register(var: EnvVar) -> EnvVar:
    """Add one variable to the registry (idempotent for identical specs;
    a conflicting re-registration is a programming error)."""
    existing = _REGISTRY.get(var.name)
    if existing is not None and existing != var:
        raise ValueError(
            f"conflicting registration for {var.name}: {existing} vs {var}"
        )
    _REGISTRY[var.name] = var
    return var


def _parse(var: EnvVar, raw: str) -> Any:
    if var.kind == "flag01":
        if raw not in ("0", "1"):
            raise EnvConfigError(
                var.name, raw, "'' (default), '0' (off) or '1' (on)"
            )
        return raw != "0"
    if var.kind == "lenient_flag":
        return raw != "0"
    if var.kind == "int":
        try:
            val = int(raw)
        except ValueError:
            raise EnvConfigError(var.name, raw, "an integer") from None
        return _bound(var, val)
    if var.kind == "float":
        try:
            val = float(raw)
        except ValueError:
            raise EnvConfigError(var.name, raw, "a number") from None
        return _bound(var, val)
    if var.kind == "choice":
        if raw not in var.choices:
            raise EnvConfigError(
                var.name, raw, f"one of {list(var.choices)}"
            )
        return raw
    return raw  # "str"


def _bound(var: EnvVar, val):
    if var.zero_disables and val <= 0:
        return None
    if var.minimum is not None and val < var.minimum:
        raise EnvConfigError(
            var.name, str(val), f"a value >= {var.minimum:g}"
        )
    return val


def env_value(name: str) -> Any:
    """Parse + validate one registered variable from the process
    environment. Unset/empty yields the registered default; malformed
    values raise typed :class:`EnvConfigError`."""
    var = _REGISTRY.get(name)
    if var is None:
        raise KeyError(f"{name} is not a registered DEEQU_TPU env var")
    raw = os.environ.get(name, "")
    if var.kind != "lenient_flag":
        raw = raw.strip()
    if raw == "":
        return var.default
    return _parse(var, raw)


def registry_snapshot() -> Dict[str, dict]:
    """{name: {raw, value|error, doc}} for every registered variable —
    the configuration observable for execution reports."""
    out: Dict[str, dict] = {}
    for name, var in sorted(_REGISTRY.items()):
        raw = os.environ.get(name)
        row = {"raw": raw, "doc": var.doc, "kind": var.kind}
        try:
            row["value"] = env_value(name)
        except EnvConfigError as e:
            row["error"] = str(e)
        out[name] = row
    return out


# -- the registered variables (one declaration point; the modules that
#    consume them import these constants so the name can never drift
#    from the parse site) ---------------------------------------------------

SCAN_WINDOW = register(EnvVar(
    "DEEQU_TPU_SCAN_WINDOW", "int", default=None, minimum=1,
    doc="pipelined-dispatch window (chunks in flight) for fused scans",
))
SELECT_KERNEL = register(EnvVar(
    "DEEQU_TPU_SELECT_KERNEL", "flag01", default=True,
    doc="0 keeps the device-sort quantile path (A/B hatch, PR 6)",
))
ENCODED_INGEST = register(EnvVar(
    "DEEQU_TPU_ENCODED_INGEST", "flag01", default=True,
    doc="0 packs every column decoded (A/B hatch, PR 8)",
))
HIST_VARIANT = register(EnvVar(
    "DEEQU_TPU_HIST_VARIANT", "choice", default=None,
    choices=("scatter", "onehot", "pallas"),
    doc="force the histogram/segment-fold kernel variant "
        "(ops/histogram_device.py; unset = device_policy auto — the "
        "kernel A/B hatch, PR 14)",
))
HOST_GROUP_LIMIT = register(EnvVar(
    "DEEQU_TPU_HOST_GROUP_LIMIT", "int", default=None, minimum=0,
    doc="row count at or below which grouping bincounts/uniques run on "
        "HOST instead of paying a device round trip (ops/segment.py "
        "latency regime; unset = the module default 2^14; sweepable by "
        "the kernel A/B probe, PR 14)",
))
HIST_CPU_CAP = register(EnvVar(
    "DEEQU_TPU_HIST_CPU_CAP", "int", default=None, minimum=1,
    doc="widest keyspace the one-hot matmul kernel accepts on a CPU "
        "backend (ops/device_policy.resolve_hist_variant crossover; "
        "unset = the module default 32 — the round-14 sweep point; read "
        "by the plan-cost model, PR 19 autotuner groundwork)",
))
HIST_ACCEL_CAP = register(EnvVar(
    "DEEQU_TPU_HIST_ACCEL_CAP", "int", default=None, minimum=1,
    doc="widest keyspace the one-hot matmul kernel accepts on an "
        "accelerator backend (unset = the module default 2^17 — the "
        "factored bf16 planes bound; read by the plan-cost model, "
        "PR 19 autotuner groundwork)",
))
PLAN_FUSION = register(EnvVar(
    "DEEQU_TPU_PLAN_FUSION", "flag01", default=True,
    doc="0 disables cross-pass grouping fusion (the whole-run plan "
        "optimizer's single-dispatch grouping path, PR 19 A/B hatch)",
))
DEVICE_DEADLINE = register(EnvVar(
    "DEEQU_TPU_DEVICE_DEADLINE", "float", default=None,
    zero_disables=True,
    doc="compute-watchdog deadline (s) on blocking device calls",
))
SHARD_DEADLINE = register(EnvVar(
    "DEEQU_TPU_SHARD_DEADLINE", "float", default=None,
    zero_disables=True,
    doc="per-shard straggler deadline (s) on multi-chip dispatches",
))
RUN_DEADLINE = register(EnvVar(
    "DEEQU_TPU_RUN_DEADLINE", "float", default=None, zero_disables=True,
    doc="run-level wall budget (s) for the composed fault ladder",
))
RUN_ATTEMPTS = register(EnvVar(
    "DEEQU_TPU_RUN_ATTEMPTS", "int", default=None, zero_disables=True,
    doc="run-level failure-attempt budget for the composed fault ladder",
))
ON_BUDGET_EXHAUSTED = register(EnvVar(
    "DEEQU_TPU_ON_BUDGET_EXHAUSTED", "choice", default="degrade",
    choices=("degrade", "raise"),
    doc="run-budget exhaustion policy",
))
PLAN_LINT = register(EnvVar(
    "DEEQU_TPU_PLAN_LINT", "choice", default="off",
    choices=("error", "warn", "off"),
    doc="static plan-lint enforcement mode for scan programs",
))
GROUP_MEMORY_BUDGET = register(EnvVar(
    "DEEQU_TPU_GROUP_MEMORY_BUDGET", "int", default=None, minimum=1,
    doc="host-RSS budget (bytes) for grouping state before spilling",
))
DISABLE_NATIVE = register(EnvVar(
    "DEEQU_TPU_DISABLE_NATIVE", "lenient_flag", default=False,
    doc="any non-'0' value disables the native (C-extension) kernels",
))
SERVE_MAX_BATCH = register(EnvVar(
    "DEEQU_TPU_SERVE_MAX_BATCH", "int", default=64, minimum=1,
    doc="max tenant suites coalesced into one packed dispatch (PR 10)",
))
SERVE_COALESCE_WINDOW = register(EnvVar(
    "DEEQU_TPU_SERVE_COALESCE_WINDOW", "float", default=0.002,
    minimum=0.0,
    doc="seconds the serve worker waits for co-batchable submissions",
))
SLO_CLASS = register(EnvVar(
    "DEEQU_TPU_SLO_CLASS", "choice", default="standard",
    choices=("critical", "standard", "best_effort"),
    doc="default SLO class for submissions that carry none "
        "(serve/admission.py, PR 15)",
))
SLO_DEADLINE_MS = register(EnvVar(
    "DEEQU_TPU_SLO_DEADLINE_MS", "float", default=None, zero_disables=True,
    doc="default absolute submit->dispatch deadline (ms) for submissions "
        "that carry no SLO; expired requests shed typed pre-dispatch "
        "(unset/0 = no deadline)",
))
BROWNOUT = register(EnvVar(
    "DEEQU_TPU_BROWNOUT", "flag01", default=True,
    doc="0 disables the serving brownout ladder (admission-side load "
        "shedding by SLO class; computation is never degraded)",
))
FLEET_WORKERS = register(EnvVar(
    "DEEQU_TPU_FLEET_WORKERS", "int", default=None, minimum=1,
    doc="VerificationFleet worker count (PR 12; unset = one per device, "
        "capped at 4)",
))
HEARTBEAT_INTERVAL = register(EnvVar(
    "DEEQU_TPU_HEARTBEAT_INTERVAL", "float", default=0.25, minimum=0.005,
    doc="fleet membership heartbeat-probe period (s) for worker liveness",
))
FAILOVER_RETRIES = register(EnvVar(
    "DEEQU_TPU_FAILOVER_RETRIES", "int", default=2, minimum=0,
    doc="max worker-loss re-dispatches one accepted request may ride "
        "before it rejects typed (WorkerLostException)",
))
FLEET_TRANSPORT = register(EnvVar(
    "DEEQU_TPU_FLEET_TRANSPORT", "choice", default="proc",
    choices=("proc", "loopback"),
    doc="ProcessFleet worker isolation (serve/pfleet.py, PR 17): 'proc' "
        "spawns one worker PROCESS per member over socketpair frame "
        "transport; 'loopback' runs the identical protocol loop in "
        "threads (deterministic tests, single-process deployments)",
))
FLEET_LEDGER_DIR = register(EnvVar(
    "DEEQU_TPU_FLEET_LEDGER_DIR", "str", default=None,
    doc="directory for the fleet's durable checksummed request ledger "
        "(serve/ledger.py): accepted work persists at accept time and "
        "a killed coordinator resumes from it (unset = in-RAM only, "
        "the pre-PR-17 durability)",
))
COORD_RESUME = register(EnvVar(
    "DEEQU_TPU_COORD_RESUME", "flag01", default=True,
    doc="0 disables replaying outstanding request-ledger records when a "
        "fleet opens over a ledger_dir that already holds them "
        "(forensics mode: the ledger is read but nothing re-dispatches)",
))
LEASE_DIR = register(EnvVar(
    "DEEQU_TPU_LEASE_DIR", "str", default=None,
    doc="directory for the coordinator's durable epoch-fenced lease "
        "(serve/lease.py, PR 18); unset defaults to the fleet's "
        "ledger_dir — the lease fences the same durable state the "
        "ledger holds",
))
LEASE_TTL = register(EnvVar(
    "DEEQU_TPU_LEASE_TTL", "float", default=30.0, minimum=0.05,
    doc="coordinator-lease TTL (s): the liveness knob (renewal cadence "
        "is TTL/2; takeover politeness window) — safety is the epoch "
        "ordering, never the clock",
))
FENCING = register(EnvVar(
    "DEEQU_TPU_FENCING", "flag01", default=None,
    doc="1 forces epoch fencing on, 0 forces it off; unset = on exactly "
        "when a ledger_dir is configured (split-brain safety for the "
        "process fleet, serve/lease.py)",
))
REPO_SEGMENT_ROWS = register(EnvVar(
    "DEEQU_TPU_REPO_SEGMENT_ROWS", "int", default=4096, minimum=1,
    doc="target scalar-metric rows per compacted columnar-repository "
        "append segment (repository/columnar.py)",
))
REPO_TTL = register(EnvVar(
    "DEEQU_TPU_REPO_TTL", "float", default=None, zero_disables=True,
    doc="retention window for the columnar metrics repository, in "
        "dataset-date units (the ResultKey.dataset_date axis): at "
        "compaction, results older than (newest live date - TTL) are "
        "dropped (unset/0 = keep everything)",
))
MONITOR = register(EnvVar(
    "DEEQU_TPU_MONITOR", "flag01", default=True,
    doc="0 disables QualityMonitor observation process-wide (saves and "
        "serving unaffected; alerts stop)",
))
PROMOTE_WINDOWS = register(EnvVar(
    "DEEQU_TPU_PROMOTE_WINDOWS", "int", default=3, minimum=1,
    doc="consecutive clean (anomaly-free, shadow-passing) profile "
        "windows a shadow check must accumulate before the control "
        "plane promotes it to enforcing (control/promotion.py)",
))
TRACE = register(EnvVar(
    "DEEQU_TPU_TRACE", "flag01", default=False,
    doc="1 arms the process-global flight recorder (deequ_tpu/obs)",
))
TRACE_CAPACITY = register(EnvVar(
    "DEEQU_TPU_TRACE_CAPACITY", "int", default=None, minimum=1,
    doc="ring-buffer capacity (records) of the env-armed flight recorder",
))
WINDOW_SIZE_S = register(EnvVar(
    "DEEQU_TPU_WINDOW_SIZE_S", "float", default=60.0, minimum=1e-6,
    doc="default event-time window size, in seconds, for windowed "
        "verification streams (deequ_tpu/windows) that do not pass an "
        "explicit WindowSpec",
))
WINDOW_SLIDE_S = register(EnvVar(
    "DEEQU_TPU_WINDOW_SLIDE_S", "float", default=None, minimum=1e-6,
    doc="default window slide, in seconds, for windowed verification "
        "streams (unset = tumbling: slide == size); must not exceed the "
        "window size",
))
WATERMARK_LAG_S = register(EnvVar(
    "DEEQU_TPU_WATERMARK_LAG_S", "float", default=5.0, minimum=0.0,
    doc="bounded-disorder allowance, in seconds: the per-stream "
        "watermark trails the max observed event time by this lag; rows "
        "older than the watermark are LATE and route by the late policy",
))
LATE_POLICY = register(EnvVar(
    "DEEQU_TPU_LATE_POLICY", "choice", default="drop",
    choices=("drop", "side_output", "refuse"),
    doc="routing for rows behind the watermark: 'drop' counts them "
        "(ScanStats.late_rows), 'side_output' quarantines their "
        "batch-aligned row ranges on the partial-result surface, "
        "'refuse' raises typed LateDataException",
))
