"""The plan COST MODEL — one predicted-cost function for a scan plan.

Before round 19 the knowledge of "what makes a plan expensive" was
scattered as unrelated constants: the one-hot histogram crossover caps
(ops/device_policy — now the ``DEEQU_TPU_HIST_CPU_CAP`` /
``DEEQU_TPU_HIST_ACCEL_CAP`` knobs), the host-vs-device grouping
threshold (``DEEQU_TPU_HOST_GROUP_LIMIT``, ops/segment), and the
serving coalescer's batch shaping (``DEEQU_TPU_SERVE_MAX_BATCH``). This
module unifies them behind :class:`PlanCostModel`: a deliberately small
closed-form predictor in abstract COST UNITS (~host-equivalent work;
only ordering and ratios are meaningful, never wall seconds).

Two consumers:

- the serving ADMISSION tier (serve/admission.py): ``retry_after_s`` is
  derived from the queue's summed predicted cost over the observed
  cost-drain rate — a queue of 3 heavy profiling suites now schedules a
  later retry than 3 trivial completeness checks at the same depth —
  and the brownout ladder reads queued-cost pressure alongside queue
  depth;
- the test/bench surface: cost-model MONOTONICITY (a wider or deeper
  plan never predicts cheaper) is a tier-1 contract, because admission
  decisions keyed on a non-monotone predictor would invert under load.

The model's inputs are :class:`PlanFeatures`; the output
:class:`PlanCost` splits transfer / compute / fetch and counts device
dispatches (each dispatch carries a fixed launch overhead — the same
latency term the round-14 crossover sweep measured).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

#: fixed per-dispatch launch overhead, in abstract cost units. Chosen
#: before any chip run; the v5e host's dispatch+fetch floor is ~1 ms
#: (chip_smoke.py, PR 21) — recalibrating it is ROADMAP C7
DISPATCH_OVERHEAD = 4096.0


@dataclass(frozen=True)
class PlanFeatures:
    """What the predictor sees of a plan. Every field is a size or a
    count; the model is nondecreasing in each of them."""

    #: rows the plan scans (per tenant)
    rows: int
    #: plain fused scan ops (monoid folds: completeness, mean, ...)
    scan_ops: int = 0
    #: device-sort ops (KLL/quantile on the sort path): O(n log n)
    sort_ops: int = 0
    #: selection-kernel ops (the histogram selection path): O(n) passes
    select_ops: int = 0
    #: histogram / one-hot segment-fold widths, one per hist dispatch
    hist_widths: Tuple[int, ...] = ()
    #: dense grouping keyspaces, one per grouping pass
    group_keyspaces: Tuple[int, ...] = ()
    #: tenant-axis width (a packed serving batch scales per-tenant work)
    tenants: int = 1
    #: columns riding the encoded (code-plane + LUT decode) ingest
    encoded_columns: int = 0


@dataclass(frozen=True)
class PlanCost:
    """Predicted cost split (abstract units — ordering is the API)."""

    transfer: float
    compute: float
    fetch: float
    dispatches: int

    @property
    def total(self) -> float:
        return (
            self.transfer + self.compute + self.fetch
            + DISPATCH_OVERHEAD * self.dispatches
        )


class PlanCostModel:
    """The predictor. Reads the envcfg knobs it unifies at PREDICT time
    (not construction), so a knob flipped between suites reprices the
    next admission — the registry_snapshot shows the same values the
    model used."""

    def __init__(self, platform: Optional[str] = None):
        self._platform = platform

    def _resolve_platform(self) -> str:
        if self._platform is not None:
            return self._platform
        try:
            import jax

            return jax.default_backend()
        # deequ-lint: ignore[bare-except] -- no resolvable backend: the model prices as CPU rather than refusing to price at all
        except Exception:  # noqa: BLE001
            return "cpu"

    def predict(self, f: PlanFeatures) -> PlanCost:
        """Nondecreasing in every :class:`PlanFeatures` field — the
        monotonicity contract (tier-1 ``plan`` tests): every term below
        is a nonnegative, nondecreasing function of its inputs, and
        features only ever ADD terms."""
        from deequ_tpu.ops.device_policy import hist_accel_cap, hist_cpu_cap
        from deequ_tpu.ops.segment import host_group_limit

        rows = max(int(f.rows), 0)
        tenants = max(int(f.tenants), 1)
        platform = self._resolve_platform()
        cap = hist_cpu_cap() if platform == "cpu" else hist_accel_cap()
        host_limit = host_group_limit()

        # transfer: pack + put of the value/mask planes; an encoded
        # column adds its code plane + LUT
        transfer = float(rows) * (4.0 + 2.0 * max(f.encoded_columns, 0))

        # compute: one linear pass per fused scan/select op; device
        # sorts pay the n log n factor
        log_rows = math.log2(rows + 2)
        compute = float(rows) * (
            max(f.scan_ops, 0)
            + 2.0 * max(f.select_ops, 0)
            + 4.0 * max(f.sort_ops, 0) * log_rows
        )

        # fetch: the fused pass's ONE state-vector fetch
        fetch = 64.0 * (max(f.scan_ops, 0) + max(f.select_ops, 0)
                        + max(f.sort_ops, 0))
        dispatches = 1 if (f.scan_ops or f.select_ops or f.sort_ops) else 0

        # histogram dispatches: past the variant crossover cap the
        # one-hot kernel's plane count stops amortizing (the knob the
        # round-14 sweep priced) — model it as a 4x step, still
        # nondecreasing in width
        for w in f.hist_widths:
            w = max(int(w), 0)
            dispatches += 1
            compute += float(rows) + (float(w) if w <= cap else 4.0 * w)
            fetch += float(w)

        # grouping passes: at or below the host-group limit the counts
        # fold on host (no dispatch); above it, one device bincount +
        # one O(keyspace) counts fetch per pass
        for k in f.group_keyspaces:
            k = max(int(k), 0)
            compute += float(rows) + float(k)
            if rows > host_limit:
                dispatches += 1
                fetch += float(k)

        # the tenant axis multiplies per-tenant work, not dispatches —
        # that IS the coalescer's economy, which is why admission wants
        # cost, not depth: K cheap tenants amortize, K heavy ones don't
        return PlanCost(
            transfer=transfer * tenants,
            compute=compute * tenants,
            fetch=fetch * tenants,
            dispatches=dispatches,
        )

    def estimate_suite(
        self, analyzers: Sequence, rows: int, tenants: int = 1
    ) -> PlanCost:
        """Price one tenant suite from its analyzer list — the
        admission-time entry (serve/service.py calls this per submit).
        Grouping keyspaces are unknown before the scan, so each grouping
        pass prices at its worst admissible case, ``min(rows + 1, dense
        limit)`` — monotone in rows and never an underestimate that
        would let a heavy suite skip the brownout ladder."""
        from deequ_tpu.analyzers.runner import _is_grouping_shared
        from deequ_tpu.ops.segment import DENSE_KEYSPACE_LIMIT

        scan = sort = select = 0
        widths = []
        keyspaces = []
        encoded = 0
        for a in analyzers:
            name = type(a).__name__
            if _is_grouping_shared(a):
                keyspaces.append(min(int(rows) + 1, DENSE_KEYSPACE_LIMIT))
            elif name in ("Histogram",):
                widths.append(min(int(rows) + 1, 1 << 12))
            elif "Quantile" in name or "KLL" in name:
                from deequ_tpu.ops.scan_plan import select_kernel_enabled

                try:
                    kernel = select_kernel_enabled(None)
                # deequ-lint: ignore[bare-except] -- a malformed env knob prices the sort path (the dearer estimate); the engine still raises typed at its own resolve
                except Exception:  # noqa: BLE001
                    kernel = False
                if kernel:
                    select += 1
                else:
                    sort += 1
            else:
                scan += 1
        return self.predict(PlanFeatures(
            rows=int(rows),
            scan_ops=scan,
            sort_ops=sort,
            select_ops=select,
            hist_widths=tuple(widths),
            group_keyspaces=tuple(keyspaces),
            tenants=tenants,
            encoded_columns=encoded,
        ))


#: the process-default model (admission + benches read through this)
PLAN_COST_MODEL = PlanCostModel()
