"""Unified metrics registry — one call scrapes the whole engine.

By round 10 the engine's observables lived on four disjoint surfaces:
``ScanStats`` (~40 scalar fields on a module singleton),
``RETRY_TELEMETRY`` (its own singleton in ``resilience/retry.py``), the
HBM residency ledger (``scan_engine.total_resident_bytes()``), and the
per-service counters on ``VerificationService``. None were scrapeable
together, and the serving layer had no latency distribution at all —
p50/p99 existed only as bench-probe derived numbers.

This module is the union surface:

- :class:`Counter` / :class:`Gauge` / :class:`Histogram` /
  :class:`HistogramFamily` — owned instruments (the serving layer's
  per-tenant submit→resolve latency histograms, queue depth, etc.);
- **collectors** — read-through adapters over the EXISTING singletons.
  The registry does not copy or fork their counters: a collector calls
  the singleton's own ``snapshot()`` at scrape time, so the registry
  view and the legacy view are definitionally the same numbers (chaos
  oracle 7 reads the ledger through the registry for exactly this
  proof);
- :meth:`MetricsRegistry.snapshot` — one nested dict covering
  everything; :meth:`MetricsRegistry.render_text` — a Prometheus-style
  text exposition of the owned instruments plus the scalar collector
  fields.

``deequ_tpu.execution_report()`` returns :func:`REGISTRY.snapshot`;
the pre-round-11 flat ``ScanStats`` shape stays available as
``deequ_tpu.scan_execution_report()`` (a deprecation-free alias — it IS
the registry's ``"scan"`` section).
"""

from __future__ import annotations

import bisect
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


def default_latency_buckets() -> Tuple[float, ...]:
    """Log-spaced latency bounds (seconds): 100µs .. ~2 minutes, four
    buckets per decade — fine enough that p50/p95/p99 on a ~100ms-RTT
    serving path land in distinct buckets, small enough that a histogram
    is ~30 ints."""
    bounds: List[float] = []
    for exp in range(-4, 3):  # 1e-4 .. 1e2
        for frac in (1.0, 1.78, 3.16, 5.62):
            bounds.append(frac * (10.0 ** exp))
    return tuple(bounds)


class Counter:
    """Monotone counter. ``inc`` holds a per-instrument lock: CPython's
    ``value += n`` is LOAD/ADD/STORE, and serve-layer counters are
    incremented from caller threads AND the worker — a lost update
    would skew the submitted/resolved ledger silently."""

    def __init__(self, name: str, doc: str = ""):
        self.name = name
        self.doc = doc
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n

    def reset(self) -> None:
        with self._lock:
            self.value = 0

    def snapshot(self):
        return self.value


class Gauge:
    """Point-in-time value: either set explicitly (``set``) or backed by
    a zero-argument callback evaluated at scrape time (the HBM-ledger
    shape)."""

    def __init__(self, name: str, doc: str = "", fn: Optional[Callable] = None):
        self.name = name
        self.doc = doc
        self.fn = fn
        self.value: Any = 0

    def set(self, value) -> None:
        self.value = value

    def reset(self) -> None:
        if self.fn is None:
            self.value = 0

    def snapshot(self):
        if self.fn is not None:
            return self.fn()
        return self.value


class Histogram:
    """Fixed-bound histogram with count/sum and quantile estimates.

    ``observe`` is a bisect + two adds; ``quantile(q)`` returns the
    upper bound of the bucket where the cumulative count crosses
    ``q * count`` (the standard exposition-side estimate — an upper
    bound, monotone in q)."""

    def __init__(
        self,
        name: str,
        doc: str = "",
        buckets: Optional[Sequence[float]] = None,
    ):
        self.name = name
        self.doc = doc
        self.bounds: Tuple[float, ...] = tuple(
            buckets if buckets is not None else default_latency_buckets()
        )
        if list(self.bounds) != sorted(self.bounds):
            raise ValueError("histogram buckets must be sorted ascending")
        self.counts = [0] * (len(self.bounds) + 1)  # +overflow
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def observe(self, value: float) -> None:
        value = float(value)
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.count += 1
        self.sum += value
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value

    def quantile(self, q: float) -> Optional[float]:
        if not self.count:
            return None
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= target:
                if i < len(self.bounds):
                    return self.bounds[i]
                return self.max  # overflow bucket: the observed max
        return self.max

    def reset(self) -> None:
        self.counts = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "sum": round(self.sum, 6),
            "min": self.min,
            "max": self.max,
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
        }


class HistogramFamily:
    """Labelled histograms (one per tenant) with BOUNDED cardinality.

    A serving layer meeting unbounded distinct tenants must not grow
    host state forever: past ``max_labels`` live label histograms, the
    least-recently-observed label's histogram is evicted (its
    observations survive in the aggregate). The ``_all`` aggregate
    histogram observes every value regardless of label — the fleet-wide
    p50/p95/p99."""

    def __init__(
        self,
        name: str,
        doc: str = "",
        buckets: Optional[Sequence[float]] = None,
        max_labels: int = 256,
    ):
        self.name = name
        self.doc = doc
        self._buckets = buckets
        self.max_labels = int(max_labels)
        self.aggregate = Histogram(name, doc, buckets)
        self._by_label: Dict[str, Histogram] = {}
        self._lock = threading.Lock()
        self.evicted_labels = 0

    def observe(self, label, value: float) -> None:
        key = str(label)
        # the whole observation runs under the family lock: two threads
        # racing on a fresh label would otherwise each build a
        # Histogram and the second re-insert would drop the first's
        # observation, and Histogram's own `count += 1` is not atomic
        with self._lock:
            self.aggregate.observe(value)
            hist = self._by_label.pop(key, None)
            if hist is None:
                hist = Histogram(
                    f"{self.name}{{{key}}}", self.doc, self._buckets
                )
                while len(self._by_label) >= self.max_labels:
                    self._by_label.pop(next(iter(self._by_label)))
                    self.evicted_labels += 1
            self._by_label[key] = hist  # re-insert: most recent last
            hist.observe(value)

    def labels(self) -> List[str]:
        with self._lock:
            return list(self._by_label)

    def label(self, label) -> Optional[Histogram]:
        with self._lock:
            return self._by_label.get(str(label))

    def reset(self) -> None:
        with self._lock:
            self._by_label.clear()
            self.evicted_labels = 0
        self.aggregate.reset()

    def snapshot(self) -> dict:
        with self._lock:
            per_label = {
                key: hist.snapshot() for key, hist in self._by_label.items()
            }
        return {
            "_all": self.aggregate.snapshot(),
            "labels": len(per_label),
            "evicted_labels": self.evicted_labels,
            "per_label": per_label,
        }


class MetricsRegistry:
    """Instrument + collector registry (see module doc)."""

    def __init__(self):
        self._instruments: Dict[str, Any] = {}
        self._collectors: Dict[str, Callable[[], dict]] = {}
        self._lock = threading.Lock()

    # -- registration ----------------------------------------------------

    def _register(self, instrument):
        with self._lock:
            existing = self._instruments.get(instrument.name)
            if existing is not None:
                return existing
            self._instruments[instrument.name] = instrument
            return instrument

    def counter(self, name: str, doc: str = "") -> Counter:
        return self._register(Counter(name, doc))

    def gauge(self, name: str, doc: str = "", fn=None) -> Gauge:
        return self._register(Gauge(name, doc, fn))

    def histogram(self, name: str, doc: str = "", buckets=None) -> Histogram:
        return self._register(Histogram(name, doc, buckets))

    def histogram_family(
        self, name: str, doc: str = "", buckets=None, max_labels: int = 256
    ) -> HistogramFamily:
        return self._register(
            HistogramFamily(name, doc, buckets, max_labels)
        )

    def register_collector(
        self, section: str, fn: Callable[[], dict]
    ) -> None:
        """Register a read-through section: ``fn()`` is called at every
        ``snapshot()`` and its dict lands under ``section``. The
        registry never copies the underlying counters — the section IS
        the singleton's own snapshot."""
        with self._lock:
            self._collectors[section] = fn

    # -- scraping --------------------------------------------------------

    def snapshot(self) -> dict:
        """{section: collector dict} for every collector plus an
        ``"instruments"`` section for the owned
        counters/gauges/histograms — the whole engine in one call."""
        out: Dict[str, Any] = {}
        with self._lock:
            collectors = dict(self._collectors)
            instruments = dict(self._instruments)
        for section, fn in collectors.items():
            out[section] = fn()
        out["instruments"] = {
            name: inst.snapshot() for name, inst in instruments.items()
        }
        return out

    def render_text(self) -> str:
        """Prometheus-style text exposition: owned instruments plus the
        scalar fields of every collector section (lists/dicts — event
        logs, per-label maps — are summarized by length)."""
        lines: List[str] = []

        def emit(name: str, value, doc: str = "") -> None:
            if doc:
                lines.append(f"# HELP {name} {doc}")
            lines.append(f"{name} {value}")

        snap = self.snapshot()
        for section, fields in sorted(snap.items()):
            if section == "instruments":
                continue
            for key, value in sorted(fields.items()):
                metric = f"deequ_tpu_{section}_{key}"
                if isinstance(value, bool):
                    emit(metric, int(value))
                elif isinstance(value, (int, float)):
                    emit(metric, value)
                elif isinstance(value, (list, dict)):
                    emit(f"{metric}_len", len(value))
                elif value is None:
                    continue
                else:
                    emit(f'{metric}{{value="{value}"}}', 1)
        with self._lock:
            instruments = dict(self._instruments)
        for name, inst in sorted(instruments.items()):
            metric = f"deequ_tpu_{name}"
            if isinstance(inst, Counter):
                emit(metric, inst.value, inst.doc)
            elif isinstance(inst, Gauge):
                emit(metric, inst.snapshot(), inst.doc)
            elif isinstance(inst, Histogram):
                s = inst.snapshot()
                if inst.doc:
                    lines.append(f"# HELP {metric} {inst.doc}")
                emit(f"{metric}_count", s["count"])
                emit(f"{metric}_sum", s["sum"])
                for q in ("p50", "p95", "p99"):
                    if s[q] is not None:
                        emit(f'{metric}{{quantile="{q}"}}', s[q])
            elif isinstance(inst, HistogramFamily):
                s = inst.aggregate.snapshot()
                if inst.doc:
                    lines.append(f"# HELP {metric} {inst.doc}")
                emit(f"{metric}_count", s["count"])
                emit(f"{metric}_sum", s["sum"])
                for q in ("p50", "p95", "p99"):
                    if s[q] is not None:
                        emit(f'{metric}{{quantile="{q}"}}', s[q])
                emit(f"{metric}_labels", len(inst.labels()))
        return "\n".join(lines) + "\n"

    def reset_instruments(self) -> None:
        """Reset the OWNED instruments (serve histograms, gauges).
        Collector sections are read-through — resetting their
        singletons stays the singletons' own job
        (``deequ_tpu.reset_execution_report()`` does both)."""
        with self._lock:
            instruments = list(self._instruments.values())
        for inst in instruments:
            inst.reset()


#: the process-wide registry every module registers into
REGISTRY = MetricsRegistry()


# -- the engine's read-through sections (lazy imports: the registry must
#    be importable before the engine, and a collector must not create an
#    import cycle) -----------------------------------------------------------


def _scan_section() -> dict:
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    return SCAN_STATS.snapshot()


def _retry_section() -> dict:
    from deequ_tpu.resilience.retry import RETRY_TELEMETRY

    return RETRY_TELEMETRY.snapshot()


def _hbm_section() -> dict:
    """``resident_bytes``: over all devices; ``resident_bytes_per_device``:
    on the fullest one, which is what the residency budget bounds."""
    from deequ_tpu.ops.scan_engine import (
        _ACTIVE_CACHES,
        resident_bytes_per_device,
        total_resident_bytes,
    )

    return {
        "resident_bytes": total_resident_bytes(),
        "resident_bytes_per_device": resident_bytes_per_device(),
        "resident_tables": len(_ACTIVE_CACHES),
    }


def _env_section() -> dict:
    from deequ_tpu.envcfg import registry_snapshot

    return {
        name: row.get("value", row.get("error"))
        for name, row in registry_snapshot().items()
    }


def _repository_section() -> dict:
    """Read-through over the columnar metrics repository + quality
    monitor singletons (round 13). Guarded on ``sys.modules`` rather
    than importing: a process that never touched the repository layer
    must not pay its import (or report phantom zeros as if it had)."""
    import sys

    out: Dict[str, Any] = {"active": False}
    columnar = sys.modules.get("deequ_tpu.repository.columnar")
    if columnar is not None:
        out["active"] = True
        out.update(columnar.REPO_STATS.snapshot())
    monitor = sys.modules.get("deequ_tpu.repository.monitor")
    if monitor is not None:
        out["active"] = True
        out.update(monitor.MONITOR_STATS.snapshot())
    return out


def _kernels_section() -> dict:
    """Read-through over the histogram kernel tier (round 14,
    ops/histogram_device.py): per-variant bincount/segment-fold dispatch
    counts off ScanStats plus the resolved force knob — the observable
    pair that proves the routed variant actually dispatched."""
    from deequ_tpu.envcfg import EnvConfigError, env_value
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    try:
        forced = env_value("DEEQU_TPU_HIST_VARIANT")
    except EnvConfigError as e:
        # a scrape must report the bad knob, never die on it — the same
        # degrade-to-error-string contract _env_section keeps (the
        # engine itself still raises typed at its own resolve)
        forced = f"error: {e}"
    return {
        "hist_scatter_dispatches": SCAN_STATS.hist_scatter_dispatches,
        "hist_onehot_dispatches": SCAN_STATS.hist_onehot_dispatches,
        "hist_pallas_dispatches": SCAN_STATS.hist_pallas_dispatches,
        "hist_variant_forced": forced,
    }


def _planner_section() -> dict:
    """Read-through over the whole-run plan optimizer (round 19,
    ops/segment.fused_group_counts + serve/plan_cache.SUBPLAN_CACHE):
    fused grouping-pass count, sub-plan cache hit count, and the fusion
    knob as resolved — the observable triple that proves fusion
    actually grouped and sharing actually hit."""
    from deequ_tpu.envcfg import EnvConfigError, env_value
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    try:
        fusion = env_value("DEEQU_TPU_PLAN_FUSION")
    except EnvConfigError as e:
        # same degrade-to-error-string contract as _kernels_section: a
        # scrape reports the bad knob, never dies on it
        fusion = f"error: {e}"
    return {
        "fused_group_passes": SCAN_STATS.fused_group_passes,
        "subplan_cache_hits": SCAN_STATS.subplan_cache_hits,
        "plan_fusion": fusion,
    }


def _control_section() -> dict:
    """Read-through over the closed-loop control plane (round 16,
    deequ_tpu/control): checks per lifecycle state, promotion/demotion
    event counts, shadow-eval outcomes (passed/failed/shed), and the
    profile submit/replay traffic. Guarded on ``sys.modules`` like the
    repository section — a process without a control plane reports
    ``active: False``, not phantom zeros."""
    import sys

    out: Dict[str, Any] = {"active": False}
    control = sys.modules.get("deequ_tpu.control.registry")
    if control is not None:
        out["active"] = True
        out.update(control.CONTROL_STATS.snapshot())
    return out


def _windows_section() -> dict:
    """Read-through over the continuous windowed-verification engine
    (round 20, deequ_tpu/windows): panes opened/closed, closes
    emitted/suppressed/shed, late rows per policy, resumes, and
    state-save failures. Guarded on ``sys.modules`` like the control
    section — a process that never opened a stream reports
    ``active: False``, not phantom zeros."""
    import sys

    out: Dict[str, Any] = {"active": False}
    windows = sys.modules.get("deequ_tpu.windows.engine")
    if windows is not None:
        out["active"] = True
        out.update(windows.WINDOW_STATS.snapshot())
    return out


REGISTRY.register_collector("scan", _scan_section)
REGISTRY.register_collector("retry", _retry_section)
REGISTRY.register_collector("hbm", _hbm_section)
REGISTRY.register_collector("env", _env_section)
REGISTRY.register_collector("repository", _repository_section)
REGISTRY.register_collector("kernels", _kernels_section)
REGISTRY.register_collector("planner", _planner_section)
REGISTRY.register_collector("control", _control_section)
REGISTRY.register_collector("windows", _windows_section)


# -- the serving layer's owned instruments (always-on: one histogram
#    observe per resolved future — the distribution the bench probes
#    previously re-derived from future timestamps per run) ------------------

SERVE_LATENCY = REGISTRY.histogram_family(
    "serve_latency_seconds",
    "per-tenant submit->resolve latency (serve/service.py)",
)
SERVE_QUEUE_DEPTH = REGISTRY.gauge(
    "serve_queue_depth",
    "pending requests at the last worker batch take",
)
SERVE_SUBMITTED = REGISTRY.counter(
    "serve_suites_submitted", "suites accepted by submit()"
)
SERVE_RESOLVED = REGISTRY.counter(
    "serve_suites_resolved", "futures resolved with a result"
)
SERVE_REJECTED = REGISTRY.counter(
    "serve_suites_rejected", "futures rejected with a typed error"
)

#: the admission tier's per-SLO-class instruments (serve/admission.py,
#: round 15): admissions, admission-time rejections (class budget /
#: brownout), and in-queue deadline sheds — per class because the whole
#: point of SLO classes is that these three diverge under overload
#: (best_effort sheds while critical stays clean)
_SLO_CLASSES = ("critical", "standard", "best_effort")
SERVE_ADMITTED_BY_CLASS = {
    cls: REGISTRY.counter(
        f"serve_admitted_{cls}",
        f"{cls}-class submissions accepted by the admission controller",
    )
    for cls in _SLO_CLASSES
}
SERVE_ADMISSION_REJECTED_BY_CLASS = {
    cls: REGISTRY.counter(
        f"serve_admission_rejected_{cls}",
        f"{cls}-class admission decisions refused typed (queue full / "
        "class budget / brownout / inflight cap) — counted per "
        "PER-WORKER decision, so one fleet submission spilled past k "
        "refusing workers counts k refusals (and one admission where "
        "it lands)",
    )
    for cls in _SLO_CLASSES
}
SERVE_SHED_BY_CLASS = {
    cls: REGISTRY.counter(
        f"serve_shed_{cls}",
        f"accepted {cls}-class requests shed typed pre-dispatch "
        "(in-queue deadline expiry, incl. at fleet failover)",
    )
    for cls in _SLO_CLASSES
}
SERVE_BROWNOUT_LEVEL = REGISTRY.gauge(
    "serve_brownout_level",
    "brownout ladder level of the most recent per-service transition "
    "(0 = healthy, 1 = shed best_effort admissions, 2 = + per-tenant "
    "inflight cap, 3 = critical only). Exact for a single service; in "
    "a fleet this is last-writer-wins across workers — read the fleet "
    "section's per-worker brownout_level for the true per-worker view",
)


# -- the fleet tier's owned instruments (serve/fleet.py; the "fleet"
#    collector section — per-worker queue depths + the hot-plan feed —
#    is registered by the fleet module itself, read-through over the
#    active fleet) ------------------------------------------------------------

FLEET_FAILOVERS = REGISTRY.counter(
    "fleet_failovers",
    "worker-loss failover events (serve/fleet.py; one per lost worker)",
)
FLEET_WORKERS_ALIVE = REGISTRY.gauge(
    "fleet_workers_alive",
    "alive workers of the active VerificationFleet",
)


# -- the process-fleet tier's owned instruments (serve/pfleet.py +
#    serve/ledger.py, PR 17; the "pfleet" collector section —
#    per-worker-process liveness + inflight + ledger state — is
#    registered by the pfleet module itself) ----------------------------------

LEDGER_APPENDS = REGISTRY.counter(
    "fleet_ledger_appends",
    "durable request-ledger frames appended (serve/ledger.py: one per "
    "accept, one tombstone per resolve — each fsynced before the "
    "submit/resolution proceeds)",
)
PFLEET_WORKERS_ALIVE = REGISTRY.gauge(
    "pfleet_workers_alive",
    "alive worker PROCESSES of the active ProcessFleet",
)
PFLEET_REDISPATCHES = REGISTRY.counter(
    "pfleet_redispatches",
    "accepted requests re-sent to a surviving worker process after the "
    "placed worker's process died (SIGKILL included)",
)
PFLEET_RESUMED = REGISTRY.counter(
    "pfleet_resumed",
    "outstanding ledger records a resuming coordinator replayed "
    "(coordinator kill-and-resume, serve/pfleet.py)",
)
FENCING_REJECTIONS = REGISTRY.counter(
    "pfleet_fencing_rejections",
    "submits refused typed (StaleEpochException) because the "
    "coordinator's lease epoch was fenced out by a successor "
    "(serve/lease.py, PR 18) — one per fence event plus one per "
    "subsequent submit on the fenced coordinator",
)
ZOMBIE_RESULTS_IGNORED = REGISTRY.counter(
    "pfleet_zombie_results_ignored",
    "result frames a coordinator dropped because they carried a "
    "stale epoch or arrived after it was fenced — the zombie side of "
    "split-brain adds zero effects",
)
CRASHPOINTS_SURVIVED = REGISTRY.counter(
    "crashpoints_survived",
    "crashpoint-matrix cells (write seam x byte boundary, "
    "resilience/vfs_faults.py) a durable store recovered from typed "
    "with no silent data loss",
)


def _serve_section() -> dict:
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    tenants = SCAN_STATS.coalesced_tenants
    padded = SCAN_STATS.coalesce_padded_slots
    lat = SERVE_LATENCY.aggregate.snapshot()
    return {
        "submitted": SERVE_SUBMITTED.value,
        "resolved": SERVE_RESOLVED.value,
        "rejected": SERVE_REJECTED.value,
        "queue_depth": SERVE_QUEUE_DEPTH.snapshot(),
        "coalesce_occupancy": round(
            tenants / max(tenants + padded, 1), 4
        ),
        "latency": lat,
        "latency_tenants": len(SERVE_LATENCY.labels()),
        "brownout_level": SERVE_BROWNOUT_LEVEL.snapshot(),
        "admitted_by_class": {
            cls: c.value for cls, c in SERVE_ADMITTED_BY_CLASS.items()
        },
        "admission_rejected_by_class": {
            cls: c.value
            for cls, c in SERVE_ADMISSION_REJECTED_BY_CLASS.items()
        },
        "shed_by_class": {
            cls: c.value for cls, c in SERVE_SHED_BY_CLASS.items()
        },
    }


REGISTRY.register_collector("serve", _serve_section)
