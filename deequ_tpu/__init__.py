"""deequ_tpu — a TPU-native data-quality verification framework.

A brand-new JAX/XLA implementation of the capabilities of AWS Labs deequ
("unit tests for data", reference: /root/reference): declarative checks are
compiled into a minimal number of fused device scan passes, analyzer states
form commutative monoids that merge across devices (ICI collectives) and
across time (incremental computation), and everything driver-side
(constraints, repository, anomaly detection, profiling, suggestion) is plain
Python operating on collected scalars.

Architecture (see SURVEY.md for the reference layer map):

  - ``deequ_tpu.data``      — columnar tables (dictionary-encoded strings)
  - ``deequ_tpu.expr``      — SQL-subset predicate DSL (where / satisfies)
  - ``deequ_tpu.analyzers`` — ~25 metric analyzers + the fused-scan planner
  - ``deequ_tpu.ops``       — JAX kernels: fused reductions, segment group-by,
                              HLL++, KLL sketches
  - ``deequ_tpu.parallel``  — device mesh + shard_map row-sharding + tagged
                              collective state merges
  - ``deequ_tpu.checks``    — the fluent Check DSL (reference: checks/Check.scala)
  - ``deequ_tpu.verification`` — VerificationSuite entry point
  - ``deequ_tpu.states``    — state persistence (incremental compute backbone)
  - ``deequ_tpu.repository`` — metric time-series store + query DSL
  - ``deequ_tpu.anomaly``   — anomaly detection strategies
  - ``deequ_tpu.profiles``  — column profiler
  - ``deequ_tpu.suggestions`` — constraint suggestion rules
  - ``deequ_tpu.lint``      — static contract checking (jaxpr plan lint +
                              AST repo lint; docs/static_analysis.md)

Numeric note: metric semantics follow the reference's double precision; we
enable jax x64 so device aggregation states are float64 (bandwidth-bound, not
MXU-bound, so this costs little on TPU).
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)


def _compile_cache_dir(environ) -> "str | None":
    """The compilation-cache directory this package sets in code: none
    where ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside
    (jax reads that variable itself), else ONE fixed directory inside
    the checkout — the path is part of jax's cache key, so a directory
    that moves (home, temp, pid, time) never hits."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )


# Persistent XLA compilation cache: each analysis run builds a fresh fused
# program; identical (analyzer-set, schema, chunk-shape) programs then hit
# this cache instead of recompiling — across runs of one checkout and
# across the worker processes a fleet spawns from it (they import this
# module and resolve the same directory).
_cache_dir = _compile_cache_dir(_os.environ)
if _cache_dir is not None:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from deequ_tpu.metrics import (  # noqa: E402
    DoubleMetric,
    Entity,
    HistogramMetric,
    KeyedDoubleMetric,
    Metric,
)
from deequ_tpu.data.table import ColumnarTable  # noqa: E402
from deequ_tpu.data.streaming import StreamingTable, stream_table  # noqa: E402
from deequ_tpu.data.source import ParquetBatchSource  # noqa: E402
from deequ_tpu.analyzers.incremental import (  # noqa: E402
    IncrementalAnalysisStream,
)
from deequ_tpu.exceptions import (  # noqa: E402
    DeviceCompileException,
    DeviceException,
    DeviceHangException,
    DeviceLostException,
    DeviceOOMException,
    MeshDegradedException,
    PeerLostException,
    PlanLintError,
    PlanLintWarning,
    RunBudgetExhaustedException,
)
from deequ_tpu.checks import Check, CheckLevel, CheckStatus  # noqa: E402
from deequ_tpu.verification import (  # noqa: E402
    IncrementalVerificationStream,
    VerificationResult,
    VerificationSuite,
)

__version__ = "0.1.0"


def execution_report() -> dict:
    """Engine execution report — the UNIFIED obs-registry snapshot
    (deequ_tpu/obs/registry; round 11): one call scrapes the whole
    engine. Sections: ``"scan"`` (the ScanStats counters — fused
    passes, rows/bytes, fault-ladder telemetry), ``"retry"``
    (RETRY_TELEMETRY), ``"hbm"`` (device-residency ledger), ``"serve"``
    (queue depth, per-tenant latency histograms, coalesce occupancy),
    ``"env"`` (the DEEQU_TPU_* configuration this process runs under),
    and ``"instruments"`` (the registry's owned
    counters/gauges/histograms). The first-class analogue of the
    reference's test-only SparkMonitor job accounting (SURVEY.md §5).

    The pre-round-11 flat ScanStats shape stays available as
    :func:`scan_execution_report` (a deprecation-free alias — it IS the
    ``"scan"`` section)."""
    from deequ_tpu.obs.registry import REGISTRY

    return REGISTRY.snapshot()


def scan_execution_report() -> dict:
    """The flat ``ScanStats`` dict ``execution_report()`` returned
    before round 11 — kept as a first-class alias (no deprecation):
    identical to ``execution_report()["scan"]``."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    return SCAN_STATS.snapshot()


def execution_report_text() -> str:
    """Prometheus-style text exposition of the unified registry — the
    scrape endpoint payload for online monitoring (ROADMAP item 5)."""
    from deequ_tpu.obs.registry import REGISTRY

    return REGISTRY.render_text()


def reset_execution_report() -> None:
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.reset()
    REGISTRY.reset_instruments()

__all__ = [
    "Check",
    "CheckLevel",
    "CheckStatus",
    "ColumnarTable",
    "DeviceException",
    "DeviceOOMException",
    "DeviceCompileException",
    "DeviceLostException",
    "DeviceHangException",
    "MeshDegradedException",
    "PeerLostException",
    "PlanLintError",
    "RunBudgetExhaustedException",
    "PlanLintWarning",
    "DoubleMetric",
    "Entity",
    "HistogramMetric",
    "KeyedDoubleMetric",
    "Metric",
    "VerificationResult",
    "VerificationSuite",
]
