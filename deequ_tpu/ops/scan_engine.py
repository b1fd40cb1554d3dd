"""The fused scan engine — one compiled device pass for N analyzers.

This is the TPU-native analogue of the reference's single
``data.agg(expr_1 .. expr_K)`` job (analyzers/runners/AnalysisRunner.scala:
303-325, where all scan-shareable analyzers' aggregation expressions are
concatenated into one Spark scan). Here every scan-shareable analyzer
contributes a ``ScanOp``:

  - ``columns``: which columns its update function reads,
  - ``update(vals, row_valid, xp, n) -> pytree``: a pure JAX function mapping
    one row chunk to a partial-state pytree,
  - ``tags``: a matching pytree of reduction tags ('sum' | 'min' | 'max')
    describing how partial states combine.

The engine pads the table into fixed-size chunks (static shapes => one XLA
compilation), jits ONE function computing every op's partial state per chunk,
and — when a device mesh is active — wraps it in ``shard_map`` with the rows
sharded across the mesh and per-leaf XLA collectives (psum for sums,
all_gather + reduce for min/max, over ICI) performing the cross-device
monoid merge. Partial states across chunks are folded on the host (they are
tiny).

All leaves reduce elementwise with sum/min/max; this covers every
scan-shareable analyzer including the sketches (HLL register file merges via
elementwise max, DataType histogram via vector sum). KLL gets its own pass
(see ops/kll.py), mirroring the reference's KLLRunner extra pass.
"""

from __future__ import annotations

import itertools
import math
import os
import threading
import weakref
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import (
    DeviceException,
    DeviceHangException,
    DeviceOOMException,
)
from deequ_tpu.expr.eval import Val
from deequ_tpu.obs.recorder import (
    SEAM_NAMES,
    bind_seam_counters,
    current_recorder,
    device_fed,
    device_ready,
    fed_mark,
    maybe_arm_from_env,
    recording_scope,
    resolve_recorder,
    seam,
    seam_fields,
    seam_ids,
    worker_seams,
)
from deequ_tpu.ops.device_policy import (
    DEVICE_HEALTH,
    MESH_HEALTH,
    current_watchdog_call_abandoned,
    default_device_deadline,
    _call_with_deadline,
    default_shard_deadline,
    device_call,
    device_fetch,
    wait_then_copy,
    install_scan_fault_hook,  # noqa: F401 — re-exported: the seam lives here
)
from deequ_tpu.parallel.mesh import (
    ROW_AXIS,
    current_mesh,
    mesh_device_ids,
    mesh_excluding,
    shard_map,
)

DEFAULT_CHUNK_ROWS = 1 << 20
# target bytes per packed chunk transfer: large enough to amortize the
# per-transfer latency of slow host<->device links, small enough to
# double-buffer comfortably in HBM
DEFAULT_CHUNK_BYTES = 512 << 20
MAX_CHUNK_ROWS = 1 << 23
# persist()'s resident chunks: what ONE device holds of a chunk, and the
# most rows a chunk has whatever the mesh
RESIDENT_CHUNK_BYTES = 2 << 30
MAX_RESIDENT_CHUNK_ROWS = 1 << 25
# streaming chunks are smaller: several live copies per chunk exist at once
# (decoded batch in the prefetch queue, packed buffers, in-flight transfers),
# so the host-RSS bound is ~6x the chunk size
STREAM_CHUNK_BYTES = 128 << 20

# pipelined-dispatch window default: how many chunks stay in flight before
# the engine blocks on the oldest (bounds pinned host buffers / queued
# device work). Override per call (run_scan(window=...)) or process-wide
# via DEEQU_TPU_SCAN_WINDOW.
DEFAULT_SCAN_WINDOW = 3

# device-fold gather capacity for STREAMS (chunk count unknown up front):
# the on-device accumulator reserves this many chunk slots for 'gather'
# leaves; past it the accumulator drains to the host (one fetch) and a
# fresh one continues — fetches stay O(chunks / capacity), and the f64
# 'sum' regrouping that restart introduces is ulp-level (docs/numerics.md)
STREAM_FOLD_CAPACITY = 512

# floor for the budget-derived watchdog deadline: an almost-expired run
# budget must still give each device call a beat to finish (a 0-second
# watchdog would convert every healthy dispatch into a spurious hang) —
# the budget's own wall check then terminates the run typed right after
MIN_BUDGET_WATCHDOG_SECONDS = 0.05

# in-memory scans with 'gather' leaves size the accumulator to the exact
# chunk count; past this many chunks they keep the host fold instead —
# the capacity scales the gather region, and OOM bisection (which DOUBLES
# n_chunks per halving) must not grow the accumulator on an already-OOM
# device (each capacity is also a fresh merge-program trace)
MAX_FOLD_CAPACITY = 1024


def _resolve_scan_window(window: Optional[int] = None) -> int:
    """The pipelined-dispatch window: explicit argument wins, then the
    DEEQU_TPU_SCAN_WINDOW env var (envcfg registry), then
    DEFAULT_SCAN_WINDOW. Validated >= 1 (a zero/negative window would
    deadlock the dispatch loop)."""
    from deequ_tpu.envcfg import env_value

    if window is None:
        window = env_value("DEEQU_TPU_SCAN_WINDOW")
        if window is None:
            window = DEFAULT_SCAN_WINDOW
    window = int(window)
    if window < 1:
        raise ValueError(f"scan window must be >= 1, got {window}")
    return window


def device_foldable(op: "ScanOp") -> bool:
    """True when ``op``'s chunk partials can fold ON DEVICE: sum/min/max
    leaves merge elementwise and 'gather' leaves append into a
    fixed-capacity device buffer. Ops with a ``compact()`` hook (KLL)
    need host-side compaction mid-fold and keep the host path."""
    return op.compact is None


def _folds_on_device(ops: Sequence["ScanOp"]) -> bool:
    """The rule for "fold the chunk partials on the device" wherever the
    scan is not bounded beforehand: every op is ``device_foldable``.
    Otherwise the host fold (``_PartialFolder``, one fetch per chunk) is
    the only fold that can compact mid-scan. (A RESIDENT table folds on
    the device whatever its ops: ``_run_scan_once``.)"""
    return all(device_foldable(op) for op in ops)


def _auto_chunk_rows_from_dtypes(
    dtypes: Sequence[DType],
    target_bytes: int = DEFAULT_CHUNK_BYTES,
    max_rows: int = MAX_CHUNK_ROWS,
) -> int:
    bytes_per_row = 0
    for dtype in dtypes:
        if dtype == DType.STRING:
            bytes_per_row += 4  # i32 codes
        elif dtype == DType.FRACTIONAL:
            bytes_per_row += 9  # f32 pair + mask
        else:
            bytes_per_row += 5  # i32 + mask
    bytes_per_row = max(bytes_per_row, 1)
    rows = target_bytes // bytes_per_row
    return int(min(max(rows, 1 << 18), max_rows))


def _auto_chunk_rows(
    cols: Dict[str, Column],
    target_bytes: int = DEFAULT_CHUNK_BYTES,
    max_rows: int = MAX_CHUNK_ROWS,
) -> int:
    return _auto_chunk_rows_from_dtypes(
        [c.dtype for c in cols.values()], target_bytes, max_rows
    )


@dataclass
class ScanOp:
    """One analyzer's contribution to the fused scan."""

    columns: Tuple[str, ...]
    update: Callable[[Dict[str, Val], Any, Any, int], Any]
    tags: Any  # pytree matching update's output; leaves: 'sum'|'min'|'max'
    # identity of the analyzer that built this op (hashable); lets the
    # engine reuse the traced+compiled fused program across repeated runs
    # over the same persisted table (retracing a 100-op program costs
    # seconds of host Python — the analogue of Spark reusing a compiled
    # whole-stage-codegen plan)
    cache_key: Any = None
    # dictionary-derived lookup tables this op needs, as (column, kind,
    # builder(dictionary)->np.ndarray): the engine builds them (memoized per
    # dictionary), pads to pow2, transfers ONCE, and passes them to the
    # jitted step as arguments — update reads vals[col].lut(kind). Programs
    # whose only dictionary dependence goes through luts stay reusable
    # across tables/batches.
    luts: Tuple[Tuple[str, str, Callable], ...] = ()
    # True when update reads v.dictionary directly at trace time (e.g. a
    # where-predicate comparing string literals) — such programs bake
    # table-specific constants and are excluded from cross-table caches
    dictionary_baked: bool = False
    # optional coalescing hint: ops sharing a batch_hint "kind" can be
    # merged by the planner into ONE vectorized op (e.g. N same-parameter
    # KLL sorts -> one vmapped batched sort). Shape: (kind, params, column).
    batch_hint: Optional[Tuple] = None
    # optional host-side compaction of the accumulated partial: called by
    # the folder whenever a 'gather' leaf exceeds compact_threshold rows,
    # returning an equivalent pytree of bounded size (e.g. KLL folds the
    # gathered weighted items into a sketch and re-emits its weighted
    # items). Keeps host memory O(1) in chunk count on TB-scale streams.
    compact: Optional[Callable[[Any], Any]] = None
    compact_threshold: int = 1 << 20
    # kernel-variant seam (ops/scan_plan.py): an alternative update fn
    # computing the SAME partial state via the batched histogram
    # selection kernel (ops/select_device.py) instead of a device sort.
    # The planner swaps it in per scan ATTEMPT when the table is
    # resident and select_columns all ride (hi, lo) key planes; the
    # fault ladder never sees the substitution.
    select_update: Optional[Callable[[Dict[str, Val], Any, Any, int], Any]] = None
    select_columns: Tuple[str, ...] = ()
    # histogram segment-counts the select path's bincount passes run
    # (ops/select_device.py: 2^16 + (k+2)*256+1) — the keyspace-width
    # input to the histogram kernel-variant policy
    # (ops/device_policy.resolve_hist_variant); () = no histogram passes
    hist_widths: Tuple[int, ...] = ()
    # True when `update` runs a full device sort per chunk (the KLL
    # summary kernels) — the census behind ScanStats.device_sort_passes
    sorts_chunk: bool = False
    # HLL register folds `update` runs per chunk (ApproxCountDistinct: 1)
    # — the census behind ScanStats.hll_folds
    hll_folds: int = 0
    # plane seam (ops/scan_plan.py): set by an analyzer whose partial is
    # made of the statistics of ONE column with no `where`. plane_stats
    # names what it needs beyond the two counts ("sum", "min", "max",
    # "m2"), plane_update maps that column's statistics (PlaneStats.of,
    # which adds "mean" to "m2") to the SAME partial
    # `update` returns. The planner swaps it in per scan ATTEMPT when the
    # column rides the (hi, lo) pair planes of the attempt's packer: the
    # statistics of all such columns come out of one batched reduction
    # along the rows of the planes, where `update` slices its column out
    # and reduces it alone.
    plane_column: Optional[str] = None
    plane_stats: Tuple[str, ...] = ()
    plane_update: Optional[Callable[[Dict[str, Any]], Any]] = None
    # the planner's mark on an op it routed so: the plan's PlaneRoute
    plane_route: Optional["PlaneRoute"] = None


class ScanStats:
    """Execution-report counters — the analogue of the reference's test-only
    SparkMonitor job accounting (SparkMonitor.scala:55-80), but first-class
    (SURVEY.md §5 calls for an execution-report hook): fused-pass counts,
    rows/bytes scanned, and wall time per pass. Tests assert fusion by
    counting device passes; users read it via deequ_tpu.execution_report()."""

    def __init__(self):
        # fetch accounting is written from caller threads AND watchdog
        # workers; the lock makes record_fetch's read-modify-write (and
        # snapshot()'s view of the pair) atomic — a lost update would
        # silently falsify the one-fetch contract asserts
        self._fetch_lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        self.scan_passes = 0
        self.chunks_processed = 0
        self.rows_scanned = 0
        self.bytes_packed = 0
        # bytes of host-packed staging planes served from a buffer the
        # staging pool had kept mapped (_StagingPool), of bytes_packed
        self.staging_bytes_reused = 0
        self.grouping_passes = 0
        self.kll_passes = 0
        # chunk (or shard) summaries the host folded into KLL sketches
        # (analyzers/sketches._kll_state_from_result, the sketch_fold
        # seam): columns x resident chunks on the selection path
        self.kll_summaries_folded = 0
        self.scan_seconds = 0.0
        self.resident_passes = 0
        self.bytes_resident = 0
        self.programs_built = 0
        self.programs_reused = 0
        self.device_sort_passes = 0
        # per-chunk KLL/quantile summary kernels that ran the histogram
        # SELECTION kernel instead of a sort (ops/select_device.py): on
        # the resident selection path device_sort_passes stays 0 and
        # this counts what replaced it — the config-3 contract pair
        self.device_select_passes = 0
        # histogram kernel-tier census (ops/histogram_device.py, round
        # 14): bincount/segment-fold dispatches per variant — the
        # selection kernel's three passes count under the plan's
        # resolved hist_variant, the grouping kernels
        # (ops/segment.py) under their per-dispatch resolution. The
        # obs registry's "kernels" section reads these through; the
        # kernel A/B probe (bench.measure_kernel_ab) asserts the
        # routed variant actually dispatched
        self.hist_scatter_dispatches = 0
        self.hist_onehot_dispatches = 0
        self.hist_pallas_dispatches = 0
        # of those, the bincount passes over a keyspace past the
        # platform's one-hot cap (device_policy.hist_is_wide): the
        # high-cardinality dictionaries of a string table
        self.hist_wide_dispatches = 0
        # HLL register folds: the ApproxCountDistinct ops of each
        # dispatched plan, times its chunks (counted on the host, where
        # _record_kernel_passes counts a dispatch)
        self.hll_folds = 0
        # columns whose HLL registers were folded out of the dictionary
        # entries PRESENT in the counts a Histogram of the same run
        # already had (segment.resident_top_k): K entries of work a
        # column, no per-row gather, no per-row fold, no scan
        self.hll_presence_folds = 0
        # dictionary LUTs BUILT (a memo miss of lut_cache.dictionary_lut:
        # O(dictionary) host work, hashing included); a warm suite
        # builds none
        self.lut_builds = 0
        # device->host result bytes (grouping paths): the sparse group-by
        # contract is fetched bytes ~ O(k*G), never O(k*n)
        self.bytes_fetched = 0
        # device->host MATERIALIZATIONS (every np.asarray of a device
        # array): the observable for the one-fetch-per-scan contract — a
        # multi-chunk device-folded scan must show exactly 1
        self.device_fetches = 0
        # time spent issuing host->device transfers and step dispatches
        # (host-side enqueue; near zero unless the runtime backpressures)
        # vs time blocked waiting for device results. drain_wait ~=
        # device compute + any in-flight transfer not hidden by the
        # pipeline window. Both are written by the seams below:
        # dispatch_seconds = stage + dispatch, drain_wait_seconds =
        # drain + fetch; scan_seconds is the scan_attempt seams' wall
        # and run_seconds the root seams' (a verification run's).
        self.dispatch_seconds = 0.0
        self.drain_wait_seconds = 0.0
        self.run_seconds = 0.0
        # exclusive seam seconds that began with the caller thread's
        # feed gauge at zero: the host had dispatched nothing the device
        # could still be computing (obs/recorder.py:device_fed)
        self.unfed_seconds = 0.0
        # every duration the engine takes (obs/recorder.py:seam): the
        # EXCLUSIVE seconds of each seam and how often it opened, as
        # flat numbers so a counter snapshot carries them
        for name in SEAM_NAMES:
            seconds, count = seam_fields(name)
            setattr(self, seconds, 0.0)
            setattr(self, count, 0)
        # out-of-core spill engine (deequ_tpu/spill): sorted runs written,
        # bytes moved to/from disk, merge cascade passes, and the largest
        # in-RAM grouping tail observed (the number the group memory
        # budget bounds)
        self.spill_runs = 0
        self.spill_bytes_written = 0
        self.spill_bytes_read = 0
        self.spill_merge_passes = 0
        self.peak_group_state_bytes = 0
        # device-fault tolerance (ops/device_policy.py + run_scan's
        # bisection/fallback driver): classified device faults seen,
        # OOM-driven chunk halvings, the deepest bisection any single scan
        # needed, watchdog conversions of hung calls, scans that completed
        # on the CPU fallback backend (and which backend that was), and a
        # structured log of every degradation decision
        self.device_faults = 0
        self.oom_bisections = 0
        self.bisection_depth = 0
        self.watchdog_timeouts = 0
        self.fallback_scans = 0
        self.fallback_backend = None
        self.degradation_events = []
        # mesh-fault tolerance (run_scan's degraded-mesh policy +
        # parallel/distributed.py's peer-loss path): device-attributable
        # faults seen on a multi-chip mesh, mesh rebuilds over a healthy
        # subset, straggler-deadline conversions, peers lost across hosts,
        # and the [start, stop) row ranges a degraded multi-host run
        # completed WITHOUT verifying (on_peer_loss="degrade")
        self.mesh_faults = 0
        self.mesh_reshards = 0
        self.mesh_stragglers = 0
        self.peer_losses = 0
        self.unverified_row_ranges = []
        # collective leaves dispatched on a mesh: each dispatch of a
        # sharded step adds the number of state leaves its program merges
        # across the mesh (one psum or all_gather each, _tag_collective);
        # counted on the host at the dispatch, 0 without a mesh
        self.mesh_collectives = 0
        # ops of dispatched plans that read their scalars out of the
        # batched plane statistics (ScanPlan.plane_ops per dispatch of a
        # step; the host-side census, as mesh_collectives)
        self.plane_ops = 0
        # static plan lint (deequ_tpu/lint/plan_lint.py, armed via
        # run_scan(plan_lint=...) / DEEQU_TPU_PLAN_LINT): finding rows
        # the jaxpr pass produced for this process's scans, and how many
        # actual lint TRACES ran — memoization means repeated scans of an
        # identical plan add zero traces (the bench memoization assert)
        self.plan_lints = []
        self.plan_lint_traces = 0
        # columnar ingest pipeline (round 8): host->device bytes moved
        # through the double-buffered staging step of the packing loops,
        # how many chunk transfers were staged, and how many of those
        # were issued while an earlier chunk was still in flight — the
        # structural observable behind ingest_overlap_frac (staging
        # overlapped compute instead of serializing after it)
        self.bytes_staged = 0
        self.chunks_staged = 0
        self.chunks_staged_overlapped = 0
        # scans whose plan routed >= 1 column over the encoded (int16
        # dictionary-code) plane, and fault-ladder demotions of an
        # encoded attempt back onto the decoded path (the OOM response,
        # mirroring the PR-6 selection->sort demotion)
        self.encoded_scan_passes = 0
        self.encoded_demotions = 0
        # run-level governance (resilience/governance.py): ladder/retry
        # attempts charged against an armed RunBudget (I/O retries, OOM
        # bisections, encoded demotions, mesh reshards, CPU fallbacks —
        # one ledger for the composed ladder) and how many runs
        # exhausted one. Healthy runs charge ZERO — the observable pair
        # behind bench.py's measure_governance_overhead <1% contract
        self.budget_charges = 0
        self.budget_exhaustions = 0
        # serving layer (deequ_tpu/serve, round 10): compiled-plan cache
        # traffic — a HIT means the suite ran with zero new traces, zero
        # compiles, and zero plan-lint traces (the hard repeat-tenant
        # contract measure_serving_load asserts); a MISS pays the
        # one-time build. Coalescing telemetry: packed multi-tenant
        # dispatches, real tenant suites they carried, and padding slots
        # burned to reach the tenant-axis bucket (occupancy =
        # coalesced_tenants / (coalesced_tenants + coalesce_padded_slots))
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        self.coalesced_batches = 0
        self.coalesced_tenants = 0
        self.coalesce_padded_slots = 0
        # whole-run plan optimizer (round 19): grouping passes that rode
        # a FUSED multi-pass dispatch (each fused group of K passes
        # counts K here while paying ONE record_hist_dispatch + ONE
        # fetch), and serving suites whose packed program came from the
        # cross-suite SUB-PLAN cache (a canonical-op-order hit below the
        # exact PlanKey). Read through the obs "planner" section.
        self.fused_group_passes = 0
        self.subplan_cache_hits = 0
        # windowed verification (deequ_tpu/windows, round 20): rows that
        # arrived behind their stream's watermark and were routed by the
        # typed late policy ('drop' counts here; 'side_output'
        # additionally quarantines the batch range via
        # record_unverified; 'refuse' raises LateDataException instead)
        self.late_rows = 0

    @property
    def ingest_overlap_frac(self) -> float:
        """Fraction of staged chunk transfers issued while the previous
        chunk was still STAGED (transferred but not yet dispatched) —
        the defining property of the double-buffered ordering. A healthy
        n-chunk scan shows (n-1)/n; a serial put-then-dispatch loop (the
        regression this observable guards) shows 0.0, as does a
        single-chunk scan."""
        if not self.chunks_staged:
            return 0.0
        return self.chunks_staged_overlapped / self.chunks_staged

    def snapshot(self) -> dict:
        # the synchronized read of the fetch ledger (tests assert the
        # one-fetch contract through here); private fields (the lock)
        # never enter reports
        with self._fetch_lock:
            snap = {
                k: v for k, v in self.__dict__.items()
                if not k.startswith("_")
            }
        # events are mutable rows — hand out a copy so a caller's report
        # is a point-in-time record, not a live view
        snap["degradation_events"] = [dict(e) for e in self.degradation_events]
        snap["unverified_row_ranges"] = [
            tuple(r) for r in self.unverified_row_ranges
        ]
        snap["plan_lints"] = [dict(f) for f in self.plan_lints]
        snap["ingest_overlap_frac"] = round(self.ingest_overlap_frac, 4)
        return snap

    def record_unverified(
        self, start: int, stop: int, reason: str, kind: str = "peer_lost"
    ) -> dict:
        """Mark one [start, stop) row range as UNVERIFIED (a degraded
        multi-host run completed without the lost hosts' shards; a
        budget-exhausted run completed without its remaining rows —
        ``kind="budget_exhausted"``). The omission is reported, never
        silent — mirrored onto
        ``VerificationResult.unverified_row_ranges``."""
        self.unverified_row_ranges.append((int(start), int(stop)))
        return self.record_degradation(
            kind, start=int(start), stop=int(stop), reason=reason
        )

    def record_fetch(self, nbytes: int) -> None:
        """Account one device->host materialization (the unit the
        one-fetch-per-scan contract counts) and its result bytes.

        Fetches performed by an ABANDONED watchdog call are dropped: the
        call's scan already failed typed (DeviceHangException) and the
        ladder moved on — when the hung device call finally wakes,
        possibly a whole test later, its counter bump would land on
        whatever run is active then (the cross-test device_fetches race
        behind the historical oom_mid_fold tier-1 flake)."""
        if current_watchdog_call_abandoned():
            return
        with self._fetch_lock:
            self.device_fetches += 1
            self.bytes_fetched += int(nbytes)

    def record_late_rows(self, n: int) -> None:
        """Account ``n`` stream rows that fell behind their watermark
        (deequ_tpu/windows late routing). Written from stream-hub worker
        threads, so the read-modify-write shares the fetch lock."""
        with self._fetch_lock:
            self.late_rows += int(n)

    def record_hist_dispatch(
        self, variant: str, n: int = 1, wide: bool = False
    ) -> None:
        """Account ``n`` histogram/segment-fold kernel dispatches under
        their resolved variant (ops/histogram_device.py tier); ``wide``
        where their keyspace is past the one-hot cap
        (device_policy.hist_is_wide). Written from serve/fleet worker
        threads like the fetch ledger, so the read-modify-write shares
        its lock."""
        field_name = f"hist_{variant}_dispatches"
        with self._fetch_lock:
            setattr(self, field_name, getattr(self, field_name) + int(n))
            if wide:
                self.hist_wide_dispatches += int(n)

    def record_fused_group_pass(self, n: int = 1) -> None:
        """Account ``n`` grouping passes that executed inside one fused
        multi-pass dispatch (the plan optimizer's cross-pass fusion).
        Lock-serialized like the hist census — the serve/fleet workers
        share the singleton."""
        with self._fetch_lock:
            self.fused_group_passes += int(n)

    def record_subplan_hit(self, n: int = 1) -> None:
        """Account ``n`` tenant suites served from the cross-suite
        sub-plan cache (a shared traced program below the exact
        PlanKey). Lock-serialized like the fetch ledger."""
        with self._fetch_lock:
            self.subplan_cache_hits += int(n)

    def record_staged(
        self, nbytes: int, overlapped: bool, count_chunk: bool = True
    ) -> None:
        """Account one HOST->DEVICE chunk staging (the double-buffered
        transfers of the packing loops). Staging is the opposite
        direction from a fetch — it never counts against the one-fetch
        contract; ``overlapped`` marks transfers issued while the
        previous chunk was still staged-undispatched (see
        ``ingest_overlap_frac``). A put that is no chunk's (the device
        fold's fresh accumulator) adds its bytes only
        (``count_chunk=False``): the overlap ratio is one of CHUNK
        transfers."""
        self.bytes_staged += int(nbytes)
        if count_chunk:
            self.chunks_staged += 1
            if overlapped:
                self.chunks_staged_overlapped += 1

    def record_degradation(self, kind: str, **detail) -> dict:
        """Append one degradation decision (kind: 'oom_bisect' |
        'cpu_fallback' | 'watchdog_timeout' | 'device_fault') for
        execution reports and VerificationResult.device_events.

        This is also the flight recorder's fault-ladder seam: EVERY
        rung of every ladder (oom_bisect, encoded_demote, mesh_reshard,
        cpu_fallback, coalesce_bisect, tenant_quarantine, ...) reports
        here, so one instant-event emission covers them all — inside
        the attempt span when the rung fires within one, parentless
        otherwise."""
        event = {"kind": kind, **detail}
        self.degradation_events.append(event)
        rec = current_recorder()
        if rec is not None:
            rec.event(kind, **{
                k: v for k, v in detail.items()
                if isinstance(v, (int, float, str, bool, type(None)))
            })
        return event

    def effective_bytes_per_sec(self) -> float:
        """Scanned bytes per wall second across all passes (compare to the
        chip's HBM bandwidth for a utilization denominator)."""
        total = self.bytes_packed + self.bytes_resident
        return total / self.scan_seconds if self.scan_seconds > 0 else 0.0


SCAN_STATS = ScanStats()
bind_seam_counters(SCAN_STATS)


def _tag_reduce_np(tag: str, a, b):
    if tag == "sum":
        return a + b
    if tag == "min":
        return np.minimum(a, b)
    if tag == "max":
        return np.maximum(a, b)
    if tag == "gather":
        # non-reducible partials (e.g. Welford moments): stack across chunks,
        # the analyzer folds them with its own exact merge rule on the host
        return np.concatenate([np.atleast_1d(a), np.atleast_1d(b)], axis=0)
    raise ValueError(f"unknown reduce tag {tag}")


def _tag_collective(tag: str, leaf, axis_name: str):
    """One state leaf merged across the mesh, under the scope
    ``deequ.collective.<tag>`` (what a device trace calls its time)."""
    if tag not in ("sum", "min", "max", "gather"):
        raise ValueError(f"unknown reduce tag {tag}")
    with jax.named_scope(f"deequ.collective.{tag}"):
        if tag == "sum":
            return jax.lax.psum(leaf, axis_name)
        if tag == "gather":
            return jax.lax.all_gather(
                jnp.atleast_1d(leaf), axis_name
            ).reshape((-1,) + jnp.shape(jnp.atleast_1d(leaf))[1:])
        # min / max: all_gather + local reduce, not pmin/pmax: XLA:TPU
        # lowers a 64-bit all-reduce for Sum only ("Supported lowering only
        # of Sum all reduce"), and min/max leaves are f64. Both reductions
        # are exactly associative, so the result is bit-identical; the
        # leaves are a few scalars (or one 512-register HLL file) per op.
        gathered = jax.lax.all_gather(leaf, axis_name)
        return gathered.min(axis=0) if tag == "min" else gathered.max(axis=0)


def _state_tags(ops) -> List[str]:
    """The reduce tag of every state leaf of every op: on a mesh each is
    one collective a dispatch of the sharded step (``_tag_collective``)."""
    return [tag for op in ops for tag in jax.tree.leaves(op.tags)]


def _tag_identity_wrap(tag: str, leaf):
    """Single-device normalization: give 'gather' leaves a leading axis so
    the host fold concatenates uniformly."""
    if tag == "gather":
        return jnp.atleast_1d(leaf)
    return leaf


def _packs_as_i32(col: Column) -> bool:
    """Integral columns whose values fit int32 transfer at half width,
    losslessly (the exact (hi, lo) f32 split happens inside the jitted
    step, ops/df32.py:int32_pair). Boolean columns always qualify. The
    O(n) min/max is computed once per Column and cached (repeated packer
    construction over streaming batches / persisted tables reuses it)."""
    if col.dtype == DType.BOOLEAN:
        return True
    if col.dtype != DType.INTEGRAL or len(col.values) == 0:
        return False
    cached = getattr(col, "_i32_safe", None)
    if cached is None:
        lo = int(col.values.min())
        hi = int(col.values.max())
        cached = -(2 ** 31) < lo and hi < 2 ** 31
        col._i32_safe = cached
    return cached


def _packs_as_pair(col: Column) -> bool:
    """Fractional columns whose finite values fit the (hi, lo) f32 pair
    representation (|x| <= f32_max) — the native-dtype compute path. The
    range check is cached per Column like _packs_as_i32. Columns marked by
    a comparison predicate (expr/eval._mark_exact_compare_columns) route
    wide: predicate boundaries need the exact f64 value."""
    from deequ_tpu.ops.df32 import pair_safe_np

    if col.dtype != DType.FRACTIONAL:
        return False
    if getattr(col, "_exact_compare", False):
        return False
    cached = getattr(col, "_pair_safe", None)
    if cached is None:
        cached = pair_safe_np(col.values)
        col._pair_safe = cached
    return cached


def _enc_eligible(col: Column) -> bool:
    """True when the column can ride the encoded (int16 dictionary-code)
    plane: it carries a ColumnChunk encoding whose dictionary fits the
    device decode path — pair-safe f64 values (fractional) or i32-safe
    values (integral; the exact pair split runs on the gathered
    dictionary entries). Predicate-boundary columns
    (``_exact_compare``) route wide exactly as on the decoded path. The
    O(cardinality) dictionary check is cached per Column like
    ``_packs_as_i32``."""
    enc = getattr(col, "encoding", None)
    if enc is None or col.dtype not in (DType.FRACTIONAL, DType.INTEGRAL):
        return False
    if getattr(col, "_exact_compare", False):
        return False
    cached = getattr(col, "_enc_safe", None)
    if cached is None:
        from deequ_tpu.ops.df32 import pair_safe_np

        d = enc.dictionary
        if col.dtype == DType.INTEGRAL:
            cached = bool(
                len(d) == 0
                or (-(2 ** 31) < int(d.min()) and int(d.max()) < 2 ** 31)
            )
        else:
            # deequ-lint: ignore[host-fetch] -- d is the ColumnChunk's host numpy dictionary, never a device array
            cached = pair_safe_np(np.asarray(d, dtype=np.float64))
        col._enc_safe = cached
    return cached


_PAIR_COMPARE_WARNED: set = set()


def _warn_pair_compare_once(name: str, col=None) -> None:
    """A persisted/stream-pinned layout already routed this column over the
    ~49-bit f32 pair, but a predicate now compares it at a boundary; the
    layout can't change mid-flight, so comparisons may be ~1e-16 (relative)
    off exact f64. Re-persisting the table after the check is declared
    routes the column over the wide plane (exact predicate semantics).
    Deduped per Column OBJECT — a different table reusing the same column
    name still gets its own warning."""
    key = (id(col), name)
    if key in _PAIR_COMPARE_WARNED:
        return
    _PAIR_COMPARE_WARNED.add(key)
    import warnings

    warnings.warn(
        f"column {name!r} is compared at a predicate boundary but was "
        "persisted/pinned on the two-float f32 plane (~49 mantissa bits); "
        "exact-equality predicates may miss values within ~1e-16 relative. "
        "Re-persist the table (or restart the stream) after declaring the "
        "check: the marked column then takes the wide f64 plane.",
        stacklevel=3,
    )


@dataclass(frozen=True)
class PlaneRoute:
    """The columns one plan routed onto the batched plane statistics, in
    the order of their rows on the pair planes, each with the statistics
    its ops need (``ScanOp.plane_stats``). One per plan (ops/scan_plan.py), shared by its routed
    updates: the key under which a trace computes the statistics once."""

    columns: Tuple[Tuple[str, Tuple[str, ...]], ...]


class ColumnVals(dict):
    """What ``unpack_vals`` hands out: the per-column Vals by name, and
    beside them ``plane``, the statistics of the packed pair planes."""

    plane: "PlaneStats" = None


class PlaneStats:
    """Statistics of pair-plane columns computed where the planes lie: one
    batched reduction along the rows of ``hi[a:b]`` / ``lo[a:b]`` for all
    the columns of a run, never a column sliced out on its own. Lives for
    one trace of a step; nothing is computed until a routed op asks.

    Two sweeps, as the mathematics orders them. Sweep 1: counts,
    compensated sums, hi extrema. Sweep 2, which needs sweep 1's mean and
    extrema: centred squares, the lo extremum among the hi ties. The
    arithmetic is ops/df32.py's, the same a per-column ``update`` runs."""

    def __init__(self, packer, hi, lo, masks, row_valid, xp):
        self._packer = packer
        self._hi, self._lo, self._masks = hi, lo, masks
        self._row_valid = row_valid
        self._xp = xp
        self._memo: Dict[PlaneRoute, Dict[str, Dict[str, Any]]] = {}

    def of(self, route: PlaneRoute) -> Dict[str, Dict[str, Any]]:
        """``{column: {"count", "rows", "sum", "mean", "m2", "min",
        "max"}}`` (what the route asked for) as scalars of this trace."""
        stats = self._memo.get(route)
        if stats is None:
            stats = self._memo[route] = self._compute(route)
        return stats

    def _runs(self, route: PlaneRoute):
        """Cut the routed columns into runs that are ONE static slice of
        each plane: consecutive hi/lo rows whose mask rows are consecutive
        too, or absent throughout (null-free columns ship none). A layout
        that alternates degrades to runs of one row, never to a gather."""
        pair_row, mask_row = self._packer._pair_row, self._packer._mask_row
        runs: List[Tuple[int, Optional[int], List[str], set]] = []
        for name, needs in route.columns:
            row, mrow = pair_row[name], mask_row.get(name)
            if runs:
                start, mstart, names, wanted = runs[-1]
                k = len(names)
                if row == start + k and mrow == (
                    None if mstart is None else mstart + k
                ):
                    names.append(name)
                    wanted.update(needs)
                    continue
            runs.append((row, mrow, [name], set(needs)))
        return runs

    def _compute(self, route: PlaneRoute) -> Dict[str, Dict[str, Any]]:
        from deequ_tpu.ops import df32

        xp = self._xp
        row_valid = self._row_valid
        with jax.named_scope("deequ.plane.sweep1"):
            rows = df32.masked_count(row_valid, xp)
        stats: Dict[str, Dict[str, Any]] = {}
        for start, mstart, names, wanted in self._runs(route):
            stop = start + len(names)
            hi, lo = self._hi[start:stop], self._lo[start:stop]
            if mstart is None:
                ok = xp.broadcast_to(row_valid, hi.shape)
            else:
                ok = self._masks[mstart:mstart + len(names)] & row_valid
            run: Dict[str, Any] = {}
            with jax.named_scope("deequ.plane.sweep1"):
                run["count"] = df32.masked_count(ok, xp)
                if wanted & {"sum", "m2"}:
                    run["sum"] = df32.masked_sum(hi, lo, ok, xp)
                tops = {
                    mode: df32.extremum_hi(hi, ok, xp, mode)
                    for mode in ("min", "max") if mode in wanted
                }
            with jax.named_scope("deequ.plane.sweep2"):
                if "m2" in wanted:
                    run["mean"] = run["sum"] / xp.maximum(run["count"], 1)
                    run["m2"] = df32.centered_m2(hi, lo, run["mean"], ok, xp)
                for mode, top in tops.items():
                    run[mode] = df32.extremum_tie(hi, lo, ok, top, xp, mode)
            for i, name in enumerate(names):
                stats[name] = {k: v[i] for k, v in run.items()}
                stats[name]["rows"] = rows
        return stats


class _ChunkPacker:
    """Packs one chunk of a table into a handful of contiguous host buffers
    (two-float f32 pair planes, wide f64 values, narrow i32 values,
    validity masks, string codes).

    Every host->device transfer pays a fixed per-call cost on top of its
    bytes, so the packer both batches transfers (one buffer per dtype class
    instead of 2 x N columns) and minimizes bytes. Column routing (the
    native-dtype compute path, ops/df32.py):

    - fractional -> (hi, lo) f32 pair planes: same 8 bytes/row as f64,
      ~48-bit lossless, every O(n) device op runs on native f32 units;
    - int32-safe integral + boolean -> i32 plane (exact pair split happens
      on device);
    - huge integers, |x| > f32_max fractionals and predicate-compared
      columns (``_exact_compare``) -> wide f64 plane (XLA software-f64
      fallback): the data selects it, nothing else does;
    - null-free columns ship no mask row (validity is just row_valid);
    - dictionary-ENCODED numeric columns (``encode_ingest=True``, round
      8) -> int16 ``enc`` code plane, 2 bytes/row, null = -1 (no mask
      row either — validity rides in the codes); the tiny dictionary
      ships once as (hi, lo) / i32 LUT arguments and decode is a gather
      fused into the scan program (docs/ingest.md).
    """

    def __init__(
        self,
        cols: Dict[str, Column],
        chunk: int,
        layout: Optional[dict] = None,
        encode_ingest: bool = False,
    ):
        numeric = [n for n, c in cols.items() if c.dtype != DType.STRING]
        self.string_names = [n for n, c in cols.items() if c.dtype == DType.STRING]
        if layout is not None:
            # streaming: a pinned buffer layout shared by every batch of the
            # stream so the traced program is reusable (the caller validates
            # each batch against it, see _layout_upgrades)
            self.narrow_i32 = list(layout["narrow_i32"])
            self.pair_names = list(layout["pair"])
            self.wide_names = list(layout["wide"])
            self.masked_names = list(layout["masked"])
            self.enc_names = list(layout.get("enc", ()))
            for n in self.pair_names:
                if getattr(cols.get(n), "_exact_compare", False):
                    _warn_pair_compare_once(n, cols.get(n))
        else:
            # encoded routing first: enc columns leave the decoded-plane
            # classification entirely (and their classification must not
            # touch .values — that would force the decode the plane
            # exists to avoid)
            self.enc_names = (
                [n for n in numeric if _enc_eligible(cols[n])]
                if encode_ingest
                else []
            )
            enc_set = set(self.enc_names)
            decoded = [n for n in numeric if n not in enc_set]
            self.narrow_i32 = [n for n in decoded if _packs_as_i32(cols[n])]
            self.pair_names = [n for n in decoded if _packs_as_pair(cols[n])]
            routed = set(self.narrow_i32) | set(self.pair_names)
            self.wide_names = [n for n in decoded if n not in routed]
            # null-free columns don't ship a mask row at all — their
            # validity is just row_valid (saves 1 byte/row/column);
            # encoded columns carry validity in their -1 codes
            self.masked_names = [
                n for n in decoded if not bool(cols[n].mask.all())
            ]
        self.numeric_names = numeric
        # a pair column's row on the hi plane and on the lo plane
        self._pair_row = {n: i for i, n in enumerate(self.pair_names)}
        self._mask_row = {n: i for i, n in enumerate(self.masked_names)}
        self._enc_row = {n: i for i, n in enumerate(self.enc_names)}
        self.cols = cols
        self.chunk = chunk
        # metadata-only view for trace closures: dtypes + string/encoded
        # dictionaries, NOT the column arrays — a traced program held in a
        # long-lived cache must not pin entire batches in host memory
        # (encoded dictionaries are <= 2^15 entries by construction)
        self.col_dtype = {n: c.dtype for n, c in cols.items()}
        self.col_dict = {
            n: cols[n].dictionary for n in self.string_names
        }
        self.enc_dict = {
            n: cols[n].encoding.dictionary for n in self.enc_names
        }

    def pack(self, start: int, stop: int, take=np.empty):
        """Rows ``[start, stop)`` as one chunk's planes. ``take(shape,
        dtype)`` supplies each plane's memory and says who owns it
        afterwards: ``np.empty`` (fresh planes, the caller's for good) or a
        ``_StagingLease.take`` (planes that go back to the staging pool).
        What ``take`` hands out may hold another chunk's bytes, so EVERY
        byte of every plane is written here, the tail past ``stop`` too."""
        from deequ_tpu.ops.df32 import split_pair_np

        chunk = self.chunk
        n = stop - start

        def buf(names, dtype, fill):
            # empty categories are genuinely 0-row: the old 1-row dummy
            # shipped chunk-width buffers of padding over the (slow) link
            # on every chunk — for a numeric-only table that was ~1/3 of
            # all transferred bytes
            out = take((len(names), chunk), dtype)
            if n < chunk and names:
                out[:, n:] = fill
            return out

        values = buf(self.wide_names, np.float64, 0.0)
        hi = buf(self.pair_names, np.float32, 0.0)
        lo = buf(self.pair_names, np.float32, 0.0)
        narrow_i = buf(self.narrow_i32, np.int32, 0)
        masks = buf(self.masked_names, np.bool_, False)
        codes = buf(self.string_names, np.int32, -1)
        # encoded plane: int16 dictionary codes; padding joins the null
        # rows at -1, so device masks (code >= 0) need no row_valid AND
        enc = buf(self.enc_names, np.int16, -1)

        for i, name in enumerate(self.wide_names):
            values[i, :n] = self.cols[name].values[start:stop]
        for i, name in enumerate(self.pair_names):
            split_pair_np(
                self.cols[name].values[start:stop], hi[i, :n], lo[i, :n]
            )
        for i, name in enumerate(self.narrow_i32):
            narrow_i[i, :n] = self.cols[name].values[start:stop]
        for name, i in self._mask_row.items():
            masks[i, :n] = self.cols[name].mask[start:stop]
        for j, name in enumerate(self.string_names):
            codes[j, :n] = self.cols[name].codes[start:stop]
        for i, name in enumerate(self.enc_names):
            enc[i, :n] = self.cols[name].encoding.codes[start:stop]
        row_valid = take((chunk,), np.bool_)
        row_valid[:n] = True
        row_valid[n:] = False
        return values, hi, lo, narrow_i, masks, codes, row_valid, enc

    def unpack_vals(
        self, values, hi, lo, narrow_i, masks, codes, xp, row_valid=None,
        col_luts=None, enc=None,
    ) -> ColumnVals:
        """Slice the packed buffers back into per-column Vals (inside jit),
        with the planes' own statistics beside them (``.plane``: what the
        plan routed there is reduced in place, PlaneStats).

        Numeric Vals carry the two-float pair: ``data`` = f32 hi plane,
        ``lo`` = f32 lo plane (None for wide-f64 columns). Reductions go
        through ops/df32.py; the expression evaluator reconstructs f64
        lazily (expr/eval.py:EvalContext.get).

        Encoded columns decode INSIDE the program: the int16 code plane
        gathers the dictionary's precomputed (hi, lo) planes (fractional;
        the split of a value is elementwise-deterministic, so the
        gathered pair is bit-identical to splitting the decoded column)
        or its i32 entries through the same on-device ``int32_pair`` the
        narrow plane uses (integral). Validity is ``code >= 0``."""
        from deequ_tpu.ops.df32 import int32_pair

        vals = ColumnVals()
        vals.plane = PlaneStats(self, hi, lo, masks, row_valid, xp)
        for name in self.enc_names:
            code = enc[self._enc_row[name]].astype(xp.int32)
            mask = code >= 0
            safe = xp.where(mask, code, 0)
            luts = (col_luts or {}).get(name, {})
            if self.col_dtype[name] == DType.INTEGRAL:
                gathered = xp.take(luts["_enc_i32"], safe)
                h, l = int32_pair(xp.where(mask, gathered, 0), xp)
            else:
                h = xp.where(mask, xp.take(luts["_enc_hi"], safe), 0.0)
                l = xp.where(mask, xp.take(luts["_enc_lo"], safe), 0.0)
            vals[name] = Val("num", h, mask, lo=l)
        narrow_set = set(self.narrow_i32)
        enc_set = set(self.enc_names)
        wide_row = {n: i for i, n in enumerate(self.wide_names)}
        narrow_row = {n: i for i, n in enumerate(self.narrow_i32)}
        for name in self.numeric_names:
            if name in enc_set:
                continue  # decoded above, straight off the code plane
            if name in self._mask_row:
                mask = masks[self._mask_row[name]]
            elif row_valid is not None:
                mask = row_valid
            else:
                mask = None  # shaped below once data is known
            dtype = self.col_dtype[name]
            if name in narrow_set:
                data_i = narrow_i[narrow_row[name]]
                if mask is None:
                    mask = xp.ones(data_i.shape, dtype=bool)
                if dtype == DType.BOOLEAN:
                    vals[name] = Val("bool", data_i != 0, mask)
                else:
                    h, l = int32_pair(data_i, xp)
                    vals[name] = Val("num", h, mask, lo=l)
            elif name in self._pair_row:
                h = hi[self._pair_row[name]]
                l = lo[self._pair_row[name]]
                if mask is None:
                    mask = xp.ones(h.shape, dtype=bool)
                vals[name] = Val("num", h, mask, lo=l)
            else:
                data = values[wide_row[name]]
                if mask is None:
                    mask = xp.ones(data.shape, dtype=bool)
                if dtype == DType.BOOLEAN:
                    vals[name] = Val("bool", data != 0.0, mask)
                else:
                    vals[name] = Val("num", data, mask)
        for j, name in enumerate(self.string_names):
            vals[name] = Val(
                "str", codes[j], None, dictionary=self.col_dict[name],
                luts=(col_luts or {}).get(name),
            )
        return vals

    def layout(self) -> dict:
        return {
            "narrow_i32": tuple(self.narrow_i32),
            "pair": tuple(self.pair_names),
            "wide": tuple(self.wide_names),
            "masked": tuple(self.masked_names),
            "enc": tuple(self.enc_names),
        }

    def unpack_view(self) -> "_ChunkPacker":
        """A copy safe to capture in long-lived trace closures: same unpack
        metadata, no references to the source column arrays."""
        view = _ChunkPacker.__new__(_ChunkPacker)
        view.string_names = self.string_names
        view.narrow_i32 = self.narrow_i32
        view.pair_names = self.pair_names
        view.wide_names = self.wide_names
        view.numeric_names = self.numeric_names
        view.masked_names = self.masked_names
        view.enc_names = self.enc_names
        view._pair_row = self._pair_row
        view._mask_row = self._mask_row
        view._enc_row = self._enc_row
        view.cols = None  # pack() is not available on a view
        view.chunk = self.chunk
        view.col_dtype = self.col_dtype
        view.col_dict = self.col_dict
        view.enc_dict = self.enc_dict
        return view


def _staging_capacity(nbytes: int) -> int:
    """The bytes mapped for a plane of ``nbytes``: rounded up to an eighth
    of its power of two (at most 12.5% more, in pages never touched), so
    that a partition some rows longer than the last one still fits the
    buffer the last one left."""
    granule = 1 << max(nbytes.bit_length() - 4, 0)
    return -(-nbytes // granule) * granule


class _StagingPool:
    """Byte buffers that stay mapped between host-packed chunks.

    A chunk's planes are far above glibc's mmap threshold, so fresh ones
    are mapped, faulted in page by page and unmapped again for every chunk:
    that first touch, not the split, was four fifths of ``pack``
    (docs/ingest.md, "Staging planes: lease and release"). Free buffers are
    kept oldest first; a request takes the smallest one that holds it and is
    at most twice its size, else maps a new one. What comes back over
    ``max_bytes`` pushes the oldest out, to be freed as any array is."""

    def __init__(self, max_bytes: int, min_plane_bytes: int):
        self.max_bytes = max_bytes
        self.min_plane_bytes = min_plane_bytes
        self._lock = threading.Lock()
        self._free: List[np.ndarray] = []
        self._free_bytes = 0

    def free_bytes(self) -> int:
        return self._free_bytes

    def take(self, nbytes: int) -> Tuple[np.ndarray, bool]:
        """A uint8 buffer of at least ``nbytes``, and whether it was
        mapped before."""
        with self._lock:
            fits = [
                i for i, b in enumerate(self._free)
                if nbytes <= b.nbytes <= 2 * nbytes
            ]
            if fits:
                buf = self._free.pop(
                    min(fits, key=lambda i: self._free[i].nbytes)
                )
                self._free_bytes -= buf.nbytes
                return buf, True
        return np.empty(_staging_capacity(nbytes), dtype=np.uint8), False

    def give(self, bufs: Sequence[np.ndarray]) -> None:
        dropped = []  # unmapped after the lock is released
        with self._lock:
            self._free.extend(bufs)
            self._free_bytes += sum(b.nbytes for b in bufs)
            while self._free_bytes > self.max_bytes:
                dropped.append(self._free.pop(0))
                self._free_bytes -= dropped[-1].nbytes

    def clear(self) -> None:
        with self._lock:
            self._free, self._free_bytes = [], 0


# What the pool may retain: the planes one host-packed scan can hold at once
# (DEFAULT_SCAN_WINDOW - 1 chunks in flight, one staged, one being packed),
# which all come back when it ends.
STAGING_POOL_MAX_BYTES = (DEFAULT_SCAN_WINDOW + 1) * DEFAULT_CHUNK_BYTES
# A plane under the size cut is np.empty's. Read on the v5e's host (PR 29,
# benchmarks/staging_probe.py: three planes at once, allocate + fill + free
# against lock + fill of buffers kept mapped, a young heap): up to 128 KiB
# the two are equal (7.5 against 8.5 us: malloc recycles what is under its
# mmap threshold, and the lock is a tenth on top); from 256 KiB fresh
# planes are mapped and faulted in every time, 817 against 20.6 us (x40),
# x16-59 from there to 64 MiB. After large frees glibc's dynamic threshold
# recycles some sizes up to ~28 MiB, never one over 32 MiB. At the cell's
# 100 MB planes: split into fresh planes 246.8 ms, into mapped ones 45.2 ms.
STAGING_POOL_MIN_PLANE_BYTES = 1 << 18
_STAGING_POOL = _StagingPool(
    STAGING_POOL_MAX_BYTES, STAGING_POOL_MIN_PLANE_BYTES
)


class _StagingLease:
    """The pooled buffers under ONE chunk's planes, from ``pack`` until
    that chunk's device result is known ready. ``release`` is called on
    the success path only (after ``_block_throttle`` returned for the
    chunk's result, or a drain fetched it or the accumulator it was
    merged into): a ready result means the step ran, so the transfer that
    read the planes is complete. Not after ``put``: the TPU runtime reads
    the numpy buffer until the asynchronous transfer completes, and the
    CPU backend may alias it without a copy. On any other path (an
    exception, a timeout, an abandoned worker, a scan never resolved) the
    lease is simply dropped and the garbage collector frees the planes
    once nothing reads them, as it frees fresh ones."""

    __slots__ = ("_bufs",)

    def __init__(self):
        self._bufs: List[np.ndarray] = []

    def take(self, shape, dtype) -> np.ndarray:
        dtype = np.dtype(dtype)
        nbytes = math.prod(shape) * dtype.itemsize
        if nbytes < _STAGING_POOL.min_plane_bytes:
            return np.empty(shape, dtype=dtype)
        buf, reused = _STAGING_POOL.take(nbytes)
        if reused:
            SCAN_STATS.staging_bytes_reused += nbytes
        self._bufs.append(buf)
        return buf[:nbytes].view(dtype).reshape(shape)

    def release(self) -> None:
        bufs, self._bufs = self._bufs, []
        # a watchdog worker abandoned at its deadline belongs to a run that
        # already failed: what it holds is dropped like that run's leases
        if bufs and not current_watchdog_call_abandoned():
            _STAGING_POOL.give(bufs)


# what a resident chunk holds: nothing was packed for it
_NO_LEASE = _StagingLease()


class _BoundedLRU:
    """Tiny bounded LRU over a dict (insertion order = recency)."""

    def __init__(self, cap: int):
        self.cap = cap
        self._d: Dict[Any, Any] = {}

    def get(self, key):
        val = self._d.pop(key, None)
        if val is not None:
            self._d[key] = val  # re-insert: most-recently-used
        return val

    def put(self, key, val) -> None:
        self._d[key] = val
        while len(self._d) > self.cap:
            self._d.pop(next(iter(self._d)))

    def __len__(self) -> int:
        return len(self._d)

    def clear(self) -> None:
        self._d.clear()


class DeviceTableCache:
    """Packed table chunks resident in HBM — the analogue of Spark's
    ``df.persist()`` (StorageLevel.MEMORY) that the reference leans on for
    its multi-pass profiler (AnalysisRunner.scala:493-497).

    Host packing plus the host->device transfer of a whole table costs
    seconds per GB (10M x 23 columns, 1.93 GB: 16 s on the v5e host,
    chip_smoke.py, PR 21), so any multi-pass workload (the 3-pass
    ColumnProfiler, repeated verification runs, incremental re-checks) is
    ingest-bound unless the table ships ONCE. persist() packs every column
    with the same _ChunkPacker layout the scan uses and device_puts the
    buffers with the mesh shardings; subsequent run_scan calls stream
    straight from HBM.
    """

    # what ONE device may hold resident: leaves headroom in a v5e chip's
    # 16 GB of HBM. Row-sharded tables are held to it by their per-device
    # share (per_device_bytes), never by their total
    MAX_RESIDENT_BYTES = 12 << 30
    MAX_CACHED_PROGRAMS = 32  # LRU cap on traced programs per table

    def __init__(self, packer, chunk, device_chunks, mesh, nbytes, device_count):
        self.packer = packer
        self.chunk = chunk
        self.device_chunks = device_chunks  # list of 8-tuples of device arrays (values, hi, lo, narrow_i, masks, codes, row_valid, enc)
        self.mesh = mesh
        self.nbytes = nbytes
        self.device_count = device_count
        # (op cache_keys, chunk) -> (step_fn, shapes): reused traced
        # programs, LRU-bounded so long-lived services with varied analyzer
        # sets don't accumulate executables without limit
        self.programs = _BoundedLRU(self.MAX_CACHED_PROGRAMS)
        _ACTIVE_CACHES.add(self)

    @property
    def per_device_bytes(self) -> int:
        """What each device of the mesh holds of it: every buffer is
        row-sharded over a chunk that the mesh divides, so the shares are
        equal."""
        return self.nbytes // self.device_count

    def get_program(self, key):
        return self.programs.get(key)

    def put_program(self, key, prog) -> None:
        self.programs.put(key, prog)

    def mesh_matches(self, mesh) -> bool:
        return (mesh is None and self.mesh is None) or (
            mesh is not None
            and self.mesh is not None
            and mesh.devices.shape == self.mesh.devices.shape
            and tuple(mesh.devices.flat) == tuple(self.mesh.devices.flat)
        )

    def matches(self, mesh, needed_cols) -> bool:
        return self.mesh_matches(mesh) and (
            set(needed_cols) <= set(self.packer.cols)
        )


# Live caches (weakly held): persist() checks the COMBINED resident
# footprint — e.g. the profiler holding both the raw and the numeric-cast
# table — against a device's HBM budget, not just the newest table's share.
_ACTIVE_CACHES: "weakref.WeakSet[DeviceTableCache]" = weakref.WeakSet()

# Global traced-program cache for STREAMING runs over tables with identical
# (analyzer set, packer layout, chunk, mesh) — the incremental-monitoring
# hot path: the same suite runs on every arriving batch, and retracing a
# wide fused program per batch costs more than scanning the batch. Only
# table-INDEPENDENT programs are cacheable: ops over string columns bake
# per-table dictionary lookup tables into the trace as constants
# (PatternMatch regex LUT, length LUT, DataType classify LUT, string-code
# resolution in predicates), so any string column disables the cache.
# Entries hold only the jitted function (closing over a metadata-only
# unpack view) + result shapes — never batch data.
_GLOBAL_PROGRAMS = _BoundedLRU(64)


def total_resident_bytes() -> int:
    """Bytes resident over ALL devices: the ledger that returns to zero
    when every table is unpersisted."""
    return sum(c.nbytes for c in _ACTIVE_CACHES)


def resident_bytes_per_device() -> int:
    """Bytes resident on the FULLEST device, which is what
    ``MAX_RESIDENT_BYTES`` bounds: every live cache's per-device share,
    summed (tables on different meshes are taken to meet on one device).
    On one device it equals ``total_resident_bytes()``."""
    return sum(c.per_device_bytes for c in _ACTIVE_CACHES)


def persist_table(
    table: ColumnarTable,
    mesh=None,
    chunk_rows: Optional[int] = None,
    max_bytes: Optional[int] = None,
    encode: Optional[bool] = None,
) -> DeviceTableCache:
    """Pack ALL columns of the table and transfer them to device HBM once.

    Returns the cache and attaches it to ``table._device_cache`` so every
    subsequent ``run_scan`` over this table skips host packing + transfer.

    ``max_bytes`` (default ``DeviceTableCache.MAX_RESIDENT_BYTES``) bounds
    what ONE device holds: under a mesh the table's per-device share plus
    what is resident there already; past it the typed ``MemoryError``.

    Columns carrying a dictionary encoding stay ENCODED in HBM (int16
    code plane + dictionary LUTs, 2-8x smaller than the decoded planes);
    scans decode via a fused gather.
    ``encode`` overrides the DEEQU_TPU_ENCODED_INGEST default.
    """
    from deequ_tpu.ops.scan_plan import encoded_ingest_enabled

    encode = encoded_ingest_enabled(encode)
    if max_bytes is None:
        max_bytes = DeviceTableCache.MAX_RESIDENT_BYTES
    if mesh is None:
        mesh = current_mesh()
    cols = {name: table[name] for name in table.column_names}
    n_rows = table.num_rows
    n_dev = math.prod(mesh.devices.shape) if mesh is not None else 1
    # resident chunks can be much larger than streaming ones: every extra
    # chunk costs a device dispatch (on a mesh a round of collectives and
    # a fold merge too), and each device's HBM holds its share of the
    # whole table anyway. The byte target is per device, so the chunk in
    # rows grows with the mesh
    chunk = chunk_rows or min(
        _auto_chunk_rows(
            cols,
            target_bytes=RESIDENT_CHUNK_BYTES * n_dev,
            max_rows=MAX_RESIDENT_CHUNK_ROWS,
        ),
        max(n_rows, 1),
    )
    chunk = max(n_dev, ((chunk + n_dev - 1) // n_dev) * n_dev)

    with seam("persist.pack", what="layout"):
        # the layout check reads every column once (range, nulls)
        packer = _ChunkPacker(cols, chunk, encode_ingest=encode)
    put = _make_put(mesh)

    n_chunks = max(1, (n_rows + chunk - 1) // chunk)
    device_chunks = []
    nbytes = 0
    for ci in range(n_chunks):
        start = ci * chunk
        stop = min(start + chunk, n_rows)
        with seam("persist.pack", chunk=ci):
            args = packer.pack(start, stop)
        chunk_bytes = sum(a.nbytes for a in args)
        nbytes += chunk_bytes
        if nbytes // n_dev + resident_bytes_per_device() > max_bytes:
            raise MemoryError(
                f"persist_table: combined resident size would exceed "
                f"{max_bytes} bytes on each of {n_dev} device(s); stream "
                f"instead or raise max_bytes"
            )
        with seam("persist.stage", chunk=ci, bytes=chunk_bytes, devices=n_dev):
            device_chunks.append(put(args))
    with seam("persist.stage", wait=True):
        jax.block_until_ready(device_chunks)
    cache = DeviceTableCache(packer, chunk, device_chunks, mesh, nbytes, n_dev)
    table._device_cache = cache
    return cache


def _chunk_shardings(mesh):
    """Per-buffer shardings for one packed chunk tuple (values, hi, lo,
    narrow_i, masks, codes, row_valid, enc): column-planes shard rows
    along axis 1, row_valid along axis 0."""
    from jax.sharding import NamedSharding

    plane = NamedSharding(mesh, P(None, ROW_AXIS))
    return tuple(
        [plane] * 6 + [NamedSharding(mesh, P(ROW_AXIS))] + [plane]
    )


def _make_put(mesh):
    """Async host->device transfer fn; in the mesh path buffers land
    host->each-device directly with the shardings matching in_specs (no
    redistribution hop)."""
    if mesh is None:
        return jax.device_put
    arg_shardings = _chunk_shardings(mesh)

    def put(args):
        return tuple(jax.device_put(a, s) for a, s in zip(args, arg_shardings))

    return put


def _split_lut_key(key: str) -> Tuple[str, str]:
    col, _, kind = key.partition("\x00")
    return col, kind


def op_scope(op: "ScanOp") -> str:
    """The ``jax.named_scope`` of one op's reductions in the fused step,
    ``deequ.<Analyzer>.<column>[_<column>]``: what a device trace calls
    the XLA ops it lowers to (metadata only: no operation changes).
    ``cache_key`` is the analyzer that built the op (or a tuple whose
    head names a coalesced kernel)."""
    key = op.cache_key
    if key is None:
        label = "op"
    elif isinstance(key, tuple):
        label = str(key[0])
    else:
        label = type(key).__name__
    parts = ["deequ", label] + (["_".join(op.columns)] if op.columns else [])
    return ".".join(parts).replace("/", "_")


def _scoped_update(op: "ScanOp", vals, row_valid, local_n):
    if op.plane_route is not None:
        # the batched sweeps lower under their own names, not under the
        # scope of whichever routed op is traced first
        vals.plane.of(op.plane_route)
    with jax.named_scope(op_scope(op)):
        return op.update(vals, row_valid, jnp, local_n)


def _build_step_fns(ops, unpacker, mesh, local_n, lut_keys: Tuple[str, ...] = ()):
    """Build (jitted flat step fn, shape fn, raw flat fn) for one packer
    layout — the raw (unjitted) flat fn is what the plan lint traces.

    The flat step computes every op's partial state for one packed chunk,
    merges across the mesh with per-leaf collectives, and concatenates all
    leaves into ONE f64 vector: every device->host fetch pays the link's
    round-trip floor PER BUFFER (~1 ms on the v5e host, PR 21), and a fused
    scan easily produces hundreds of small state leaves (f64 is lossless
    for all state leaves: counts < 2^53, registers i32). ``lut_keys`` names
    the dictionary LUTs passed as an extra dict argument (replicated across
    the mesh)."""

    def step(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts):
        col_luts: Dict[str, Dict[str, Any]] = {}
        for key, arr in luts.items():
            col, kind = _split_lut_key(key)
            col_luts.setdefault(col, {})[kind] = arr
        with jax.named_scope("deequ.unpack"):
            vals = unpacker.unpack_vals(
                values, hi, lo, narrow_i, masks, codes, jnp, row_valid,
                col_luts=col_luts, enc=enc,
            )
        partials = tuple(
            _scoped_update(op, vals, row_valid, local_n) for op in ops
        )
        if mesh is not None:
            partials = tuple(
                jax.tree.map(
                    partial(_tag_collective, axis_name=ROW_AXIS),
                    op.tags,
                    p,
                )
                for op, p in zip(ops, partials)
            )
        else:
            partials = tuple(
                jax.tree.map(_tag_identity_wrap, op.tags, p)
                for op, p in zip(ops, partials)
            )
        return partials

    def _flatten(partials):
        leaves = jax.tree.leaves(partials)
        with jax.named_scope("deequ.flatten"):
            return jnp.concatenate(
                [jnp.ravel(leaf).astype(jnp.float64) for leaf in leaves]
            )

    if mesh is not None:
        inner = shard_map(
            step,
            mesh=mesh,
            in_specs=(
                P(None, ROW_AXIS), P(None, ROW_AXIS), P(None, ROW_AXIS),
                P(None, ROW_AXIS), P(None, ROW_AXIS), P(None, ROW_AXIS),
                P(ROW_AXIS), P(None, ROW_AXIS),
                {key: P() for key in lut_keys},
            ),
            out_specs=P(),
            check_vma=False,
        )

        def flat_outer(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts):
            return _flatten(
                inner(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts)
            )

        return jax.jit(flat_outer), inner, flat_outer

    def flat_single(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts):
        return _flatten(
            step(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts)
        )

    return jax.jit(flat_single), step, flat_single


def _unflatten_partials(flat: np.ndarray, shapes):
    leaves = []
    offset = 0
    for sd in jax.tree.leaves(shapes):
        size = int(np.prod(sd.shape)) if sd.shape else 1
        # integer leaves (i32 device counts) widen to i64 on host: the
        # cross-CHUNK accumulation in _tag_reduce_np would otherwise wrap
        # silently past 2^31 rows on long streams (per-chunk counts fit
        # i32 by construction; the accumulator must not)
        dtype = np.int64 if np.issubdtype(sd.dtype, np.integer) else sd.dtype
        leaf = flat[offset:offset + size].reshape(sd.shape).astype(dtype)
        leaves.append(leaf if sd.shape else leaf.reshape(()))
        offset += size
    return jax.tree.unflatten(jax.tree.structure(shapes), leaves)


def _collect_luts(ops, dictionaries: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Build (memoized) + device-put every dictionary LUT the ops declare.
    Returns {"col\\x00kind": device_array}."""
    from deequ_tpu.ops.lut_cache import dictionary_lut_device

    lut_arrays: Dict[str, Any] = {}
    for op in ops:
        for col, kind, builder in op.luts:
            key = col + "\x00" + kind
            if key in lut_arrays:
                continue
            lut_arrays[key] = dictionary_lut_device(
                dictionaries[col], kind, builder, mesh
            )
    return lut_arrays


def _enc_hi_lut(d):
    from deequ_tpu.ops.df32 import split_pair_np

    # deequ-lint: ignore[host-fetch] -- d is a host numpy dictionary (lut_cache builder input), never a device array
    return split_pair_np(np.asarray(d, dtype=np.float64))[0]


def _enc_lo_lut(d):
    from deequ_tpu.ops.df32 import split_pair_np

    # deequ-lint: ignore[host-fetch] -- d is a host numpy dictionary (lut_cache builder input), never a device array
    return split_pair_np(np.asarray(d, dtype=np.float64))[1]


def _enc_i32_lut(d):
    # deequ-lint: ignore[host-fetch] -- d is a host numpy dictionary (lut_cache builder input), never a device array
    return np.asarray(d, dtype=np.int32)


def _collect_enc_luts(packer, mesh) -> Dict[str, Any]:
    """Device LUTs for the packer's ENCODED columns: the dictionary's
    precomputed (hi, lo) pair planes (fractional — gathering the split of
    a dictionary entry is bit-identical to splitting the decoded value)
    or its i32 entries (integral). Memoized per dictionary identity like
    the string LUTs (ops/lut_cache.py), pow2-padded, shipped once and
    passed to the jitted step as arguments — re-runs ship no dictionary
    bytes and programs stay cacheable across tables."""
    from deequ_tpu.ops.lut_cache import dictionary_lut_device

    lut_arrays: Dict[str, Any] = {}
    for name in packer.enc_names:
        d = packer.enc_dict[name]
        if packer.col_dtype[name] == DType.INTEGRAL:
            lut_arrays[name + "\x00_enc_i32"] = dictionary_lut_device(
                d, "_enc_i32", _enc_i32_lut, mesh
            )
        else:
            lut_arrays[name + "\x00_enc_hi"] = dictionary_lut_device(
                d, "_enc_hi", _enc_hi_lut, mesh
            )
            lut_arrays[name + "\x00_enc_lo"] = dictionary_lut_device(
                d, "_enc_lo", _enc_lo_lut, mesh
            )
    return lut_arrays


def _lut_sig(lut_arrays: Dict[str, Any]):
    """Shape/dtype signature of the LUT argument set (part of the program
    identity — content is a runtime input, shape is compile-time)."""
    return tuple(
        sorted(
            (key, int(arr.shape[0]), str(arr.dtype))
            for key, arr in lut_arrays.items()
        )
    )


def _ops_prog_key(ops, chunk, lut_sig=()):
    """Hashable identity of the fused program, or None if any op opted out."""
    if not all(op.cache_key is not None for op in ops):
        return None
    try:
        key = (tuple(op.cache_key for op in ops), chunk, lut_sig)
        hash(key)
        return key
    except TypeError:
        return None


def _mesh_key(mesh):
    return (
        (mesh.devices.shape, tuple(mesh.axis_names), tuple(mesh.devices.flat))
        if mesh is not None
        else None
    )


def _global_prog_key(prog_key, packer, mesh):
    """Key for the cross-table program cache. Only table-INDEPENDENT
    programs are cacheable: string ops that route their dictionary
    dependence through LUT arguments qualify; an op that reads the
    dictionary at trace time (dictionary_baked) bakes per-table constants
    and disables the cache (checked by the caller)."""
    if prog_key is None:
        return None
    layout = (
        tuple(packer.wide_names),
        tuple(packer.narrow_i32),
        tuple(packer.pair_names),
        tuple(packer.masked_names),
        tuple(packer.string_names),
        tuple(packer.enc_names),
        # packer.col_dtype, not the caller's needed-column subset: a
        # persisted table's packer covers ALL table columns
        tuple((name, packer.col_dtype[name]) for name in packer.numeric_names),
    )
    return (prog_key, layout, _mesh_key(mesh))


class _DeviceFoldPlan:
    """The on-device analogue of ``_tag_reduce_np``: folds per-chunk flat
    state vectors into a single device-resident accumulator so a whole
    scan pays ONE device->host fetch (of the tiny final vector) instead
    of one per chunk.

    Accumulator layout (one flat f64 vector)::

        [ elementwise region | gather region | chunk counter (1) ]

    - sum/min/max leaves live in the elementwise region and merge with
      plain f64 ops — the exact operations the host fold applies, in the
      same left-to-right chunk order, so results are bit-identical
      (``deequ_tpu.ops.df32.merge_tags_f64`` documents why the merge must
      NOT be compensated);
    - 'gather' leaves (Welford moments, co-moments) append into a
      fixed-capacity block of ``capacity`` chunk slots via
      ``dynamic_update_slice`` at the on-device chunk counter — the
      device-side equivalent of the host's np.concatenate, order
      preserved;
    - the counter rides in the accumulator itself so the merge needs no
      per-chunk host scalar (each host->device transfer costs a round
      trip on slow links).

    Integer leaves accumulate in f64 (exact below 2^53 — far past the
    2^31 wrap ``_unflatten_partials`` widens against) and widen to i64 at
    the final host unflatten, matching the host fold's dtypes.
    """

    def __init__(self, ops, shapes, capacity: int, donate: bool):
        self.capacity = int(capacity)
        elem_off = 0
        gather_off = 0
        src_off = 0
        elem_src: List[np.ndarray] = []
        sum_mask: List[np.ndarray] = []
        min_mask: List[np.ndarray] = []
        elem_init: List[np.ndarray] = []
        self._gather_specs: List[Tuple[int, int, int]] = []
        # per op: (treedef, [(tag, region_off, size, shape, dtype), ...])
        self._op_plans = []
        contiguous = True
        for op, shp in zip(ops, shapes):
            tag_leaves = jax.tree.leaves(op.tags)
            shape_leaves = jax.tree.leaves(shp)
            if len(tag_leaves) != len(shape_leaves):
                raise ValueError(
                    f"op {op.cache_key!r}: tags/partials structure mismatch"
                )
            leaf_plans = []
            for tag, sd in zip(tag_leaves, shape_leaves):
                size = int(np.prod(sd.shape)) if sd.shape else 1
                if tag == "gather":
                    contiguous = False
                    self._gather_specs.append((src_off, size, gather_off))
                    leaf_plans.append(
                        (tag, gather_off, size, sd.shape, sd.dtype)
                    )
                    gather_off += self.capacity * size
                else:
                    elem_src.append(np.arange(src_off, src_off + size))
                    is_sum = tag == "sum"
                    is_min = tag == "min"
                    if not (is_sum or is_min or tag == "max"):
                        raise ValueError(f"unknown reduce tag {tag}")
                    sum_mask.append(np.full(size, is_sum))
                    min_mask.append(np.full(size, is_min))
                    elem_init.append(
                        np.full(
                            size,
                            0.0 if is_sum else (np.inf if is_min else -np.inf),
                        )
                    )
                    leaf_plans.append((tag, elem_off, size, sd.shape, sd.dtype))
                    elem_off += size
                src_off += size
            self._op_plans.append((jax.tree.structure(shp), leaf_plans))
        self.elem_size = elem_off
        self.gather_size = gather_off
        self.acc_size = self.elem_size + self.gather_size + 1
        cat = lambda parts, dt: (  # noqa: E731
            np.concatenate(parts).astype(dt)
            if parts
            else np.zeros(0, dtype=dt)
        )
        # when no gather leaves exist the elementwise region IS the chunk
        # flat (same order, same offsets): skip the take() entirely
        self._elem_src = None if contiguous else cat(elem_src, np.int32)
        self._sum_mask = cat(sum_mask, bool)
        self._min_mask = cat(min_mask, bool)
        self._init_np = np.concatenate(
            [cat(elem_init, np.float64), np.zeros(self.gather_size + 1)]
        )
        donate_args = (0,) if donate else ()
        self._merge_jit = jax.jit(self.merge_body, donate_argnums=donate_args)

    def fresh_init(self):
        """A NEW device accumulator (never reuse one across scans: the
        first merge donates it): a host->device put like a chunk's, so
        under the ``stage`` seam and in ``bytes_staged`` (on the caller's
        thread whatever the watchdog: the default device is the
        thread's)."""
        nbytes = self._init_np.nbytes
        acc = device_call(
            lambda: jnp.asarray(self._init_np), "transfer",
            what="fold accumulator", bytes=nbytes,
        )
        SCAN_STATS.record_staged(nbytes, overlapped=False, count_chunk=False)
        return acc

    def merge_body(self, acc, new):
        """Pure traced merge: fold one chunk's flat vector into the
        accumulator (left-to-right order = call order)."""
        with jax.named_scope("deequ.fold"):
            return self._merge(acc, new)

    def _merge(self, acc, new):
        if self.elem_size:
            from deequ_tpu.ops.df32 import merge_tags_f64

            elem = acc[: self.elem_size]
            new_elem = new if self._elem_src is None else new[self._elem_src]
            merged = merge_tags_f64(
                self._sum_mask, self._min_mask, elem, new_elem, jnp
            )
            acc = jax.lax.dynamic_update_slice(acc, merged, (0,))
        if self._gather_specs:
            ci = acc[self.acc_size - 1].astype(jnp.int32)
            for src, size, base in self._gather_specs:
                chunk_leaf = jax.lax.dynamic_slice(new, (src,), (size,))
                acc = jax.lax.dynamic_update_slice(
                    acc, chunk_leaf, (self.elem_size + base + ci * size,)
                )
        return jax.lax.dynamic_update_slice(
            acc,
            acc[self.acc_size - 1 :] + 1.0,
            (self.acc_size - 1,),
        )

    def merge(self, acc, new):
        return self._merge_jit(acc, new)

    def unflatten_host(self, flat: np.ndarray, filled: int) -> List[Any]:
        """The fetched accumulator back into per-op reduced pytrees —
        shaped exactly like the host fold's output (`filled` = chunks
        actually merged; gather blocks truncate to it)."""
        out = []
        for treedef, leaf_plans in self._op_plans:
            leaves = []
            for tag, off, size, shape, dtype in leaf_plans:
                wide = (
                    np.int64 if np.issubdtype(dtype, np.integer) else dtype
                )
                if tag == "gather":
                    base = self.elem_size + off
                    block = flat[base : base + filled * size]
                    lead = shape[0] if shape else 1
                    leaf = block.reshape((filled * lead,) + tuple(shape[1:]))
                else:
                    leaf = flat[off : off + size].reshape(shape)
                    if not shape:
                        leaf = leaf.reshape(())
                leaves.append(leaf.astype(wide))
            out.append(jax.tree.unflatten(treedef, leaves))
        return out


# memoized fold plans (each carries one jitted merge program): keyed on
# the leaf-level identity so repeated scans of the same analyzer suite
# reuse one compiled merge instead of retracing per run
_FOLD_PLANS = _BoundedLRU(64)


def _fold_plan_for(ops, shapes, capacity: int) -> _DeviceFoldPlan:
    # donation makes the merge update the accumulator in place; the CPU
    # backend doesn't implement donation and would warn per compile
    donate = jax.default_backend() != "cpu"
    try:
        key = (
            capacity,
            donate,
            tuple(
                (
                    jax.tree.structure(shp),
                    tuple(
                        (tag, tuple(sd.shape), str(sd.dtype))
                        for tag, sd in zip(
                            jax.tree.leaves(op.tags), jax.tree.leaves(shp)
                        )
                    ),
                )
                for op, shp in zip(ops, shapes)
            ),
        )
        hash(key)
    except TypeError:
        key = None
    if key is not None:
        plan = _FOLD_PLANS.get(key)
        if plan is not None:
            return plan
    plan = _DeviceFoldPlan(ops, shapes, capacity, donate)
    if key is not None:
        _FOLD_PLANS.put(key, plan)
    return plan


class _PartialFolder:
    """Accumulates per-chunk flat results into per-op reduced pytrees.

    Two modes: the host fold (one ``drain`` per chunk result, tag-reduced
    with numpy) and the device fold (``fold_plan`` set: each drained
    vector is a device-side accumulator covering ``fold_filled`` chunks,
    unflattened by the plan and merged — a scan that stays within one
    accumulator drains exactly once)."""

    def __init__(self, ops, deadline: Optional[float] = None):
        self.ops = ops
        self.merged = None
        self.shapes = None
        self.fold_plan: Optional[_DeviceFoldPlan] = None
        self.fold_filled = 0
        # the run's watchdog deadline: the fetch below is the blocking
        # device round trip, the watchdog's prime target
        self.deadline = deadline

    def drain(self, device_result, newest=False) -> None:
        # host-side slices (fetch_deferred hands those out) are already
        # materialized: only a true device array is a fetch. Async device
        # failures (OOM, device loss) surface HERE: device_call classifies
        # them once, so every drain path (inline, deferred, grouped)
        # raises typed, and a hung device becomes DeviceHangException.
        # ``newest`` names the dispatch this result proves ready when it
        # is its scan's last (device_policy.device_call): the feed gauge
        # drops where its wait ends
        if isinstance(device_result, np.ndarray):
            flat = device_result
        else:
            flat = device_fetch(
                device_result, "scan drain", self.deadline, newest,
            )
            SCAN_STATS.record_fetch(flat.nbytes)
        with seam("evaluate", what="fold"):
            self._fold(flat)

    def _fold(self, flat: np.ndarray) -> None:
        if self.fold_plan is not None:
            # the vector IS an accumulator already covering fold_filled
            # chunks: unflatten and merge (a second drain only happens
            # when a stream overflowed the gather capacity)
            partials = self.fold_plan.unflatten_host(flat, self.fold_filled)
            SCAN_STATS.chunks_processed += self.fold_filled
        else:
            partials = _unflatten_partials(flat, self.shapes)
            SCAN_STATS.chunks_processed += 1
        if self.merged is None:
            self.merged = list(partials)
        else:
            out = []
            for op, acc, p in zip(self.ops, self.merged, partials):
                m = jax.tree.map(_tag_reduce_np, op.tags, acc, p)
                if op.compact is not None:
                    gathered = max(
                        (
                            np.size(leaf)
                            for tag, leaf in zip(
                                jax.tree.leaves(op.tags), jax.tree.leaves(m)
                            )
                            if tag == "gather"
                        ),
                        default=0,
                    )
                    if gathered > op.compact_threshold:
                        m = op.compact(m)
                out.append(m)
            self.merged = out


class DeferredScan:
    """An in-flight fused scan: dispatch has happened, device results have
    NOT been fetched. ``result()`` drains — calling it is the one host
    round trip. Lets incremental pipelines keep several batches' scans in
    flight (analyzers/incremental.py) so the per-fetch PCIe round trip
    amortizes across batches instead of serializing them."""

    def __init__(
        self,
        folder: _PartialFolder,
        in_flight,
        inline: bool = False,
        scan_id: Optional[int] = None,
        leases: Sequence[_StagingLease] = (),
    ):
        self._folder = folder
        self._in_flight = in_flight
        # the staging planes of host-packed chunks whose results are still
        # in flight: back to the pool once every pending result is fetched
        self._leases = leases
        # resolved-inline scans (run_scan defer=False) drain inside the
        # attempt's own scan_attempt seam; a genuinely deferred scan
        # opens one around its BLOCKING drain segment — the wall between
        # dispatch and drain belongs to the caller, and with several
        # scans in flight it would double-count in scan_seconds
        self._inline = inline
        self._scan_id = scan_id
        # the thread's newest dispatch is this scan's last: what the
        # last of the pending results proves ready when it is fetched
        self._fed_mark = fed_mark()
        self._done = False
        self._error: Optional[BaseException] = None

    def result(self) -> List[Any]:
        if not self._done:
            pending = self._in_flight
            leases, self._leases = self._leases, ()
            self._in_flight = []
            self._done = True
            with (
                nullcontext() if self._inline
                else seam("scan_attempt", scan_id=self._scan_id,
                          deferred=True)
            ):
                try:
                    for i, device_result in enumerate(pending, 1):
                        self._folder.drain(
                            device_result,
                            i == len(pending) and self._fed_mark,
                        )
                    for lease in leases:
                        lease.release()
                except BaseException as e:  # noqa: BLE001 — a retry must
                    # not re-fold already-drained chunks into the
                    # accumulator, and even a KeyboardInterrupt mid-drain
                    # must leave the scan FAILED, never silently
                    # half-folded. Non-Exception control-flow signals
                    # (Ctrl-C) propagate immediately.
                    self._error = e
                    if not isinstance(e, Exception):
                        raise
        if self._error is not None:
            raise self._error
        return self._folder.merged


def fetch_deferred(scans: Sequence["DeferredScan"]) -> None:
    """Drain several DeferredScans with ONE device->host fetch.

    Each scan's pending chunk results are tiny flat f64 vectors; fetches
    serialize at the link's fixed round-trip latency regardless of size,
    so fetching them one scan at a time makes an incremental loop of small
    batches latency-bound. Here
    every pending vector concatenates ON DEVICE (one async dispatch) and
    comes back in a single fetch; the slices then feed each scan's folder
    in order. After this, ``result()`` on every scan is free."""
    pending = [s for s in scans if not s._done and s._in_flight]
    if not pending:
        return
    with seam("scan_attempt", deferred=True, scans=len(pending)):
        _fetch_deferred(pending)


def _fetch_deferred(pending: Sequence["DeferredScan"]) -> None:
    arrays = [a for s in pending for a in s._in_flight]
    # a CPU-fallback scan's accumulator is committed to the CPU backend
    # while its siblings sit on the accelerator — cross-device arrays
    # cannot concatenate, so a mixed window (rare: only around a
    # fallback) fetches per array instead of coalescing
    def _dev_key(a):
        try:
            return tuple(sorted(str(d) for d in a.devices()))
        # deequ-lint: ignore[bare-except] -- device-placement probe on maybe-non-jax arrays; absence of .devices() IS the answer
        except Exception:  # noqa: BLE001 — non-jax array
            return None

    same_device = len({_dev_key(a) for a in arrays}) <= 1
    # the watchdog deadline travels with the scans (per-run
    # device_deadline), falling back to the process-wide env default —
    # this blocking fetch is where async faults and hangs surface now
    deadline = next(
        (s._folder.deadline for s in pending
         if s._folder.deadline is not None),
        default_device_deadline(),
    )

    # the newest dispatch among the scans': what this fetch proves ready
    newest = max((s._fed_mark for s in pending), key=lambda mark: mark[1])

    def materialize():
        if len(arrays) == 1 or not same_device:
            return wait_then_copy(arrays, newest)
        # the one round trip
        host = wait_then_copy(jnp.concatenate(arrays), newest)
        parts = []
        off = 0
        for a in arrays:
            size = int(a.shape[0])
            parts.append(host[off:off + size])
            off += size
        return parts

    # the coalesced fetch is a device boundary like any other: classify
    # async faults typed and keep the watchdog armed (a hung device at
    # this blocking fetch must become DeviceHangException, not a freeze)
    parts = device_call(
        materialize, "fetch", what="deferred scan fetch", deadline=deadline,
        newest=newest,
    )
    for s in pending:  # every pending result is on the host: all ran
        leases, s._leases = s._leases, ()
        for lease in leases:
            lease.release()
    # the batched round trip is a device->host fetch like any other —
    # attribute it so the one-fetch contract stays observable (the
    # per-scan folder.drain calls below see numpy slices and count
    # nothing)
    with SCAN_STATS._fetch_lock:
        SCAN_STATS.device_fetches += (
            len(arrays) if (len(arrays) > 1 and not same_device) else 1
        )
        SCAN_STATS.bytes_fetched += sum(p.nbytes for p in parts)
    i = 0
    for s in pending:
        n_parts = len(s._in_flight)
        s._in_flight = []
        s._done = True
        try:
            for k in range(n_parts):
                s._folder.drain(parts[i + k])
        except BaseException as e:  # noqa: BLE001 — isolate per scan (a
            # bad fold fails ITS analyzers at result()) AND keep the
            # half-folded-accumulator invariant: even a KeyboardInterrupt
            # mid-drain leaves the scan marked failed, never retryable.
            # Non-Exception control-flow signals propagate immediately.
            s._error = e
            if not isinstance(e, Exception):
                raise
        i += n_parts


# smallest chunk the OOM bisection will try before giving up: below this
# the per-chunk dispatch overhead dominates and an OOM is no longer about
# chunk size (something else holds the HBM). log2(MAX_CHUNK_ROWS/64) = 17
# bounds the halvings of any single scan
MIN_BISECT_CHUNK_ROWS = 64

# one id per logical run_scan call, stable across bisection/fallback
# retries — the key the deterministic fault hook scripts against
_SCAN_IDS = itertools.count()


#: histogram passes one selection-kernel summary dispatch runs (the
#: 16+8+8-bit radix plan of ops/select_device._select_u32_multirank)
_HIST_PASSES_PER_SELECT = 3


def _record_kernel_passes(plan_ir, chunks: int) -> None:
    """Account the per-chunk KLL/quantile kernel census of one or more
    chunk dispatches (ops/scan_plan.py): how many ran a device sort vs
    the histogram selection kernel — the observable behind the config-3
    zero-sort contract — and, for selection dispatches, the histogram
    kernel-variant census (each selection summary runs three bincount
    passes under the plan's resolved hist_variant); and how many ops
    read their scalars out of the batched plane statistics."""
    if chunks:
        SCAN_STATS.plane_ops += plan_ir.plane_ops * chunks
        SCAN_STATS.device_sort_passes += plan_ir.sort_ops * chunks
        SCAN_STATS.device_select_passes += plan_ir.select_ops * chunks
        SCAN_STATS.hll_folds += plan_ir.hll_folds * chunks
        if plan_ir.select_ops and plan_ir.hist_variant != "none":
            SCAN_STATS.record_hist_dispatch(
                plan_ir.hist_variant,
                _HIST_PASSES_PER_SELECT * plan_ir.select_ops * chunks,
            )


def _maybe_plan_lint(
    plan_ir,
    raw_flat,
    args,
    lut_arrays,
    prog_key,
    packer,
    mesh,
    mode: str,
    fallback: bool = False,
) -> None:
    """Static plan lint (deequ_tpu/lint/plan_lint.py): trace the fused
    flat step to a jaxpr and check the IR against the contracts the plan
    declares — BEFORE the first dispatch of the attempt, so a
    planner/packer drift (a sort primitive inside a selection-variant
    plan, a mis-tagged fold leaf) is rejected as a typed
    ``PlanLintError`` while the program is still just IR.

    Memoized alongside the FULL program identity — the same
    (prog_key, packer layout, mesh) triple `_global_prog_key` uses for
    the cross-table program cache, plus variant and backend leg — so a
    program rebuilt under a different packer layout lints fresh instead
    of inheriting another layout's verdict, and enforcement still costs
    one trace per (plan, kernel-variant). Dictionary-baked programs
    (table-specific constants in the trace) skip memoization entirely,
    mirroring their exclusion from the program cache. Each attempt of
    the fault ladder re-enters here with ITS plan, which is exactly the
    re-lint the ladder's re-planning needs (an OOM-mid-selection retry
    lints under the sort variant's contract, the CPU fallback re-jit
    lints once on its own key)."""
    if mode == "off" or not args:
        return
    from deequ_tpu.lint.plan_lint import enforce_plan_lint, lint_plan_cached

    with seam("plan", what="plan_lint", variant=plan_ir.variant, mode=mode):
        avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
        memo_key = None
        baked = any(op.dictionary_baked for op in plan_ir.ops)
        if prog_key is not None and not baked:
            global_key = _global_prog_key(prog_key, packer, mesh)
            if global_key is not None:
                memo_key = (
                    global_key,
                    plan_ir.variant,
                    plan_ir.hist_variant,
                    plan_ir.ingest_variant,
                    plan_ir.encoded_columns,
                    plan_ir.fold_tags,
                    # fusion signature: fused and unfused variants of the
                    # same op set lint separately (plan-fusion-refetch)
                    plan_ir.fusion,
                    bool(fallback),
                )
        findings, traced = lint_plan_cached(
            plan_ir, lambda *a: raw_flat(*a, lut_arrays), avals, memo_key
        )
        if traced:
            SCAN_STATS.plan_lint_traces += 1
        if findings:
            SCAN_STATS.plan_lints.extend(f.as_dict() for f in findings)
        enforce_plan_lint(findings, mode)


def _block_throttle(arr, what: str, deadline: Optional[float]) -> None:
    """Wait for a device result WITHOUT fetching it (pipeline
    backpressure for the device-fold loops). The wait is a ``drain`` —
    time blocked on the device — but moves no bytes and counts no
    fetch."""
    device_call(
        lambda: jax.block_until_ready(arr), "execute", what=what,
        deadline=deadline, seam_name="drain",
    )


def _cpu_fallback_device():
    """The CPU device the fallback re-jits on, or None when the process
    has no CPU backend (e.g. JAX_PLATFORMS pinned to the accelerator
    only) — then the typed device error propagates instead of a
    confusing secondary backend-lookup failure."""
    try:
        return jax.devices("cpu")[0]
    # deequ-lint: ignore[bare-except] -- backend-registration probe: no CPU backend is a valid state, not a device fault
    except Exception:  # noqa: BLE001 — backend not registered
        return None


def _evict_device_cache(table) -> int:
    """Free a persisted table's HBM residency (the first response to a
    device OOM: the resident chunks are the biggest HBM tenant). Returns
    the bytes released."""
    cache = getattr(table, "_device_cache", None)
    if cache is None:
        return 0
    freed = cache.nbytes
    # drop the buffers eagerly — the WeakSet entry dies with the cache,
    # but the device arrays must not wait for a GC cycle mid-OOM (any
    # in-flight fold accumulator dies with the attempt: a bisected retry
    # starts a fresh fold)
    cache.device_chunks = []
    cache.programs.clear()
    # the cache object may outlive the eviction (a caller's reference, a
    # pending GC cycle): zero its accounting and drop it from the live
    # set NOW, or total_resident_bytes() keeps charging the HBM budget
    # for buffers that no longer exist
    cache.nbytes = 0
    _ACTIVE_CACHES.discard(cache)
    table._device_cache = None
    return freed


def _governed_attempt(budget, fn: Callable, what: str):
    """Run one WHOLE scan attempt under the run budget's wall watchdog.

    One worker thread per governed attempt — never per device call: the
    healthy-path cost of governance must stay <1% of wall (bench.py's
    ``measure_governance_overhead`` contract), and a per-call watchdog
    measured ~30% on the config-1 profile. A hang anywhere inside the
    attempt becomes a typed ``DeviceHangException`` at the remaining
    budget, which the ladder then charges — so termination within
    ``run_deadline`` holds for hangs, not just exceptions. Ungoverned
    (or deadline-free) budgets run ``fn`` inline at zero cost.

    The ambient budget is THREAD-LOCAL, so the watchdog worker re-enters
    the scope explicitly — charge sites inside the attempt (stream-read
    retries) keep drawing on this run's ledger, and a worker abandoned
    after a timeout can only ever charge its own (exhausted) budget,
    never a later run's."""
    wall_left = budget.remaining_seconds() if budget is not None else None
    if wall_left is None:
        return fn()
    from deequ_tpu.resilience.governance import run_budget_scope

    # both ambient slots are thread-local; the watchdog worker re-enters
    # them (budget: so charge sites keep drawing on this run's ledger;
    # recorder: so the attempt's seam spans keep recording, parented to
    # the caller's current span)
    rec = current_recorder()
    rec_parent = rec.current_span_id() if rec is not None else None
    ids = seam_ids()

    def governed_fn():
        with run_budget_scope(budget), worker_seams(ids):
            if rec is not None:
                with recording_scope(rec, rec_parent):
                    return fn()
            return fn()

    # the attempt's own seams run on the watchdog worker as spans only;
    # what this thread spends is the wait for it. The worker feeds the
    # device where this thread cannot see: the wait counts as fed (no
    # unfed time is claimed for a governed attempt)
    with seam("drain", what=what, governed=True):
        device_fed()
        try:
            return _call_with_deadline(
                governed_fn, max(wall_left, MIN_BUDGET_WATCHDOG_SECONDS),
                what, "execute",
            )
        finally:
            device_ready()


def run_scan(
    table,
    ops: Sequence[ScanOp],
    chunk_rows: Optional[int] = None,
    mesh=None,
    defer: bool = False,
    on_device_error: str = "fail",
    device_deadline: Optional[float] = None,
    window: Optional[int] = None,
    shard_deadline: Optional[float] = None,
    select_kernel: Optional[bool] = None,
    plan_lint: Optional[str] = None,
    encoded_ingest: Optional[bool] = None,
    run_deadline: Optional[float] = None,
    max_total_attempts: Optional[int] = None,
    on_budget_exhausted: Optional[str] = None,
    trace=None,
) -> List[Any]:
    """Run all ops in ONE fused device pass over the table (in-memory,
    device-resident, or streaming).

    Returns one reduced numpy pytree per op — or, with ``defer=True`` (in-
    memory tables only), a ``DeferredScan`` whose ``result()`` fetches
    them later.

    When every op is ``device_foldable`` the per-chunk partials merge ON
    DEVICE (left-to-right chunk order) and the whole pass performs
    exactly one device->host fetch of the final flat state vector — the
    one-fetch-per-scan contract, observable as
    ``SCAN_STATS.device_fetches``. The ops and the residency select the
    fold: off a resident table an op with a ``compact()`` hook
    (``_folds_on_device``), and anywhere a gather leaf past
    ``MAX_FOLD_CAPACITY`` chunks, keeps the host fold (one fetch per
    chunk).

    ``window`` bounds in-flight chunks (pipelined dispatch); default 3,
    overridable process-wide via ``DEEQU_TPU_SCAN_WINDOW``.

    Device-fault policy (in-memory tables; ops/device_policy.py):

    - raw jaxlib/XLA failures at the pack/transfer, trace, and execute
      boundaries raise as typed ``Device*Exception``s;
    - a ``DeviceOOMException`` evicts the table's HBM residency, halves
      the chunk row count, and retries — down to ``MIN_BISECT_CHUNK_ROWS``
      — so the fused pass degrades to more, smaller device steps instead
      of an OOM cliff (each halving is a recorded degradation event);
    - ``on_device_error="fallback"`` re-runs the same fused program on the
      CPU backend when the accelerator fails to compile, is lost, hangs,
      or OOMs below the bisection floor (states are backend-agnostic
      monoids, so results match the accelerator's); ``"fail"`` (default)
      raises the typed exception;
    - ``device_deadline`` (seconds; default from
      ``DEEQU_TPU_DEVICE_DEADLINE``) arms the compute watchdog: a blocking
      device call that exceeds it raises ``DeviceHangException`` instead
      of hanging the run.

    Mesh-fault policy (multi-chip meshes; the degraded-mesh ladder is
    reshard -> bisect -> CPU fallback, and no path falls back to the CPU
    while a healthy accelerator subset remains):

    - a classified fault that NAMES its mesh member(s)
      (``MeshDegradedException`` / any ``Device*Exception`` with
      ``device_ids``) records against ``MESH_HEALTH``, evicts residency
      pinned to the failed chip(s), rebuilds the mesh over the largest
      healthy device subset, and re-dispatches the SAME fused program —
      the monoid fold restarts from scratch on the survivors, so the
      degraded result is bit-identical to a healthy run on that smaller
      mesh;
    - chips ``MESH_HEALTH`` has quarantined are excluded from the mesh
      UP FRONT (with a half-open probe readmitting them periodically),
      so a known-dead chip doesn't re-fail every scan first;
    - ``shard_deadline`` (seconds; default from
      ``DEEQU_TPU_SHARD_DEADLINE``) arms the straggler watchdog on mesh
      dispatches: a chip stalling a collective past it raises a typed
      ``DeviceHangException`` recorded as a ``mesh_straggler`` event.

    ``select_kernel`` (default: the DEEQU_TPU_SELECT_KERNEL env var,
    default on) routes resident KLL/quantile summary ops through the
    batched histogram selection kernel instead of the device sort
    (ops/scan_plan.py decides per attempt; ops/select_device.py is the
    kernel). ``select_kernel=False`` / DEEQU_TPU_SELECT_KERNEL=0 keeps
    the sort path everywhere — the A/B + regression-triage escape hatch.

    ``plan_lint`` (``"error"`` | ``"warn"`` | ``"off"``; default from
    ``DEEQU_TPU_PLAN_LINT``, default off) arms the STATIC plan lint
    (deequ_tpu/lint): each attempt's fused program is traced to a jaxpr
    and checked against the plan's declared contracts before dispatch —
    a selection-variant plan containing a ``sort`` primitive, a host
    callback inside the fused program, or a mis-tagged fold leaf raises
    a typed ``PlanLintError`` (``"error"``) or warns
    (``PlanLintWarning``). Findings land on ``SCAN_STATS.plan_lints``;
    results are memoized with the program cache so the lint costs one
    trace per (plan, kernel-variant), observable via
    ``SCAN_STATS.plan_lint_traces``.

    ``encoded_ingest`` (default: the DEEQU_TPU_ENCODED_INGEST env var,
    default on) routes dictionary-encoded columns over the int16 code
    plane with decode fused into the program (docs/ingest.md); ``False``
    / DEEQU_TPU_ENCODED_INGEST=0 packs every column decoded — the A/B
    escape hatch. A device OOM during an encoded attempt DEMOTES the
    rest of the run onto the decoded path (recorded as an
    ``encoded_demote`` degradation event) before any chunk bisection,
    exactly like the selection->sort re-plan.

    Run-level governance (resilience/governance.py): ``run_deadline`` /
    ``max_total_attempts`` (defaults from ``DEEQU_TPU_RUN_DEADLINE`` /
    ``DEEQU_TPU_RUN_ATTEMPTS``) arm ONE fault budget for this scan that
    every rung of the composed ladder charges — I/O retries, OOM
    bisections, encoded demotions, mesh reshards, CPU fallback
    transitions. A scan already running under an ambient
    ``run_budget_scope`` (e.g. one VerificationSuite run spanning many
    per-batch scans) charges THAT budget instead — the per-scan
    arguments never stack a second ledger on top. The first charge past
    the budget raises a typed ``RunBudgetExhaustedException``
    (``degraded`` flag per ``on_budget_exhausted``); when the budget
    carries a wall deadline, each WHOLE scan attempt (and the fallback
    rung, and whole stream scans) additionally runs under one
    attempt-level watchdog armed with the remaining budget
    (``_governed_attempt``) so even a hung device call terminates typed
    within ``run_deadline`` — one worker thread per attempt, so healthy
    runs stay within the <1% governance-overhead contract.
    """
    from deequ_tpu.lint.plan_lint import plan_lint_mode
    from deequ_tpu.ops.scan_plan import (
        encoded_ingest_enabled,
        select_kernel_enabled,
    )
    from deequ_tpu.resilience.governance import (
        current_run_budget,
        resolve_run_policy,
        run_budget_scope,
    )

    if on_device_error not in ("fail", "fallback"):
        raise ValueError(
            f"on_device_error must be 'fail' or 'fallback', "
            f"got {on_device_error!r}"
        )
    # flight recorder (deequ_tpu/obs): an explicit trace argument scopes
    # a recorder (True = the env-armed global, else a call-scoped
    # anonymous one; False suppresses) for this whole scan, every
    # ladder attempt included — then re-enters so every seam below
    # resolves it ambiently. trace=None defers to the ambient scope /
    # the DEEQU_TPU_TRACE-armed global. Nothing here installs
    # process-wide state: one traced call must not leave later runs
    # armed.
    maybe_arm_from_env()
    if trace is not None:
        with recording_scope(resolve_recorder(trace)):
            return run_scan(
                table, ops,
                chunk_rows=chunk_rows, mesh=mesh, defer=defer,
                on_device_error=on_device_error,
                device_deadline=device_deadline, window=window,
                shard_deadline=shard_deadline,
                select_kernel=select_kernel, plan_lint=plan_lint,
                encoded_ingest=encoded_ingest,
                run_deadline=run_deadline,
                max_total_attempts=max_total_attempts,
                on_budget_exhausted=on_budget_exhausted,
            )
    budget = current_run_budget()
    if budget is None:
        run_policy = resolve_run_policy(
            run_deadline, max_total_attempts, on_budget_exhausted
        )
        if run_policy is not None:
            # arm a scan-local budget and re-enter with it ambient, so
            # every nested charge site (stream-read retries included)
            # draws on one ledger
            with run_budget_scope(run_policy.arm()):
                return run_scan(
                    table, ops, chunk_rows, mesh, defer, on_device_error,
                    device_deadline, window, shard_deadline, select_kernel,
                    plan_lint, encoded_ingest,
                )
    # resolve (and validate) the selection-kernel switch ONCE per run so
    # every bisection/reshard attempt plans against the same setting
    select_kernel = select_kernel_enabled(select_kernel)
    # same for the encoded-ingest switch; unlike select_kernel it is
    # also the ladder's DEMOTION state — an OOM mid-encoded-scan flips
    # it off for every subsequent attempt of this run
    encoded_ingest = encoded_ingest_enabled(encoded_ingest)
    # same for the plan-lint mode: every attempt of the fault ladder
    # lints (or doesn't) under one resolved setting
    plan_lint = plan_lint_mode(plan_lint)
    if mesh is None:
        mesh = current_mesh()
    if device_deadline is None:
        device_deadline = default_device_deadline()
    if shard_deadline is None:
        shard_deadline = default_shard_deadline()
    window = _resolve_scan_window(window)
    scan_id = next(_SCAN_IDS)
    from deequ_tpu.ops import scan_executors

    if scan_executors.classify(table, mesh) == "streaming":
        return scan_executors.run_streaming_scan(
            table, ops,
            chunk_rows=chunk_rows, mesh=mesh, defer=defer,
            device_deadline=device_deadline,
            shard_deadline=shard_deadline, window=window,
            select_kernel=select_kernel, plan_lint=plan_lint,
            encoded_ingest=encoded_ingest, budget=budget,
            scan_id=scan_id,
        )

    # fallback needs a CPU backend to land on; a process pinned to the
    # accelerator platform only degrades to raising the typed error
    can_fallback = (
        on_device_error == "fallback" and _cpu_fallback_device() is not None
    )

    def _mesh_size(m) -> int:
        return math.prod(m.devices.shape) if m is not None else 1

    # chips MESH_HEALTH has quarantined are excluded UP FRONT (half-open:
    # healthy_subset periodically readmits them as a probe) — a known-dead
    # mesh member must not re-fail every scan before each reshard
    mesh_exhausted = False
    if _mesh_size(mesh) > 1:
        healthy, excluded = MESH_HEALTH.healthy_subset(mesh_device_ids(mesh))
        if excluded:
            shrunk = mesh_excluding(mesh, excluded)
            if shrunk is not None:
                SCAN_STATS.record_degradation(
                    "mesh_quarantine", scan_id=scan_id,
                    excluded_devices=sorted(excluded),
                    mesh_from=_mesh_size(mesh), mesh_to=_mesh_size(shrunk),
                )
                mesh = shrunk
            else:
                # EVERY mesh member is quarantined: no accelerator subset
                # remains, the CPU fallback is the only degradation left
                mesh_exhausted = True
    # can_fallback first: should_force_fallback() advances the half-open
    # probe counter and must not run for on_device_error="fail" scans
    fallback = can_fallback and (
        DEVICE_HEALTH.should_force_fallback() or mesh_exhausted
    )
    if fallback:
        SCAN_STATS.record_degradation(
            "cpu_fallback", scan_id=scan_id,
            reason="mesh_exhausted" if mesh_exhausted
            else "unhealthy_backend",
            consecutive_faults=DEVICE_HEALTH.consecutive_faults,
        )
    # resident and sharded scans share one ladder body (the mesh rungs
    # self-gate on mesh size, so a mesh shrunk by quarantine needs no
    # second look)
    return scan_executors.run_laddered_scan(
        table, ops,
        chunk_rows=chunk_rows, mesh=mesh, defer=defer,
        on_device_error=on_device_error,
        device_deadline=device_deadline, shard_deadline=shard_deadline,
        window=window, select_kernel=select_kernel, plan_lint=plan_lint,
        encoded_ingest=encoded_ingest, budget=budget, scan_id=scan_id,
        fallback=fallback,
    )


def _run_scan_once(
    table,
    ops: Sequence[ScanOp],
    chunk_rows: Optional[int],
    mesh,
    defer: bool,
    device_deadline: Optional[float],
    scan_ctx: Dict[str, Any],
    report: Dict[str, Any],
    window: int = DEFAULT_SCAN_WINDOW,
    select_kernel: bool = True,
    plan_lint: str = "off",
    encoded: bool = True,
) -> List[Any]:
    """One attempt of the fused in-memory scan (the pre-fault-tolerance
    run_scan body, instrumented at the three device boundaries).
    ``report`` returns the chunk size actually used (and whether the
    attempt ran the encoded ingest variant) so the bisection/demotion
    driver can react."""
    with seam("plan"):
        from deequ_tpu.ops.scan_plan import plan_scan_ops
        n_rows = table.num_rows
        needed = sorted({c for op in ops for c in op.columns})
        cols = {name: table[name] for name in needed}

        n_dev = math.prod(mesh.devices.shape) if mesh is not None else 1

        # device-resident fast path: table was persist()ed with a compatible
        # mesh — stream chunks straight from HBM, no packing, no transfer
        cache = getattr(table, "_device_cache", None)
        if cache is not None and cache.packer.enc_names and not encoded:
            # encoded residency cannot serve a decoded-path attempt (the
            # A/B switch, or a fault-ladder demotion whose eviction raced a
            # concurrent re-persist): bypass it, scan from host decoded
            cache = None
        if cache is not None and not cache.mesh_matches(mesh):
            # a mesh change (degraded-mesh reshard, explicit use_mesh) strands
            # the per-device shards on devices that may no longer be in the
            # active mesh — stale residency must be FREED (and uncharged from
            # the HBM budget), not just skipped, or a dead chip keeps its
            # buffers and the budget gate overcommits the survivors
            freed = _evict_device_cache(table)
            SCAN_STATS.record_degradation(
                "stale_residency_evicted",
                scan_id=scan_ctx.get("scan_id"),
                evicted_bytes=freed,
            )
            cache = None
        if cache is not None and not cache.matches(mesh, needed):
            cache = None
        if cache is not None and chunk_rows is not None and chunk_rows != cache.chunk:
            cache = None

        if cache is not None:
            chunk = cache.chunk
            packer = cache.packer
            for name in packer.pair_names:
                if getattr(cols.get(name), "_exact_compare", False):
                    _warn_pair_compare_once(name, cols.get(name))
        else:
            chunk = chunk_rows or min(_auto_chunk_rows(cols), max(n_rows, 1))
            # static shapes: round the chunk up so it splits evenly across devices
            chunk = max(n_dev, ((chunk + n_dev - 1) // n_dev) * n_dev)
            packer = _ChunkPacker(cols, chunk, encode_ingest=encoded)
        report["chunk"] = chunk
        local_n = chunk // n_dev if mesh is not None else chunk

        # kernel-variant resolution for THIS attempt (ops/scan_plan.py):
        # resident tables route KLL/quantile summaries through the histogram
        # selection kernel; re-planned per attempt, so an OOM retry that
        # evicted residency falls back to the sort path by construction
        plan_ir = plan_scan_ops(
            ops, packer, resident=cache is not None,
            select_kernel=select_kernel, rows=chunk,
        )
        ops = plan_ir.ops
        report["encoded"] = plan_ir.ingest_variant == "encoded"
        if report["encoded"]:
            SCAN_STATS.encoded_scan_passes += 1

        # dictionary LUTs ship once (memoized device arrays) and enter the
        # jitted step as arguments; encoded columns add their dictionary's
        # decode planes the same way
        lut_arrays = _collect_luts(
            ops, {n: packer.col_dict.get(n) for n in packer.string_names}, mesh
        )
        lut_arrays.update(_collect_enc_luts(packer, mesh))
        lut_sig = _lut_sig(lut_arrays)
        baked = any(op.dictionary_baked for op in ops)

        # reuse the traced program across repeated runs: per-table cache for
        # persisted tables, plus the global cache for any program without
        # trace-baked dictionary constants (resident and streamed runs over
        # same-schema tables share one traced program)
        prog_key = _ops_prog_key(ops, chunk, lut_sig)
        dtypes = {n: c.dtype for n, c in cols.items()}
        global_key = (
            _global_prog_key(prog_key, packer, mesh) if not baked else None
        )
        cached_prog = None
        if cache is not None and prog_key is not None:
            cached_prog = cache.get_program(prog_key)
        if cached_prog is None and global_key is not None:
            cached_prog = _GLOBAL_PROGRAMS.get(global_key)

        if cached_prog is not None:
            step_fn, shapes0, raw_flat = cached_prog
            shape_fn = None
            SCAN_STATS.programs_reused += 1
        else:
            shapes0 = None
            SCAN_STATS.programs_built += 1
            # the trace closure captures a metadata-only view, never the column
            # arrays — cached programs must not pin batches in host memory
            step_fn, shape_fn, raw_flat = _build_step_fns(
                ops, packer.unpack_view(), mesh, local_n,
                tuple(sorted(lut_arrays)),
            )
    # the first call of a program built in this attempt traces and
    # compiles: that dispatch is a `build`, every later one a `dispatch`
    building = shapes0 is None

    def dispatch_seam() -> Optional[str]:
        nonlocal building
        name, building = ("build" if building else None), False
        return name

    SCAN_STATS.scan_passes += 1
    SCAN_STATS.rows_scanned += n_rows

    folder = _PartialFolder(ops, deadline=device_deadline)
    folder.shapes = shapes0
    n_chunks = (
        len(cache.device_chunks)
        if cache is not None
        else max(1, (n_rows + chunk - 1) // chunk)
    )

    # pipelined dispatch: transfers go through explicit async device_put
    # (one bulk transfer per buffer — the jit arg-conversion path can
    # fragment them) and a small window of chunks stays in flight so host
    # packing, host->device transfer, and device compute overlap.
    put = _make_put(mesh)

    in_flight = []
    # beside each result in flight, the lease on the staging planes its
    # chunk was packed into
    held: List[_StagingLease] = []
    # on-device partial fold: the per-chunk state vectors merge into ONE
    # device-resident accumulator (exact left-to-right chunk order), so
    # the whole scan fetches once — per-chunk fetches pay the link's
    # round-trip floor each.
    # A single-chunk scan is already one fetch: folding it would only add
    # a merge dispatch (a round trip on serialized links), so skip it.
    # Gather-leaf ops cap at MAX_FOLD_CAPACITY chunks (the gather region
    # scales with the chunk count — see the constant's rationale).
    tags = _state_tags(ops)
    has_gather = "gather" in tags
    collectives = len(tags) if mesh is not None else 0
    use_fold = (
        n_chunks > 1
        and (not has_gather or n_chunks <= MAX_FOLD_CAPACITY)
        # a resident table's chunks are what HBM holds of it: what its
        # scan gathers (the KLL summaries) is bounded before it starts
        # and needs no compaction on the way, so it fetches once too
        and (cache is not None or _folds_on_device(ops))
    )
    plan: Optional[_DeviceFoldPlan] = None
    acc = None
    folded = 0

    def fold_chunk(flat, ci):
        nonlocal plan, acc, folded
        if plan is None:
            plan = _fold_plan_for(ops, folder.shapes, n_chunks)
            acc = plan.fresh_init()
        acc = device_call(
            lambda: plan.merge(acc, flat),
            "execute", what=f"chunk {ci} fold", deadline=device_deadline,
        )
        folded += 1

    def after_dispatch(flat, ci, lease=_NO_LEASE) -> None:
        """Fold or queue one chunk's result, window-bounded. The oldest
        result, once known ready, hands its chunk's planes back."""
        _record_kernel_passes(plan_ir, 1)
        SCAN_STATS.mesh_collectives += collectives
        in_flight.append(flat)
        held.append(lease)
        if use_fold:
            fold_chunk(flat, ci)
            # throttle, don't drain: block on (not fetch) the oldest
            # chunk's result so pinned host buffers / queued device
            # work stay window-bounded while zero fetches happen
            if len(in_flight) >= window:
                _block_throttle(
                    in_flight.pop(0), f"chunk throttle (window at {ci})",
                    device_deadline,
                )
                held.pop(0).release()
        elif len(in_flight) >= window:
            # an OLDER result while newer chunks are in flight: the gauge
            # stays up (at a window of one it is the newest)
            oldest = in_flight.pop(0)
            folder.drain(oldest, not in_flight)
            held.pop(0).release()

    if cache is not None:
        SCAN_STATS.resident_passes += 1
        SCAN_STATS.bytes_resident += cache.nbytes
        # static plan lint BEFORE any dispatch: the resident chunks supply
        # the arg shapes
        if cache.device_chunks:
            _maybe_plan_lint(
                plan_ir, raw_flat, cache.device_chunks[0], lut_arrays,
                prog_key, packer, mesh, plan_lint,
                fallback=bool(scan_ctx.get("fallback")),
            )
        for ci, args in enumerate(cache.device_chunks):
            if folder.shapes is None:
                folder.shapes = device_call(
                    lambda: jax.eval_shape(shape_fn, *args, lut_arrays),
                    "trace", what="fused-scan trace",
                )
                if prog_key is not None:
                    cache.put_program(
                        prog_key, (step_fn, folder.shapes, raw_flat)
                    )
                if global_key is not None:
                    _GLOBAL_PROGRAMS.put(
                        global_key, (step_fn, folder.shapes, raw_flat)
                    )
            flat = device_call(
                lambda: step_fn(*args, lut_arrays),
                "execute", what=f"chunk {ci} dispatch",
                deadline=device_deadline,
                hook_ctx={**scan_ctx, "chunk_index": ci},
                seam_name=dispatch_seam(),
            )
            after_dispatch(flat, ci)
    else:
        # double-buffered host->device staging (round 8, the Eiger
        # discipline): chunk k+1's async device_put is ISSUED before
        # chunk k's dispatch, so the transfer rides the link while the
        # device computes — staged-but-undispatched chunks live in
        # `pending_stage` (depth 1: one buffer in transfer, one in
        # compute), and ScanStats.record_staged observes both the bytes
        # and whether each transfer had in-flight work to hide behind
        pending_stage: List[Tuple] = []

        def dispatch_staged(entry) -> None:
            device_args, ci, lease = entry
            flat = device_call(
                lambda: step_fn(*device_args, lut_arrays),
                "execute", what=f"chunk {ci} dispatch",
                deadline=device_deadline,
                hook_ctx={**scan_ctx, "chunk_index": ci},
                seam_name=dispatch_seam(),
            )
            after_dispatch(flat, ci, lease)

        for ci in range(n_chunks):
            start = ci * chunk
            stop = min(start + chunk, n_rows)
            lease = _StagingLease()
            with seam("pack", chunk=ci):
                args = packer.pack(start, stop, take=lease.take)
            chunk_bytes = sum(a.nbytes for a in args)
            SCAN_STATS.bytes_packed += chunk_bytes
            if ci == 0:
                # static plan lint on the first chunk's shapes, before
                # its transfer/dispatch (memoized per program identity)
                _maybe_plan_lint(
                    plan_ir, raw_flat, args, lut_arrays,
                    prog_key, packer, mesh, plan_lint,
                    fallback=bool(scan_ctx.get("fallback")),
                )
            if folder.shapes is None:
                folder.shapes = device_call(
                    lambda: jax.eval_shape(shape_fn, *args, lut_arrays),
                    "trace", what="fused-scan trace",
                )
                if global_key is not None:
                    _GLOBAL_PROGRAMS.put(
                        global_key, (step_fn, folder.shapes, raw_flat)
                    )
            # overlapped iff the PREVIOUS chunk is still staged
            # (transferred but undispatched) — true only under the
            # double-buffered ordering; a serial put-then-dispatch loop
            # always sees an empty stage here and reports 0, so the
            # observable genuinely detects a dead double buffer
            overlapped = bool(pending_stage)
            device_args = device_call(
                lambda: put(args), "transfer",
                what=f"chunk {ci} transfer", deadline=device_deadline,
                bytes=chunk_bytes, overlapped=overlapped,
            )
            SCAN_STATS.record_staged(chunk_bytes, overlapped)
            pending_stage.append((device_args, ci, lease))
            if len(pending_stage) > 1:
                dispatch_staged(pending_stage.pop(0))
        while pending_stage:
            dispatch_staged(pending_stage.pop(0))
    if use_fold and acc is not None:
        folder.fold_plan = plan
        folder.fold_filled = folded
        in_flight = [acc]
    deferred = DeferredScan(
        folder, in_flight, inline=not defer,
        scan_id=scan_ctx.get("scan_id"), leases=held,
    )
    if defer:
        return deferred
    # the drain is the blocking device round trip — the watchdog's prime
    # target (folder.drain runs its fetch through device_call: typed
    # errors, and the hang deadline on top)
    return deferred.result()


# -- micro-batched group scan (incremental pipelines) -----------------------


class DeferredGroupScan:
    """K batches' scans fused into ONE dispatch + ONE fetch (vmapped over
    a leading batch axis). ``results()`` drains once and returns one
    reduced-pytree list per table, identical to K separate run_scan calls
    (same pure per-chunk function, vmapped)."""

    def __init__(self, device_out, folders):
        self._device_out = device_out
        self._folders = folders
        self._fed_mark = fed_mark()  # the group's one dispatch
        self._results: Optional[list] = None
        self._done = False
        self._error: Optional[BaseException] = None

    def results(self) -> list:
        if not self._done:
            # same half-folded-accumulator invariant as DeferredScan /
            # fetch_deferred: mark done BEFORE draining so a mid-drain
            # failure (or Ctrl-C) can never be retried into double-folds
            self._done = True
            with seam("scan_attempt", deferred=True,
                      scans=len(self._folders)):
                try:
                    # the one round trip
                    host = device_fetch(
                        self._device_out, "group scan fetch",
                        newest=self._fed_mark,
                    )
                    SCAN_STATS.record_fetch(host.nbytes)
                    out = []
                    for k, folder in enumerate(self._folders):
                        folder.drain(host[k])
                        out.append(folder.merged)
                    self._results = out
                except BaseException as e:  # noqa: BLE001
                    self._error = e
                    if not isinstance(e, Exception):
                        raise
        if self._error is not None:
            raise self._error
        return self._results


def group_scannable(tables, ops, mesh):
    """The shared packer layout (truthy) when run_scan_group supports
    this workload, else False: single-device, EQUAL-SIZE batches whose
    NEEDED columns share one schema AND one packer layout. String
    columns are fine — their per-batch dictionary dependence rides in
    as stacked LUT ARGUMENTS (each table's LUT padded to the group-max
    pow2; gathers never touch padding, so per-batch results stay
    bit-identical) — but ops that read the dictionary at TRACE time
    (dictionary_baked, e.g. string-literal predicates) would bake the
    first table's constants and are rejected. Equal sizes keep the group
    path bit-identical to per-batch scans: padding a batch to a larger
    chunk changes the f32-pair reduction association at the ulp level,
    which the pipelined==serial contract forbids (unequal batches fall
    back to per-batch deferred scans, which are exactly the serial
    programs)."""
    if mesh is not None:
        return False
    if any(op.dictionary_baked for op in ops):
        return False
    needed = sorted({c for op in ops for c in op.columns})
    first = tables[0]
    if any(n not in first for n in needed):
        return False
    sig = [(n, first[n].dtype) for n in needed]
    n_rows = first.num_rows
    # single-chunk guard: the serial path splits bigger batches into
    # chunks and host-merges partials — a different reduction association
    # the bit-exact contract forbids (also keeps the packed stack within
    # the per-chunk memory budget)
    first_cols = {n: first[n] for n in needed}
    if n_rows > _auto_chunk_rows(first_cols):
        return False
    # identical per-batch packer layouts: a union layout would promote
    # columns (pair -> wide, i32 -> wide, mask additions) for batches the
    # serial path packs narrower, diverging at the ulp level
    layout0 = None
    for t in tables:
        if getattr(t, "is_streaming", False) or t.num_rows == 0:
            return False
        if t.num_rows != n_rows:
            return False
        if any(n not in t for n in needed):
            return False
        if [(n, t[n].dtype) for n in needed] != sig:
            return False
        layout = _ChunkPacker({n: t[n] for n in needed}, n_rows).layout()
        if layout0 is None:
            layout0 = layout
        elif layout != layout0:
            return False
    # the validated shared layout is the return value (truthy) so
    # run_scan_group consumes the SAME derivation it was admitted under
    # instead of re-deriving it
    return layout0


def run_scan_group(
    tables: Sequence[ColumnarTable],
    ops: Sequence[ScanOp],
    defer: bool = True,
    layout: Optional[dict] = None,
):
    """One fused pass over K same-schema batches: pack each into the same
    single-chunk layout, stack to (K, ...) buffers, run ONE vmapped jitted
    step, fetch ONE (K, S) result. The micro-batching behind
    IncrementalAnalysisStream: this divides the per-batch round-trip and
    per-dispatch cost by K. Caller must have checked
    group_scannable()."""
    K = len(tables)
    needed = sorted({c for op in ops for c in op.columns})
    # group_scannable() guarantees equal nonzero batch sizes — the group
    # chunk IS the (shared) batch size, exactly the serial path's chunk
    chunk = tables[0].num_rows
    if any(t.num_rows != chunk for t in tables):
        raise ValueError(
            "run_scan_group requires equal-size batches "
            "(check group_scannable() first)"
        )

    # group_scannable() has validated that every batch packs with the
    # SAME layout at the same chunk size (no union/promotion: that would
    # change the compute path vs the per-batch serial scans and break
    # bit-exactness); callers pass that validated layout through
    with seam("plan"):
        first_cols = {name: tables[0][name] for name in needed}
        if layout is None:
            layout = _ChunkPacker(first_cols, chunk).layout()
        packer = _ChunkPacker(first_cols, chunk, layout=layout)
        # grouped micro-batches are packed fresh per call (never
        # resident): the sort path's kernels, the plane route of the
        # shared layout
        from deequ_tpu.ops.scan_plan import plan_scan_ops

        plan_ir = plan_scan_ops(ops, packer, resident=False)
        ops = plan_ir.ops

    # stack per-table packed buffers along a leading K axis
    stacked = None
    with seam("pack", tables=K):
        for t in tables:
            cols = {name: t[name] for name in needed}
            p = _ChunkPacker(cols, chunk, layout=packer.layout())
            args = p.pack(0, t.num_rows)
            SCAN_STATS.bytes_packed += sum(a.nbytes for a in args)
            if stacked is None:
                stacked = [[a] for a in args]
            else:
                for lst, a in zip(stacked, args):
                    lst.append(a)
        bufs = tuple(np.stack(lst) for lst in stacked)

    with seam("plan", what="luts and program lookup"):
        # per-table dictionary LUTs stacked to (K, L_groupmax): each table's
        # LUT pads to the GROUP's max pow2 size — padding slots are never
        # gathered (codes < that table's cardinality), so per-batch results
        # stay bit-identical to the serial path's individually-padded LUTs
        lut_stacked: Dict[str, Any] = {}
        lut_specs = {}
        for op in ops:
            for col, kind, builder in op.luts:
                lut_specs.setdefault(col + "\x00" + kind, (col, kind, builder))
        if lut_specs:
            from deequ_tpu.ops.lut_cache import dictionary_lut

            for key, (col, kind, builder) in lut_specs.items():
                per_table = [
                    dictionary_lut(t[col].dictionary, kind, builder)
                    for t in tables
                ]
                target = 1
                while target < max(len(a) for a in per_table):
                    target <<= 1
                padded = []
                for a in per_table:
                    if len(a) < target:
                        out = np.zeros(target, dtype=a.dtype)
                        out[: len(a)] = a
                        a = out
                    padded.append(a)
                lut_stacked[key] = jax.device_put(np.stack(padded))
        lut_sig = tuple(
            sorted(
                (key, tuple(int(d) for d in arr.shape), str(arr.dtype))
                for key, arr in lut_stacked.items()
            )
        )

        prog_key = _ops_prog_key(ops, chunk, lut_sig)
        global_key = None
        if prog_key is not None:
            gk = _global_prog_key(prog_key, packer, None)
            if gk is not None:
                global_key = ("group", K, gk)
        cached = _GLOBAL_PROGRAMS.get(global_key) if global_key else None

        if cached is not None:
            vstep, shapes = cached
            SCAN_STATS.programs_reused += 1
        else:
            SCAN_STATS.programs_built += 1
            view = packer.unpack_view()

            def single_tree(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts):
                col_luts: Dict[str, Dict[str, Any]] = {}
                for key, arr in luts.items():
                    lcol, lkind = _split_lut_key(key)
                    col_luts.setdefault(lcol, {})[lkind] = arr
                vals = view.unpack_vals(
                    values, hi, lo, narrow_i, masks, codes, jnp, row_valid,
                    col_luts=col_luts, enc=enc,
                )
                return tuple(
                    jax.tree.map(
                        _tag_identity_wrap,
                        op.tags,
                        _scoped_update(op, vals, row_valid, chunk),
                    )
                    for op in ops
                )

            def single_flat(*args):
                leaves = jax.tree.leaves(single_tree(*args))
                return jnp.concatenate(
                    [jnp.ravel(leaf).astype(jnp.float64) for leaf in leaves]
                )

            vstep = jax.jit(jax.vmap(single_flat))
            with seam("build", what="group-scan trace"):
                shapes = jax.eval_shape(
                    single_tree,
                    *(b[0] for b in bufs),
                    {k: v[0] for k, v in lut_stacked.items()},
                )
            if global_key is not None:
                _GLOBAL_PROGRAMS.put(global_key, (vstep, shapes))

    SCAN_STATS.scan_passes += 1
    SCAN_STATS.rows_scanned += sum(t.num_rows for t in tables)

    # the numpy buffers transfer inside the call: stage and dispatch are
    # one enqueue here (a program's first call also traces and compiles)
    with seam("build" if cached is None else "dispatch", tables=K):
        device_out = vstep(*bufs, lut_stacked)
        device_fed()
    # the kernel census, once per table in the stack
    _record_kernel_passes(plan_ir, K)

    folders = []
    for _ in range(K):
        folder = _PartialFolder(ops)
        folder.shapes = shapes
        folders.append(folder)
    deferred = DeferredGroupScan(device_out, folders)
    if defer:
        return deferred
    return deferred.results()


# -- out-of-core streaming scan ---------------------------------------------


def _prefetch(iterator, depth: int = 2):
    """Run an iterator on a reader thread with a bounded queue so host
    decode (Parquet -> numpy) overlaps packing, transfer, and device
    compute. Memory stays bounded by depth x batch size. If the consumer
    abandons the generator early (scan error, interrupt), the reader is
    signalled to stop instead of blocking forever on a full queue with
    decoded batches pinned."""
    import queue
    import threading

    from deequ_tpu.resilience.governance import (
        current_run_budget,
        run_budget_scope,
    )

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    DONE = object()
    stop = threading.Event()
    # the ambient run budget is thread-local: re-install it on the
    # reader thread so the source's retry layer keeps charging THIS
    # run's ledger (stream reads are the one charge site that executes
    # over here); same for the flight recorder, so read-retry events
    # record against this run's trace
    budget = current_run_budget()
    rec = current_recorder()
    rec_parent = rec.current_span_id() if rec is not None else None

    # scope the recorder only when one is armed: an unconditional
    # recording_scope(None) would bump the global armed counter (and
    # install a suppress slot) for the stream's whole lifetime, pushing
    # every disarmed current_recorder() call in the process off the
    # one-integer fast path
    rec_scope = (
        recording_scope(rec, rec_parent) if rec is not None
        else nullcontext()
    )

    ids = seam_ids()

    def run():
        try:
            with run_budget_scope(budget), rec_scope, worker_seams(ids):
                for item in iterator:
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
            while not stop.is_set():
                try:
                    q.put(DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue
        # deequ-lint: ignore[bare-except] -- prefetch reader forwards the exception to the consumer via the queue, re-raised there
        except BaseException as e:  # noqa: BLE001 — re-raised on the consumer
            # same stop-checked retry as items: a single timed put could
            # drop the exception while the consumer is busy packing a
            # large chunk, leaving it blocked on q.get() forever
            while not stop.is_set():
                try:
                    q.put(e, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=run, daemon=True, name="deequ-tpu-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


def _layout_upgrades(layout: dict, cols: Dict[str, Column]) -> Optional[dict]:
    """Check one batch against the stream's pinned packer layout; returns
    an upgraded layout if this batch cannot use it (an int column outgrew
    i32, a fractional column outgrew the f32 pair range, a previously
    null-free column produced nulls, or an ENCODED column arrived
    without a usable dictionary encoding), else None. Upgrades are
    monotone (enc -> wide, narrow -> wide, pair -> wide, unmasked ->
    masked), so a stream retraces at most a handful of times."""
    promote = [
        n for n in layout["narrow_i32"] if n in cols and not _packs_as_i32(cols[n])
    ]
    promote += [
        n for n in layout["pair"] if n in cols and not _packs_as_pair(cols[n])
    ]
    # an encoded column whose later batch lost the encoding (the source's
    # high-cardinality fallback kicked in mid-stream, or the dictionary
    # outgrew the decode domain) leaves the code plane for wide f64 —
    # exact for any value, and enc validity folds into the mask row
    enc_demote = [
        n
        for n in layout.get("enc", ())
        if n in cols and not _enc_eligible(cols[n])
    ]
    promote_set = set(promote)
    enc_demote_set = set(enc_demote)
    masked = set(layout["masked"])
    need_mask = [
        n
        for n, c in cols.items()
        if c.dtype != DType.STRING
        and n not in masked
        and n not in set(layout.get("enc", ())) - enc_demote_set
        and not bool(c.mask.all())
    ]
    if not promote and not need_mask and not enc_demote:
        return None
    return {
        "narrow_i32": tuple(
            n for n in layout["narrow_i32"] if n not in promote_set
        ),
        "pair": tuple(n for n in layout["pair"] if n not in promote_set),
        "wide": tuple(list(layout["wide"]) + promote + enc_demote),
        "masked": tuple(list(layout["masked"]) + need_mask),
        "enc": tuple(
            n for n in layout.get("enc", ()) if n not in enc_demote_set
        ),
    }


def _empty_batch_cols(schema, needed) -> Dict[str, Column]:
    cols = {}
    for name in needed:
        f = schema[name]
        if f.dtype == DType.STRING:
            cols[name] = Column(
                name, DType.STRING,
                codes=np.empty(0, dtype=np.int32),
                dictionary=np.empty(0, dtype=object),
            )
        else:
            cols[name] = Column(name, f.dtype, values=np.empty(0))
    return cols


def _run_scan_stream(
    stream,
    ops: Sequence[ScanOp],
    chunk_rows: Optional[int],
    mesh,
    scan_id: int = -1,
    device_deadline: Optional[float] = None,
    window: int = DEFAULT_SCAN_WINDOW,
    select_kernel: bool = True,
    plan_lint: str = "off",
    encoded: bool = True,
) -> List[Any]:
    """One fused pass over a StreamingTable: batches stream off storage on
    a reader thread, pack into fixed-size chunks, and stage through a
    DOUBLE BUFFER — chunk k+1's async host->device transfer is issued
    before chunk k's dispatch, so host read, H2D transfer, and device
    compute overlap (``ScanStats.ingest_overlap_frac`` / ``bytes_staged``
    observe it) — and host memory stays bounded by a few batches
    regardless of dataset size (the TB-scale design intent of the
    reference, profiles/ColumnProfiler.scala:57-68). Batches carrying
    dictionary-encoded columns ship int16 codes instead of decoded
    values (``encoded``; docs/ingest.md).

    The packer layout is pinned on the first batch so the traced program is
    reused across every numeric batch of the stream (string columns bake
    per-batch dictionaries into the trace and retrace per batch).

    Device failures raise TYPED (exceptions.py taxonomy) but are not
    bisected/fallback-retried here: a half-consumed stream cannot be
    re-read. Streaming runs wanting per-batch device-fault recovery go
    through the runner's resilient loop (``on_device_error`` /
    ``on_batch_error`` / ``checkpoint``), which scans each batch as an
    in-memory table under the full policy.

    Run-budget audit (round 9): this function performs NO retries of its
    own — its only retry sites are the source's batch reads
    (``RetryingBatchSource``/``resilient_batches``, which charge the
    AMBIENT run budget per failed try) and, on the resilient-loop path,
    the per-batch ``run_scan`` ladders (which resolve the same ambient
    budget). Either way a stream draws on ONE ``max_total_attempts``,
    never a fresh budget per batch."""
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    # streaming chunks are never resident: the planner keeps the sort
    # path (selection only fires on resident attempts). What it reads off
    # a packer layout is resolved per batch (plan_cols below), against
    # the layout that batch is packed under
    needed = sorted({c for op in ops for c in op.columns})
    schema = stream.schema
    if not needed and len(schema.column_names):
        # row-count-only workloads (a lone Size()) prune to ZERO
        # columns, and a zero-column batch cannot carry its row count
        # (ColumnarTable([]).num_rows == 0) — the scan would silently
        # fold 0 rows. Read one column so every batch keeps its geometry.
        needed = [schema.column_names[0]]
    dtypes = {n: schema[n].dtype for n in needed}
    n_dev = math.prod(mesh.devices.shape) if mesh is not None else 1
    # chunk size = the user's batch budget when the source has one, else a
    # streaming default small enough that the several live copies per chunk
    # keep host RSS bounded
    chunk = (
        chunk_rows
        or getattr(stream, "preferred_batch_rows", None)
        or _auto_chunk_rows_from_dtypes(
            dtypes.values(), target_bytes=STREAM_CHUNK_BYTES
        )
    )
    # a small source must not pay for a full-width padded chunk: bound by
    # the metadata row count when the source knows it
    known_rows = getattr(stream.source, "num_rows", None) if hasattr(
        stream, "source"
    ) else None
    if known_rows:
        chunk = min(chunk, known_rows)
    chunk = max(n_dev, ((chunk + n_dev - 1) // n_dev) * n_dev)
    local_n = chunk // n_dev if mesh is not None else chunk
    put = _make_put(mesh)
    baked = any(op.dictionary_baked for op in ops)
    collectives = len(_state_tags(ops)) if mesh is not None else 0

    SCAN_STATS.scan_passes += 1

    folder = _PartialFolder(ops, deadline=device_deadline)
    in_flight = []
    # beside each result in flight, the lease on its chunk's staging planes
    held: List[_StagingLease] = []
    chunk_counter = [0]
    encoded_counted = [False]
    # on-device partial fold across the WHOLE stream: instead of a fetch
    # per chunk, the accumulator drains only when its fixed gather
    # capacity fills (STREAM_FOLD_CAPACITY chunks) and once at the end —
    # a TB-scale stream fetches O(chunks/capacity) times
    use_fold = _folds_on_device(ops)
    fold_state: Dict[str, Any] = {"plan": None, "acc": None, "filled": 0}
    # double-buffered staging across the whole stream (batch boundaries
    # included): each entry is a transferred-but-undispatched chunk WITH
    # the program it was packed for — a mid-stream layout upgrade must
    # dispatch the staged chunk under its own (old-layout) program
    pending_stage: List[Tuple] = []

    # programs built in this scan whose first call (trace + compile) is
    # still to come: that dispatch is a `build`
    unbuilt: set = set()

    def dispatch_staged(entry) -> None:
        fn, device_args, luts, idx, plan_ir, lease = entry
        building = id(fn) in unbuilt
        unbuilt.discard(id(fn))
        flat = device_call(
            lambda: fn(*device_args, luts),
            "execute",
            what=f"stream chunk {idx} dispatch",
            deadline=device_deadline,
            hook_ctx={
                "scan_id": scan_id, "attempt": 0, "fallback": False,
                "chunk_index": idx,
                "device_ids": mesh_device_ids(mesh),
            },
            seam_name="build" if building else None,
        )
        _record_kernel_passes(plan_ir, 1)
        SCAN_STATS.mesh_collectives += collectives
        if use_fold:
            if fold_state["plan"] is None:
                fold_state["plan"] = _fold_plan_for(
                    ops, folder.shapes, STREAM_FOLD_CAPACITY
                )
            if fold_state["acc"] is None:
                # first chunk, or a fresh accumulator after a
                # capacity drain
                fold_state["acc"] = fold_state["plan"].fresh_init()
            plan, acc = fold_state["plan"], fold_state["acc"]
            fold_state["acc"] = device_call(
                lambda: plan.merge(acc, flat),
                "execute", what="stream chunk fold",
                deadline=device_deadline,
            )
            fold_state["filled"] += 1
            in_flight.append(flat)
            held.append(lease)
            if len(in_flight) >= window:
                _block_throttle(
                    in_flight.pop(0), "stream chunk throttle",
                    device_deadline,
                )
                held.pop(0).release()
            # only gather leaves grow with the chunk count: a
            # gather-free accumulator never overflows, so it folds
            # the WHOLE stream into one final fetch (and never pays
            # the restart's f64 sum regrouping)
            if (
                fold_state["filled"] >= STREAM_FOLD_CAPACITY
                and plan.gather_size > 0
            ):
                drain_fold()
        else:
            in_flight.append(flat)
            held.append(lease)
            if len(in_flight) >= window:
                oldest = in_flight.pop(0)
                folder.drain(oldest, not in_flight)
                held.pop(0).release()

    def drain_fold() -> None:
        if fold_state["acc"] is None:
            return
        folder.fold_plan = fold_state["plan"]
        folder.fold_filled = fold_state["filled"]
        # the accumulator is the newest dispatch's (the last merge's)
        folder.drain(fold_state["acc"], True)
        # the fetched accumulator had merged every chunk dispatched so far
        # (release is idempotent: their throttle pops find nothing left)
        for lease in held:
            lease.release()
        fold_state["acc"] = None
        fold_state["filled"] = 0
    layout: Optional[dict] = None
    # the current (layout, lut signature)'s (step_fn, shapes); reset when
    # either changes (layout upgrades are sticky; LUT shapes change only
    # when a batch dictionary crosses a pow2 size bucket)
    current_prog: Optional[tuple] = None  # (sig, step_fn, shapes, raw_flat)
    # program signatures already plan-linted THIS scan: a mid-stream
    # layout upgrade rebuilds the program under a new signature and must
    # re-lint it (dictionary-baked per-batch retraces under an UNCHANGED
    # signature share one structural lint — the baked constants differ,
    # the traced contract surface does not)
    linted_sigs: set = set()

    # predicate-compiled boundary columns recorded on the stream (its
    # schema views can't carry the per-Column mark): apply to every
    # materialized batch BEFORE the layout is derived/pinned so they
    # route over the exact wide-f64 plane (expr/eval.py)
    exact_names = set(
        getattr(stream, "_exact_compare_names", ()) or ()
    ) & set(needed)

    def plan_cols(cols: Dict[str, Column]):
        """One batch's host planning: layout (pinned, upgraded when the
        batch outgrows it), LUTs and the program lookup."""
        nonlocal layout, current_prog
        for name in exact_names:
            if name in cols:
                cols[name]._exact_compare = True
        if layout is None:
            layout = _ChunkPacker(cols, chunk, encode_ingest=encoded).layout()
        else:
            upgraded = _layout_upgrades(layout, cols)
            if upgraded is not None:
                layout = upgraded
                current_prog = None
        packer = _ChunkPacker(cols, chunk, layout=layout)
        if packer.enc_names and not encoded_counted[0]:
            encoded_counted[0] = True
            SCAN_STATS.encoded_scan_passes += 1
        # what depends on the layout (the plane route, the encoded
        # declaration) is resolved against THIS batch's packer
        batch_ir = plan_scan_ops(
            ops, packer, resident=False, select_kernel=select_kernel
        )
        batch_ops = batch_ir.ops

        lut_arrays = _collect_luts(
            batch_ops, {c: packer.col_dict.get(c) for c in packer.string_names}, mesh
        )
        lut_arrays.update(_collect_enc_luts(packer, mesh))
        lut_sig = _lut_sig(lut_arrays)
        prog_key = _ops_prog_key(batch_ops, chunk, lut_sig)
        sig = (tuple(sorted(layout.items())), lut_sig)

        prog = None
        global_key = (
            _global_prog_key(prog_key, packer, mesh) if not baked else None
        )
        if global_key is not None:
            prog = _GLOBAL_PROGRAMS.get(global_key)
        if prog is None and not baked:
            if current_prog is not None and current_prog[0] == sig:
                prog = current_prog[1:]

        if prog is not None:
            step_fn, shapes, raw_flat = prog
            shape_fn = None
            SCAN_STATS.programs_reused += 1
        else:
            SCAN_STATS.programs_built += 1
            step_fn, shape_fn, raw_flat = _build_step_fns(
                batch_ops, packer.unpack_view(), mesh, local_n,
                tuple(sorted(lut_arrays)),
            )
            shapes = None
            unbuilt.add(id(step_fn))
        return packer, lut_arrays, prog_key, sig, global_key, batch_ir, (
            step_fn, shape_fn, raw_flat, shapes
        )

    def process_cols(cols: Dict[str, Column], n: int) -> None:
        nonlocal current_prog
        with seam("plan"):
            packer, lut_arrays, prog_key, sig, global_key, batch_ir, prog = (
                plan_cols(cols)
            )
        step_fn, shape_fn, raw_flat, shapes = prog
        for start in range(0, max(n, 1), chunk):
            stop = min(start + chunk, n)
            lease = _StagingLease()
            with seam("pack", chunk=chunk_counter[0]):
                args = packer.pack(start, stop, take=lease.take)
            chunk_bytes = sum(a.nbytes for a in args)
            SCAN_STATS.bytes_packed += chunk_bytes
            if sig not in linted_sigs:
                # static plan lint before this program's first
                # transfer/dispatch — runs again after a mid-stream
                # layout upgrade (new sig = new traced program). The
                # lint checks THIS signature's packer-derived plan, so
                # encoded-ingest contracts hold per program
                _maybe_plan_lint(
                    batch_ir, raw_flat, args, lut_arrays,
                    prog_key, packer, mesh, plan_lint,
                )
                linted_sigs.add(sig)
            if shapes is None:
                shapes = device_call(
                    lambda: jax.eval_shape(shape_fn, *args, lut_arrays),
                    "trace", what="fused-stream trace",
                )
                if not baked:
                    current_prog = (sig, step_fn, shapes, raw_flat)
                    if global_key is not None:
                        _GLOBAL_PROGRAMS.put(
                            global_key, (step_fn, shapes, raw_flat)
                        )
            if folder.shapes is None:
                folder.shapes = shapes
            # double-buffered staging: issue THIS chunk's async transfer
            # before the PREVIOUS chunk's dispatch, so the H2D bytes
            # move while the device computes (Eiger's staging
            # discipline); overlapped iff the previous chunk is still
            # staged-undispatched — a serial loop reports 0 (see the
            # in-memory loop's rationale comment)
            overlapped = bool(pending_stage)
            device_args = device_call(
                lambda: put(args), "transfer",
                what=f"stream chunk {chunk_counter[0]} transfer",
                deadline=device_deadline,
                bytes=chunk_bytes, overlapped=overlapped,
            )
            SCAN_STATS.record_staged(chunk_bytes, overlapped)
            pending_stage.append(
                (step_fn, device_args, lut_arrays, chunk_counter[0],
                 batch_ir, lease)
            )
            chunk_counter[0] += 1
            if len(pending_stage) > 1:
                dispatch_staged(pending_stage.pop(0))
            if stop >= n:
                break

    got_any = False
    for batch in _prefetch(stream.batches(columns=needed, batch_rows=chunk)):
        got_any = True
        SCAN_STATS.rows_scanned += batch.num_rows
        process_cols({n: batch[n] for n in needed}, batch.num_rows)

    if not got_any:
        # identity partials from one all-padding chunk
        process_cols(_empty_batch_cols(schema, needed), 0)

    # flush the staged tail: the last chunk's transfer has no successor
    # to overlap with — dispatch it now
    while pending_stage:
        dispatch_staged(pending_stage.pop(0))

    if use_fold:
        drain_fold()  # the (usually only) fetch of the whole stream scan
    else:
        for i, device_result in enumerate(in_flight, 1):
            folder.drain(device_result, i == len(in_flight))
        for lease in held:
            lease.release()
    return folder.merged
