"""Sketch-based analyzers: ApproxCountDistinct (HLL++), KLLSketch,
ApproxQuantile(s).

ApproxCountDistinct fuses into the shared scan: its partial state is the HLL
register file (elementwise-max monoid, exactly the reference's register-max
merge, StatefulHyperloglogPlus.scala:121-139), which the engine merges with
the ``max`` collective across devices.

KLLSketch and ApproxQuantile(s) are scan-shareable: the sketch is built ON
DEVICE inside the shared fused pass (per-chunk sort + deterministic strata
compaction, ops/kll_device.py) — one pass covers everything, whereas the
reference needs a separate KLL job (KLLRunner.scala:87-179).

ApproxQuantile(s): the reference uses Spark's GK percentile digest
(StatefulApproxQuantile). Here both are backed by the same KLL sketch —
one mergeable quantile state family instead of two — with the sketch size
chosen from the requested relative error. Same capability, one kernel.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from deequ_tpu.analyzers.base import (
    Analyzer,
    DoubleValuedState,
    ScanShareableAnalyzer,
    State,
    has_column,
    is_numeric,
    metric_from_failure,
    metric_from_value,
)
from deequ_tpu.data.table import ColumnarTable, DType
from deequ_tpu.exceptions import (
    EmptyStateException,
    IllegalAnalyzerParameterException,
    wrap_if_necessary,
)
from deequ_tpu.metrics import (
    BucketDistribution,
    BucketValue,
    DoubleMetric,
    Entity,
    KeyedDoubleMetric,
    KLLMetric,
)
from deequ_tpu.ops import hll as hll_ops
from deequ_tpu.ops.kll import (
    DEFAULT_SHRINKING_FACTOR,
    DEFAULT_SKETCH_SIZE,
    KLLSketchState,
)
from deequ_tpu.obs.recorder import seam
from deequ_tpu.ops.scan_engine import SCAN_STATS, ScanOp
from deequ_tpu.tryresult import Failure, Success, Try


# -- ApproxCountDistinct ----------------------------------------------------


@dataclass(frozen=True)
class ApproxCountDistinctState(DoubleValuedState):
    """HLL register file; merge = elementwise register max.

    ``hash_version`` stamps which hash suite filled the registers (2 =
    the r5 u32-native path, 1 = the u64 splitmix path of rounds 1-4).
    Registers hashed with different suites count DIFFERENT bucketings of
    the same values — merging them double-counts, so sum() refuses."""

    registers: Tuple[int, ...]
    hash_version: int = hll_ops.HASH_VERSION

    def sum(self, other: "ApproxCountDistinctState") -> "ApproxCountDistinctState":
        if len(self.registers) != len(other.registers):
            raise ValueError("cannot merge HLL states with different precision")
        if self.hash_version != other.hash_version:
            raise ValueError(
                f"cannot merge HLL registers hashed with different suites "
                f"(v{self.hash_version} vs v{other.hash_version}); recompute "
                f"the older state with this version"
            )
        return ApproxCountDistinctState(
            tuple(max(a, b) for a, b in zip(self.registers, other.registers)),
            self.hash_version,
        )

    def metric_value(self) -> float:
        return hll_ops.estimate_cardinality(np.array(self.registers))


# the hash suite of a string column's registers (host xxhash64, v1 content)
STRING_HASH_VERSION = 1


@dataclass(frozen=True)
class ApproxCountDistinct(ScanShareableAnalyzer):
    """Approximate distinct count via HLL++
    (reference analyzers/ApproxCountDistinct.scala:26-64)."""

    column: str
    where: Optional[str] = None

    metric_name = "ApproxCountDistinct"

    def preconditions(self):
        return [has_column(self.column)]

    @property
    def instance(self) -> str:
        return self.column

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        from deequ_tpu.analyzers.scan import _compile_where, _rows, _string_baked

        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column
        dtype = table[col].dtype
        p = hll_ops.precision_from_relative_sd()

        # string registers keep the v1 content (host xxhash64 + u64
        # idx/rank derivation, just gathered as a packed i32 LUT), so
        # they stay suite 1 and MERGE with pre-v4 persisted states;
        # numeric/boolean registers come from the u32 suite (2)
        hash_version = (
            STRING_HASH_VERSION if dtype == DType.STRING
            else hll_ops.HASH_VERSION
        )

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            if dtype == DType.STRING:
                # host-precomputed packed (idx, rank) per distinct value:
                # the device only gathers + unpacks with native i32 ops
                with jax.named_scope("deequ.lut.gather"):
                    packed = v.lut(f"hll_ir_p{p}")[xp.maximum(v.data, 0)]
                idx = (packed >> xp.int32(6)).astype(xp.int32)
                rank = (packed & xp.int32(0x3F)).astype(xp.int32)
                valid = rows & (v.data >= 0)
            elif dtype == DType.BOOLEAN:
                bits = v.data.astype(xp.uint32)
                idx, rank = hll_ops.idx_rank_u32(
                    bits, xp.zeros_like(bits), p, xp
                )
                valid = rows & v.mask
            elif v.lo is not None:
                # two-float pair column: the packer's planes ARE the
                # canonical split idx_rank_numeric derives, so bitcasting
                # them directly is bit-identical — and all-u32 (no
                # emulated u64 ops; r4's dominant device compute term)
                idx, rank = hll_ops.idx_rank_pair_device(v.data, v.lo, p, xp)
                valid = rows & v.mask
            else:
                idx, rank = hll_ops.idx_rank_numeric(v.data, p, xp)
                valid = rows & v.mask
            regs = hll_ops.registers_from_idx_rank(idx, rank, valid, p, xp)
            # suite id rides the result pytree (tag "max" = identity
            # across chunk/device merges) so state_from_scan_result can
            # stamp the state without re-knowing the column dtype
            return {
                "registers": regs,
                "hash_version": xp.asarray(hash_version, dtype=xp.int32),
            }

        luts = (
            (
                (
                    col,
                    f"hll_ir_p{p}",
                    lambda d, _p=p: hll_ops.string_idx_rank_lut(d, _p),
                ),
            )
            if dtype == DType.STRING
            else ()
        )
        return ScanOp(
            tuple(sorted(cols)), update,
            {"registers": "max", "hash_version": "max"},
            luts=luts,
            dictionary_baked=_string_baked(table, wcols),
            hll_folds=1,
        )

    def state_from_scan_result(self, result) -> Optional[ApproxCountDistinctState]:
        regs = np.asarray(result["registers"]).astype(np.int64)
        return ApproxCountDistinctState(
            tuple(int(r) for r in regs),
            int(np.asarray(result["hash_version"])),
        )

    def state_from_present_registers(self, registers) -> ApproxCountDistinctState:
        """The state of a string column whose registers were folded out of
        the dictionary entries present (``segment.resident_top_k``): the
        same LUT as ``scan_op`` gathers from, so the same suite's stamp."""
        return ApproxCountDistinctState(
            tuple(np.asarray(registers).tolist()), STRING_HASH_VERSION
        )

    def compute_metric_from(self, state) -> DoubleMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        return metric_from_value(
            state.metric_value(), self.metric_name, self.instance, Entity.COLUMN
        )

    def to_failure_metric(self, exception: Exception) -> DoubleMetric:
        return metric_from_failure(
            exception, self.metric_name, self.instance, Entity.COLUMN
        )


# -- KLL state shared by KLLSketch / ApproxQuantile(s) ----------------------


@dataclass
class KLLState(State):
    """KLL sketch + global min/max (reference analyzers/KLLSketch.scala:42-73)."""

    sketch: KLLSketchState
    global_min: float
    global_max: float

    def sum(self, other: "KLLState") -> "KLLState":
        return KLLState(
            self.sketch.merge(other.sketch),
            min(self.global_min, other.global_min),
            max(self.global_max, other.global_max),
        )
    # binary persistence lives in states/serde.py (_enc_kll/_dec_kll)


@dataclass(frozen=True)
class KLLParameters:
    """(reference analyzers/KLLSketch.scala:82)"""

    sketch_size: int = DEFAULT_SKETCH_SIZE
    shrinking_factor: float = DEFAULT_SHRINKING_FACTOR
    number_of_buckets: int = 100


MAXIMUM_ALLOWED_DETAIL_BINS = 100


def _sketch_partition(
    col, mask, lo: int, hi: int, sketch_size: int, shrinking_factor: float
):
    """Build one partition's sketch (the mapPartitions body,
    KLLRunner.scala:150-177 analogue). Chunked so 1B-row columns never
    materialize a full non-null copy."""
    sketch = KLLSketchState(sketch_size, shrinking_factor)
    global_min, global_max = np.inf, -np.inf
    total = 0
    chunk = 1 << 22
    for start in range(lo, hi, chunk):
        stop = min(start + chunk, hi)
        values = col.values[start:stop][mask[start:stop]].astype(np.float64)
        if len(values) == 0:
            continue
        total += len(values)
        global_min = min(global_min, float(values.min()))
        global_max = max(global_max, float(values.max()))
        sketch.update_batch(values)
    return sketch, global_min, global_max, total


def _sketch_column(
    table: ColumnarTable,
    column: str,
    sketch_size: int,
    shrinking_factor: float,
    where_mask: Optional[np.ndarray] = None,
) -> Optional[KLLState]:
    """HOST reference implementation of the partitioned KLL pass
    (mapPartitions + treeReduce analogue, KLLRunner.scala:104-112): one
    sketch per partition in a thread pool, then a pairwise tree merge.
    The production path builds sketches on device inside the fused scan
    (_kll_scan_op); this host path pins the sketch algebra in tests and
    serves as a device-free fallback.

    ``where_mask`` fuses a predicate into the pass (no filtered table
    copy is ever materialized).
    """
    from concurrent.futures import ThreadPoolExecutor

    SCAN_STATS.kll_passes += 1
    col = table[column]
    mask = col.mask if where_mask is None else (col.mask & where_mask)
    n = len(col.values)
    # partition count derives from n ONLY (not cpu_count): the partition
    # split composes with the seeded compaction randomness, so metrics must
    # not depend on the machine the sketch ran on
    workers = max(1, min(8, n // (1 << 16)))
    bounds = np.linspace(0, n, workers + 1).astype(np.int64)

    if workers == 1:
        parts = [_sketch_partition(col, mask, 0, n, sketch_size, shrinking_factor)]
    else:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            parts = list(
                pool.map(
                    lambda i: _sketch_partition(
                        col, mask, int(bounds[i]), int(bounds[i + 1]),
                        sketch_size, shrinking_factor,
                    ),
                    range(workers),
                )
            )

    parts = [p for p in parts if p[3] > 0]
    if not parts:
        return None
    # treeReduce: levelwise pairwise merges (KLLRunner.scala:104-112)
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            a, b = parts[i], parts[i + 1]
            nxt.append(
                (a[0].merge(b[0]), min(a[1], b[1]), max(a[2], b[2]), a[3] + b[3])
            )
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    sketch, global_min, global_max, _total = parts[0]
    return KLLState(sketch, global_min, global_max)



def _make_kll_compact(K: int, sketch_size: int):
    """Mid-scan host compaction for gathered KLL summaries: fold the
    accumulated weighted items into a KLLSketchState and re-emit its
    weighted items (ops/kll.py:_weighted_items) — same pytree type,
    size bounded by sketch capacity instead of O(n_chunks). Without this
    a TB-scale stream accumulates every chunk's ~(k+W)-item summary on
    host (ADVICE r3). The fold uses DEFAULT_SHRINKING_FACTOR: any valid
    KLL parameterization yields valid power-of-two weighted items for
    the final per-analyzer fold.

    K == 1: flat (L,) leaves, any output length. K > 1 (coalesced
    batched op): leaves are (n_chunks*K, T) with column j in rows
    j::K — compaction re-emits (n_blocks*K, T) preserving both the
    trailing dim (so later chunks still concatenate) and the j::K
    slicing used by _kll_multi_extract."""
    from deequ_tpu.ops.kll_device import fold_summaries

    def compact(result):
        items = np.asarray(result["items"], dtype=np.float64)
        weights = np.asarray(result["weights"], dtype=np.float64)
        if K == 1:
            sk = fold_summaries(
                items, weights, sketch_size, DEFAULT_SHRINKING_FACTOR
            )
            if sk is None:
                # all weights zero (all-null / fully-filtered column):
                # drop the padding instead of keeping the ever-growing
                # buffers (returning `result` unchanged would leak)
                it = np.empty(0)
                wt = np.empty(0)
            else:
                it, wt = sk._weighted_items()
            return {
                **result,
                "items": it.astype(np.float64),
                "weights": wt.astype(np.float64),
            }
        T = items.shape[-1]
        per_col = []
        for j in range(K):
            sk = fold_summaries(
                items[j::K].ravel(), weights[j::K].ravel(),
                sketch_size, DEFAULT_SHRINKING_FACTOR,
            )
            per_col.append(
                sk._weighted_items() if sk is not None
                else (np.empty(0), np.empty(0))
            )
        longest = max((len(it) for it, _ in per_col), default=0)
        n_blocks = max((longest + T - 1) // T, 1)
        new_items = np.zeros((n_blocks * K, T))
        new_weights = np.zeros((n_blocks * K, T))
        for j, (it, wt) in enumerate(per_col):
            flat_i = np.zeros(n_blocks * T)
            flat_w = np.zeros(n_blocks * T)
            flat_i[: len(it)] = it
            flat_w[: len(wt)] = wt
            new_items[j::K] = flat_i.reshape(n_blocks, T)
            new_weights[j::K] = flat_w.reshape(n_blocks, T)
        return {**result, "items": new_items, "weights": new_weights}

    return compact


#: reduction tags of one KLL chunk summary (ops/kll_device.py) plus the
#: ``summaries`` leaf ``_counted`` adds
_KLL_TAGS = {
    "items": "gather",
    "weights": "gather",
    "count": "sum",
    "min": "min",
    "max": "max",
    "summaries": "sum",
}


def _counted(summary: dict, xp) -> dict:
    """A chunk summary with a ``summaries`` leaf of ones, shaped like its
    ``count``: summed over chunks and shards, it tells the host fold how
    many summaries were gathered into the result it folds."""
    return {**summary, "summaries": xp.ones_like(summary["count"])}


def _kll_scan_op(
    table: ColumnarTable,
    column: str,
    sketch_size: int,
    where: Optional[str] = None,
) -> ScanOp:
    """Device KLL summary as a fused-scan op: sort the chunk, compact to
    strata midpoints + exact remainder (ops/kll_device.py), gather the
    tiny weighted summary. Quantile sketching shares the ONE compiled
    pass with every other scan-shareable analyzer — no extra pass over
    the data, unlike the reference's separate KLL job
    (KLLRunner.scala:87-179)."""
    from deequ_tpu.analyzers.scan import _compile_where, _rows, _string_baked
    from deequ_tpu.ops.kll_device import chunk_summary
    from deequ_tpu.ops.select_device import (
        MAX_SELECT_SKETCH_SIZE,
        chunk_summary_select,
    )

    pred, wcols = _compile_where(where, table)
    cols = wcols | {column}
    col = column

    def update(vals, row_valid, xp, n):
        rows = _rows(vals, row_valid, xp, n, pred)
        v = vals[col]
        valid = rows & v.mask
        return _counted(
            chunk_summary(v.data, valid, sketch_size, n, xp, lo=v.lo), xp
        )

    def update_select(vals, row_valid, xp, n):
        rows = _rows(vals, row_valid, xp, n, pred)
        v = vals[col]
        valid = rows & v.mask
        if v.lo is None:
            # planner/packer drift: the selection variant was routed to
            # a column with no u32 key plane. Raising (trace time) beats
            # silently sorting — a silent sort here would falsify the
            # device_select/sort_passes census the config-3 contract
            # asserts are built on. DEEQU_TPU_SELECT_KERNEL=0 is the
            # mitigation while the routing bug is fixed.
            raise ValueError(
                f"selection kernel routed to wide-f64 column {col!r} "
                "(no (hi, lo) key plane); planner/packer layout drift — "
                "set DEEQU_TPU_SELECT_KERNEL=0 to fall back to the sort "
                "path"
            )
        return _counted(
            chunk_summary_select(v.data, valid, sketch_size, n, xp, lo=v.lo),
            xp,
        )

    tags = dict(_KLL_TAGS)
    # where-free single-column KLL ops are coalescible into one batched
    # sort (see _kll_multi_scan_op / runner._coalesce_scan_ops)
    hint = ("kll", sketch_size, column) if where is None else None
    # huge sketches (extreme relative_error requests) keep the sort
    # path: the selection kernel's histograms are O(k*256) per column —
    # an allocation chunk bisection cannot shrink (review catch)
    selectable = sketch_size <= MAX_SELECT_SKETCH_SIZE
    return ScanOp(
        tuple(sorted(cols)), update, tags,
        dictionary_baked=_string_baked(table, wcols),
        batch_hint=hint,
        compact=_make_kll_compact(1, sketch_size),
        select_update=update_select if selectable else None,
        select_columns=(column,),
        # the selection kernel's histogram pass widths (16-bit pass 1,
        # then (R=k+2)x256-cell passes 2/3) — the keyspace input to the
        # histogram kernel-variant policy (ops/device_policy.py)
        hist_widths=(1 << 16, (sketch_size + 2) * 256 + 1),
        sorts_chunk=True,
    )


def _kll_multi_scan_op(columns: Tuple[str, ...], sketch_size: int) -> ScanOp:
    """N same-parameter KLL columns as ONE op: stack to (K, n), run one
    vmapped batched sort + strata compaction (ops/kll_device.py). The
    planner builds this from coalescible single-column ops; per-analyzer
    results are sliced back out by leading-axis stride (runner)."""
    from deequ_tpu.ops.kll_device import chunk_summary_batched
    from deequ_tpu.ops.select_device import (
        MAX_SELECT_SKETCH_SIZE,
        chunk_summary_select_batched,
    )

    def update(vals, row_valid, xp, n):
        X = xp.stack([vals[c].data for c in columns])
        M = xp.stack([vals[c].mask & row_valid for c in columns])
        if all(vals[c].lo is not None for c in columns):
            L = xp.stack([vals[c].lo for c in columns])
        else:
            # mixed pair/wide batches aren't coalesced in practice (the
            # planner groups by dtype-uniform tables), but stay correct
            X = xp.stack(
                [
                    vals[c].data
                    if vals[c].lo is None
                    else vals[c].data.astype(xp.float64)
                    + vals[c].lo.astype(xp.float64)
                    for c in columns
                ]
            )
            L = None
        return _counted(
            chunk_summary_batched(X, M, sketch_size, n, xp, lo=L), xp
        )

    def update_select(vals, row_valid, xp, n):
        wide = [c for c in columns if vals[c].lo is None]
        if wide:
            # planner/packer drift (see the single-column variant): a
            # silent sort here would falsify the kernel census the
            # config-3 zero-sort contract asserts on — fail loudly
            raise ValueError(
                f"selection kernel routed to wide-f64 column(s) {wide!r} "
                "(no (hi, lo) key plane); planner/packer layout drift — "
                "set DEEQU_TPU_SELECT_KERNEL=0 to fall back to the sort "
                "path"
            )
        X = xp.stack([vals[c].data for c in columns])
        M = xp.stack([vals[c].mask & row_valid for c in columns])
        L = xp.stack([vals[c].lo for c in columns])
        return _counted(
            chunk_summary_select_batched(X, M, sketch_size, n, xp, lo=L), xp
        )

    tags = dict(_KLL_TAGS)
    # same huge-sketch gate as the single-column op: the batched
    # selection histograms scale O(k*256) per MEMBER column
    selectable = sketch_size <= MAX_SELECT_SKETCH_SIZE
    return ScanOp(
        tuple(sorted(columns)), update, tags,
        compact=_make_kll_compact(len(columns), sketch_size),
        select_update=update_select if selectable else None,
        select_columns=tuple(columns),
        # per-member pass widths of the batched selection kernel (the
        # vmap shares one traced program, so the policy input is the
        # same single-column width set)
        hist_widths=(1 << 16, (sketch_size + 2) * 256 + 1),
        sorts_chunk=True,
    )


def _kll_multi_extract(result, j: int, K: int) -> dict:
    """Slice column j's summary out of a batched KLL result. Gathered
    leaves concatenate along the leading axis in blocks of K rows (one
    block per chunk/device), so column j occupies rows j, j+K, j+2K, ..."""
    items = np.asarray(result["items"])
    weights = np.asarray(result["weights"])
    return {
        "items": items[j::K].ravel(),
        "weights": weights[j::K].ravel(),
        "count": np.asarray(result["count"])[j],
        "min": np.asarray(result["min"])[j],
        "max": np.asarray(result["max"])[j],
        "summaries": np.asarray(result["summaries"])[j],
    }


def _kll_state_from_result(
    result, sketch_size: int, shrinking_factor: float
) -> Optional[KLLState]:
    from deequ_tpu.ops.kll_device import fold_summaries

    count = int(np.asarray(result["count"]))
    if count == 0:
        return None
    summaries = int(np.asarray(result.get("summaries", 1)))
    with seam("sketch_fold", summaries=summaries):
        sketch = fold_summaries(
            result["items"], result["weights"], sketch_size, shrinking_factor
        )
    SCAN_STATS.kll_summaries_folded += summaries
    if sketch is None:
        return None
    # the summary weights must account for every valid row (KLL compaction
    # is weight-preserving): a mismatch means the device kernel dropped
    # data — fail loudly, never return silently-undercounted quantiles
    if sketch.count != count:
        raise AssertionError(
            f"KLL summary weight total {sketch.count} != row count {count}; "
            "device chunk summary lost rows"
        )
    return KLLState(
        sketch, float(np.asarray(result["min"])), float(np.asarray(result["max"]))
    )


@dataclass(frozen=True)
class KLLSketch(ScanShareableAnalyzer):
    """KLL quantile sketch -> equi-width BucketDistribution
    (reference analyzers/KLLSketch.scala:90-176). Scan-shareable: the
    sketch is built on device inside the shared fused pass."""

    column: str
    kll_parameters: Optional[KLLParameters] = None

    @property
    def params(self) -> KLLParameters:
        return self.kll_parameters or KLLParameters()

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self):
        def param_check(schema):
            if self.params.number_of_buckets > MAXIMUM_ALLOWED_DETAIL_BINS:
                raise IllegalAnalyzerParameterException(
                    f"Cannot return KLL Sketch related values for more than "
                    f"{MAXIMUM_ALLOWED_DETAIL_BINS} values"
                )

        return [param_check, has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        return _kll_scan_op(table, self.column, self.params.sketch_size)

    def state_from_scan_result(self, result) -> Optional[KLLState]:
        p = self.params
        return _kll_state_from_result(result, p.sketch_size, p.shrinking_factor)

    def compute_metric_from(self, state: Optional[KLLState]) -> KLLMetric:
        if state is None:
            return KLLMetric(
                self.column,
                Failure(EmptyStateException(f"Empty state for analyzer {self!r}.")),
            )

        def build() -> BucketDistribution:
            sketch = state.sketch
            start, end = state.global_min, state.global_max
            nb = self.params.number_of_buckets
            buckets = []
            for i in range(nb):
                low = start + (end - start) * i / nb
                high = start + (end - start) * (i + 1) / nb
                if i == nb - 1:
                    count = sketch.rank(high) - sketch.rank_exclusive(low)
                else:
                    count = sketch.rank_exclusive(high) - sketch.rank_exclusive(low)
                buckets.append(BucketValue(low, high, count))
            parameters = (sketch.shrinking_factor, float(sketch.sketch_size))
            data = tuple(tuple(float(x) for x in buf) for buf in sketch.compactors)
            return BucketDistribution(buckets, parameters, data)

        return KLLMetric(self.column, Try.of(build))

    def to_failure_metric(self, exception: Exception) -> KLLMetric:
        return KLLMetric(self.column, Failure(wrap_if_necessary(exception)))


def _sketch_size_for_error(relative_error: float) -> int:
    """Pick a KLL k giving rank error comparable to the requested relative
    error of the reference's GK digest (eps ~ O(1/k), constant ~2.3)."""
    return max(256, int(2.3 / max(relative_error, 1e-6)))


def _validate_quantile_type(q) -> None:
    """Construction-time validation for the failure class that would
    otherwise surface as an OPAQUE trace error inside the fused kernel:
    q must be a real number and not NaN. The RANGE check lives in
    preconditions (``_validate_quantile_range``) so persisted results /
    deequ imports written under the historic closed-interval rule still
    deserialize — they fail their run with a typed metric instead of
    making the whole repository unloadable."""
    import numbers

    if not isinstance(q, numbers.Real) or isinstance(q, bool):
        raise IllegalAnalyzerParameterException(
            f"Quantile parameter must be a number, got {q!r}"
        )
    if math.isnan(float(q)):
        raise IllegalAnalyzerParameterException(
            "Quantile parameter must not be NaN"
        )


def _validate_quantile_range(q) -> None:
    """Typed up-front (precondition) validation: q strictly inside
    (0, 1) — q = 0/1 name endpoints no rank of a finite sample maps to
    one-to-one; checked before any kernel work, so the violation is a
    typed per-analyzer failure, never a crash inside the scan."""
    _validate_quantile_type(q)
    if not (0.0 < float(q) < 1.0):
        raise IllegalAnalyzerParameterException(
            "Quantile parameter must be in the open interval (0, 1), "
            f"got {q!r}"
        )


def _validate_quantiles(qs) -> Tuple[float, ...]:
    """ApproxQuantiles argument hygiene at construction: every q
    type-checked, duplicates removed (first occurrence wins, order
    preserved — the metric is keyed by str(q), so duplicates could only
    overwrite themselves with the same value). Emptiness and range are
    precondition failures, not construction errors (see
    ``_validate_quantile_type`` on why)."""
    qs = tuple(qs)
    seen = []
    for q in qs:
        _validate_quantile_type(q)
        if q not in seen:
            seen.append(q)
    return tuple(seen)


@dataclass(frozen=True)
class ApproxQuantile(ScanShareableAnalyzer):
    """Single approximate quantile (reference analyzers/ApproxQuantile.scala).
    KLL-backed (design deviation documented in the module docstring); built
    on device inside the shared fused pass. The SAME sketch path runs for
    every table residency (in-memory, persisted, streaming), so identical
    data always yields the identical metric — the reference's
    incremental==batch invariant (IncrementalAnalysisTest.scala:30-90)."""

    column: str
    quantile: float
    relative_error: float = 0.01
    where: Optional[str] = None

    def __post_init__(self):
        # the would-crash-the-trace class (non-numeric, NaN) is rejected
        # at CONSTRUCTION; the (0, 1) range rule is a precondition so
        # persisted analyzers from the historic closed-interval era
        # still deserialize (and fail typed at run time)
        _validate_quantile_type(self.quantile)

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self):
        def param_check(schema):
            _validate_quantile_range(self.quantile)
            if not (0.0 <= self.relative_error <= 1.0):
                raise IllegalAnalyzerParameterException(
                    "Relative error parameter must be in the closed interval [0, 1]"
                )

        return [param_check, has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        return _kll_scan_op(
            table, self.column,
            _sketch_size_for_error(self.relative_error), self.where,
        )

    def state_from_scan_result(self, result) -> Optional[KLLState]:
        return _kll_state_from_result(
            result,
            _sketch_size_for_error(self.relative_error),
            DEFAULT_SHRINKING_FACTOR,
        )

    def compute_metric_from(self, state: Optional[KLLState]) -> DoubleMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        value = state.sketch.quantile(self.quantile)
        return metric_from_value(value, "ApproxQuantile", self.column, Entity.COLUMN)

    def to_failure_metric(self, exception: Exception) -> DoubleMetric:
        return metric_from_failure(
            exception, "ApproxQuantile", self.column, Entity.COLUMN
        )


@dataclass(frozen=True)
class ApproxQuantiles(ScanShareableAnalyzer):
    """Many quantiles from one sketch -> KeyedDoubleMetric
    (reference analyzers/ApproxQuantiles.scala:39-101)."""

    column: str
    quantiles: Tuple[float, ...]
    relative_error: float = 0.01

    def __init__(self, column, quantiles, relative_error=0.01):
        object.__setattr__(self, "column", column)
        # type-check + dedup (order-preserving) at construction: the
        # deduped tuple is the identity, so equal analyzer specs stay
        # equal metric_map keys; range/emptiness are preconditions
        object.__setattr__(self, "quantiles", _validate_quantiles(quantiles))
        object.__setattr__(self, "relative_error", relative_error)

    @property
    def instance(self) -> str:
        return self.column

    def preconditions(self):
        def param_check(schema):
            if not self.quantiles:
                raise IllegalAnalyzerParameterException(
                    "Quantiles parameter must be a non-empty sequence"
                )
            for q in self.quantiles:
                _validate_quantile_range(q)
            if not (0.0 <= self.relative_error <= 1.0):
                raise IllegalAnalyzerParameterException(
                    "Relative error parameter must be in the closed interval [0, 1]"
                )

        return [param_check, has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        return _kll_scan_op(
            table, self.column, _sketch_size_for_error(self.relative_error)
        )

    def state_from_scan_result(self, result) -> Optional[KLLState]:
        return _kll_state_from_result(
            result,
            _sketch_size_for_error(self.relative_error),
            DEFAULT_SHRINKING_FACTOR,
        )

    def compute_metric_from(self, state: Optional[KLLState]) -> KeyedDoubleMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        values = {
            str(q): state.sketch.quantile(q) for q in self.quantiles
        }
        return KeyedDoubleMetric(
            Entity.COLUMN, "ApproxQuantiles", self.column, Success(values)
        )

    def to_failure_metric(self, exception: Exception) -> KeyedDoubleMetric:
        return KeyedDoubleMetric(
            Entity.COLUMN, "ApproxQuantiles", self.column,
            Failure(wrap_if_necessary(exception)),
        )
