"""Grouping (frequency-based) analyzers (reference §2.3 of SURVEY.md,
analyzers/GroupingAnalyzers.scala + Uniqueness/Distinctness/etc.).

All analyzers over one distinct set of grouping columns share ONE frequency
computation per analysis run (the planner guarantees this, mirroring
AnalysisRunner.scala:175-190). The frequency state is a mergeable monoid:
merging two frequency tables is a null-safe outer join adding counts
(GroupingAnalyzers.scala:127-147) — here a dictionary merge.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.analyzers.base import (
    Analyzer,
    State,
    at_least_one,
    entity_from,
    exactly_n_columns,
    has_column,
    metric_from_failure,
    metric_from_value,
)
from deequ_tpu.data.table import ColumnarTable, DType
from deequ_tpu.exceptions import (
    EmptyStateException,
    IllegalAnalyzerParameterException,
)
from deequ_tpu.metrics import (
    Distribution,
    DistributionValue,
    DoubleMetric,
    Entity,
    HistogramMetric,
)
from deequ_tpu.ops.segment import group_counts_state
from deequ_tpu.tryresult import Failure, Success


def _cell_to_python(value, is_null: bool):
    """Typed array cell -> the python object the dict API exposes."""
    if is_null:
        return None
    if isinstance(value, np.generic):
        value = value.item()
    return value


def _column_from_cells(cells: list):
    """Python group cells (one grouping column) -> (typed values, nulls).

    Chooses the narrowest homogeneous dtype (the merge factorizes these
    with vectorized np.unique, which needs typed arrays — object arrays
    would fall back to per-element python compares). Numeric mixing
    (bool/int/float) follows python-dict key semantics (True == 1,
    5 == 5.0 share a slot); strings mixed with non-strings have NO
    faithful typed representation (stringifying would silently merge 5
    with '5'), so that refuses loudly."""
    nulls = np.array([c is None for c in cells], dtype=bool)
    present = [c for c in cells if c is not None]
    if present and all(isinstance(c, bool) for c in present):
        fill = False
        dtype = np.bool_
    elif present and all(
        isinstance(c, int) and not isinstance(c, bool) for c in present
    ):
        fill = 0
        dtype = np.int64
    elif present and all(isinstance(c, (int, float)) for c in present):
        fill = 0.0
        dtype = np.float64
    elif present and not all(isinstance(c, str) for c in present):
        raise TypeError(
            "group keys mix strings with non-strings in one column; "
            "the columnar frequency state cannot represent that without "
            "silently collapsing keys like 5 and '5'"
        )
    else:
        fill = ""
        dtype = None  # np.str_, width from data
    vals = [fill if c is None else c for c in cells]
    if dtype is None:
        values = np.array([str(v) for v in vals], dtype=np.str_)
    else:
        values = np.array(vals, dtype=dtype)
    return values, nulls


# single NaN object shared by every canonicalized NaN key: dict lookup
# succeeds via the identity fast path even though nan != nan
_CANONICAL_NAN = float("nan")


def _is_spilled(state) -> bool:
    """Disk-backed frequency state (spill engine)? Lazy import: grouping
    is imported by spill.store for FrequenciesAndNumRows."""
    from deequ_tpu.spill.store import SpilledFrequencies

    return isinstance(state, SpilledFrequencies)


class FrequenciesAndNumRows(State):
    """Group frequencies + total row count (at least one grouping column
    non-null). Merge = add counts across the union of groups.

    COLUMNAR representation (round 4): one typed numpy array + null mask
    per grouping column, plus an int64 counts vector — the merge, the
    count-distribution metrics, MutualInformation, and serde are all
    vectorized array ops, so a 100M-distinct grouping (BASELINE config 4)
    never materializes python objects per group. The dict-shaped API
    (``from_dict``/``as_dict``/``frequencies``) remains as a compatibility
    boundary for tests and small states.
    """

    def __init__(
        self,
        columns: Sequence[str],
        key_values: Tuple[np.ndarray, ...],
        key_nulls: Tuple[np.ndarray, ...],
        counts: np.ndarray,
        num_rows: int,
    ):
        self.columns = tuple(columns)
        self.key_values = tuple(np.asarray(v) for v in key_values)
        self.key_nulls = tuple(
            np.asarray(m, dtype=bool) for m in key_nulls
        )
        self.counts = np.asarray(counts, dtype=np.int64)
        self.num_rows = int(num_rows)

    # -- compatibility boundary (python dict of group tuples) ---------------

    @staticmethod
    def from_dict(
        columns: Sequence[str], frequencies: Dict[tuple, int], num_rows: int
    ) -> "FrequenciesAndNumRows":
        # distinct float('nan') objects are distinct dict keys; the
        # columnar path collapses NaN keys into one group (np.unique
        # equal_nan), so canonicalize here for one shared semantics
        canon: Dict[tuple, int] = {}
        for g, c in frequencies.items():
            key = tuple(
                _CANONICAL_NAN
                if isinstance(x, float) and x != x
                else x
                for x in g
            )
            canon[key] = canon.get(key, 0) + c
        items = sorted(canon.items(), key=lambda kv: repr(kv[0]))
        n_cols = len(tuple(columns))
        key_values = []
        key_nulls = []
        for i in range(n_cols):
            values, nulls = _column_from_cells([g[i] for g, _ in items])
            key_values.append(values)
            key_nulls.append(nulls)
        counts = np.array([c for _, c in items], dtype=np.int64)
        return FrequenciesAndNumRows(
            tuple(columns), tuple(key_values), tuple(key_nulls), counts,
            num_rows,
        )

    @property
    def frequencies(self) -> Tuple[Tuple[tuple, int], ...]:
        """Materialized ((cell, ...), count) items — compatibility accessor;
        O(#groups) python objects, avoid on hot paths."""
        groups = []
        cols = [v.tolist() for v in self.key_values]
        nulls = [m.tolist() for m in self.key_nulls]
        counts = self.counts.tolist()
        for g in range(len(counts)):
            key = tuple(
                None if nulls[i][g] else cols[i][g]
                for i in range(len(cols))
            )
            groups.append((key, counts[g]))
        return tuple(groups)

    def as_dict(self) -> Dict[tuple, int]:
        return dict(self.frequencies)

    # -- vectorized core ----------------------------------------------------

    def _code_columns(self, arrays=None, nulls=None):
        """Factorize each key column -> dense int codes (0 = null)."""
        arrays = self.key_values if arrays is None else arrays
        nulls = self.key_nulls if nulls is None else nulls
        codes = []
        for v, nl in zip(arrays, nulls):
            if v.dtype.kind == "f":
                # pin NaN-collapse semantics explicitly (numpy default
                # since 1.24): one NaN group, matching the device path
                _, inv = np.unique(v, return_inverse=True, equal_nan=True)
            else:
                _, inv = np.unique(v, return_inverse=True)
            codes.append(np.where(nl, 0, inv.reshape(v.shape) + 1))
        return codes

    def sum(self, other: "FrequenciesAndNumRows") -> "FrequenciesAndNumRows":
        if _is_spilled(other):
            # the monoid is commutative and SpilledFrequencies.sum handles
            # both directions — delegate instead of touching key arrays a
            # disk-backed state does not materialize
            return other.sum(self)
        if self.columns != other.columns:
            raise ValueError(
                f"cannot merge frequency states over different columns: "
                f"{self.columns} vs {other.columns}"
            )
        cat_vals = []
        cat_nulls = []
        _NUMERIC = set("iufb")
        for (a, an), (b, bn) in zip(
            zip(self.key_values, self.key_nulls),
            zip(other.key_values, other.key_nulls),
        ):
            ka, kb = a.dtype.kind, b.dtype.kind
            if ka != kb and not (ka in _NUMERIC and kb in _NUMERIC):
                # mismatched key kinds across states: legitimate only when
                # one side's column is entirely null (e.g. a legacy
                # from_dict state of all-None cells defaults to a string
                # dtype) — adopt the typed side. A genuine string-vs-
                # numeric merge would silently stringify keys via
                # promote_types, so refuse it loudly instead.
                if bool(an.all()):
                    a = np.zeros(len(a), dtype=b.dtype)
                elif bool(bn.all()):
                    b = np.zeros(len(b), dtype=a.dtype)
                else:
                    raise ValueError(
                        f"cannot merge frequency states with mismatched "
                        f"group-key types ({a.dtype} vs {b.dtype}) for "
                        f"columns {self.columns}"
                    )
                ka, kb = a.dtype.kind, b.dtype.kind  # adoption changed one
            # promote dtypes (e.g. two unicode widths, int64 vs float64 —
            # numeric promotion matches dict semantics, where 5 and 5.0
            # hash to the same key). integer -> float64 is only faithful
            # below 2^53; beyond that distinct keys would silently collapse.
            # Fire whenever the PROMOTED dtype is float (covers uint64 vs
            # int64, which numpy promotes to float64 too); compare min/max
            # directly — np.abs(int64 min) wraps negative.
            common = np.promote_types(a.dtype, b.dtype)
            for arr in (a, b):
                if arr.dtype.kind in "iu" and common.kind == "f" and len(
                    arr
                ) and (
                    int(arr.max()) > 2 ** 53 or int(arr.min()) < -(2 ** 53)
                ):
                    raise ValueError(
                        "cannot merge integer group keys above 2^53 into a "
                        "float64-promoted key space: promotion would "
                        "collapse distinct keys"
                    )
            cat_vals.append(
                np.concatenate([a.astype(common), b.astype(common)])
            )
            cat_nulls.append(np.concatenate([an, bn]))
        cat_counts = np.concatenate([self.counts, other.counts])
        if len(cat_counts) == 0:
            return FrequenciesAndNumRows(
                self.columns, tuple(cat_vals), tuple(cat_nulls), cat_counts,
                self.num_rows + other.num_rows,
            )
        code_cols = self._code_columns(cat_vals, cat_nulls)
        order = np.lexsort(tuple(reversed(code_cols)))
        mat = np.stack(code_cols)[:, order]
        boundary = np.any(mat[:, 1:] != mat[:, :-1], axis=0)
        starts = np.concatenate([[0], np.nonzero(boundary)[0] + 1])
        merged_counts = np.add.reduceat(cat_counts[order], starts)
        sel = order[starts]
        return FrequenciesAndNumRows(
            self.columns,
            tuple(v[sel] for v in cat_vals),
            tuple(nl[sel] for nl in cat_nulls),
            merged_counts.astype(np.int64),
            self.num_rows + other.num_rows,
        )

    def __eq__(self, other) -> bool:
        if not isinstance(other, FrequenciesAndNumRows):
            return NotImplemented
        return (
            self.columns == other.columns
            and self.num_rows == other.num_rows
            and self.as_dict() == other.as_dict()
        )

    __hash__ = None  # mutable ndarray payload; never used as a dict key

    def __repr__(self) -> str:
        return (
            f"FrequenciesAndNumRows(columns={self.columns}, "
            f"num_groups={self.num_groups}, num_rows={self.num_rows})"
        )

    @property
    def num_groups(self) -> int:
        return len(self.counts)

    def counts_array(self) -> np.ndarray:
        return self.counts


class FrequencyBasedAnalyzer(Analyzer):
    """Base class for analyzers operating on group frequencies."""

    @property
    def group_columns(self) -> List[str]:
        raise NotImplementedError

    @property
    def instance(self) -> str:
        return ",".join(self.group_columns)

    @property
    def entity(self) -> Entity:
        return entity_from(self.group_columns)

    def preconditions(self):
        cols = self.group_columns
        return [at_least_one(cols)] + [has_column(c) for c in cols]

    def compute_state_from(self, table: ColumnarTable) -> Optional[FrequenciesAndNumRows]:
        return group_counts_state(table, self.group_columns)

    def compute_state_from_stream(self, stream):
        """Per-batch frequency fold with optional disk spilling: when the
        stream carries a group memory budget
        (``StreamingTable.with_group_memory_budget``), per-batch states
        emit as canonical sorted deltas and fold into a
        ``SpillingFrequencyStore`` — host RSS stays bounded by
        max(budget, one batch's delta) no matter how many distinct groups
        the stream holds."""
        from deequ_tpu.analyzers.base import StreamStateFolder
        from deequ_tpu.spill import SpillingFrequencyStore, resolve_group_budget

        budget = resolve_group_budget(stream)
        store = (
            SpillingFrequencyStore(tuple(self.group_columns), budget)
            if budget is not None
            else None
        )
        folder = StreamStateFolder(
            spill_store=store, assume_canonical=store is not None
        )
        for batch in stream.batches(columns=self._stream_columns()):
            folder.add(self._batch_state(batch, canonicalize=store is not None))
        return folder.result()

    def _batch_state(self, batch: ColumnarTable, canonicalize: bool = False):
        return group_counts_state(
            batch, self.group_columns, canonicalize=canonicalize
        )

    def _stream_columns(self):
        return list(self.group_columns)


class ScanShareableFrequencyBasedAnalyzer(FrequencyBasedAnalyzer):
    """Computes one double from the shared frequency table
    (reference GroupingAnalyzers.scala:83-120).

    All concrete subclasses are functions of the COUNT distribution only,
    so when no state persistence is requested the planner computes them
    from device-side count aggregates (ops/segment.py:CountStats) without
    ever materializing the frequency table on host — the difference
    between O(#groups) python decode and a handful of scalars for
    high-cardinality groupings."""

    metric_name: str = ""

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        raise NotImplementedError

    def compute_from_count_stats(self, stats) -> float:
        raise NotImplementedError

    def metric_from_count_stats(self, stats) -> DoubleMetric:
        try:
            value = self.compute_from_count_stats(stats)
        except Exception as e:  # noqa: BLE001
            return self.to_failure_metric(e)
        return metric_from_value(value, self.metric_name, self.instance, self.entity)

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> DoubleMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        if _is_spilled(state):
            # disk-backed state: concrete subclasses are functions of the
            # count distribution, which streams off the merged runs as
            # cached CountStats (ONE disk pass shared by all analyzers of
            # the grouping) — the full frequency table never materializes.
            # Gated on an explicit override, same as the runner's
            # count-stats fast path: a subclass that only implements
            # compute_from_frequencies gets the materialized table instead
            # of a swallowed NotImplementedError
            if (
                type(self).compute_from_count_stats
                is not ScanShareableFrequencyBasedAnalyzer.compute_from_count_stats
            ):
                return self.metric_from_count_stats(state.count_stats())
            state = state.to_frequencies()
        try:
            value = self.compute_from_frequencies(state)
        except Exception as e:  # noqa: BLE001
            return self.to_failure_metric(e)
        return metric_from_value(value, self.metric_name, self.instance, self.entity)

    def to_failure_metric(self, exception: Exception) -> DoubleMetric:
        return metric_from_failure(
            exception, self.metric_name, self.instance, self.entity
        )


@dataclass(frozen=True)
class Uniqueness(ScanShareableFrequencyBasedAnalyzer):
    """Fraction of groups occurring exactly once over all rows
    (reference analyzers/Uniqueness.scala:26-38)."""

    columns: Tuple[str, ...]

    metric_name = "Uniqueness"

    def __init__(self, columns):
        object.__setattr__(
            self, "columns",
            (columns,) if isinstance(columns, str) else tuple(columns),
        )

    @property
    def group_columns(self) -> List[str]:
        return list(self.columns)

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        counts = state.counts_array()
        if state.num_rows == 0:
            return float("nan")
        return float((counts == 1).sum() / state.num_rows)

    def compute_from_count_stats(self, stats) -> float:
        if stats.num_rows == 0:
            return float("nan")
        return stats.singletons / stats.num_rows


@dataclass(frozen=True)
class UniqueValueRatio(ScanShareableFrequencyBasedAnalyzer):
    """(#groups with count 1) / (#distinct groups)
    (reference analyzers/UniqueValueRatio.scala:25-44)."""

    columns: Tuple[str, ...]

    metric_name = "UniqueValueRatio"

    def __init__(self, columns):
        object.__setattr__(
            self, "columns",
            (columns,) if isinstance(columns, str) else tuple(columns),
        )

    @property
    def group_columns(self) -> List[str]:
        return list(self.columns)

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        counts = state.counts_array()
        if len(counts) == 0:
            return float("nan")
        return float((counts == 1).sum() / len(counts))

    def compute_from_count_stats(self, stats) -> float:
        if stats.num_groups == 0:
            return float("nan")
        return stats.singletons / stats.num_groups


@dataclass(frozen=True)
class Distinctness(ScanShareableFrequencyBasedAnalyzer):
    """#distinct groups / #rows (reference analyzers/Distinctness.scala:29-41)."""

    columns: Tuple[str, ...]

    metric_name = "Distinctness"

    def __init__(self, columns):
        object.__setattr__(
            self, "columns",
            (columns,) if isinstance(columns, str) else tuple(columns),
        )

    @property
    def group_columns(self) -> List[str]:
        return list(self.columns)

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        if state.num_rows == 0:
            return float("nan")
        return float(state.num_groups / state.num_rows)

    def compute_from_count_stats(self, stats) -> float:
        if stats.num_rows == 0:
            return float("nan")
        return stats.num_groups / stats.num_rows


@dataclass(frozen=True)
class CountDistinct(ScanShareableFrequencyBasedAnalyzer):
    """Exact number of distinct groups (reference analyzers/CountDistinct.scala)."""

    columns: Tuple[str, ...]

    metric_name = "CountDistinct"

    def __init__(self, columns):
        object.__setattr__(
            self, "columns",
            (columns,) if isinstance(columns, str) else tuple(columns),
        )

    @property
    def group_columns(self) -> List[str]:
        return list(self.columns)

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        return float(state.num_groups)

    def compute_from_count_stats(self, stats) -> float:
        return float(stats.num_groups)


@dataclass(frozen=True)
class Entropy(ScanShareableFrequencyBasedAnalyzer):
    """Shannon entropy over the group distribution
    (reference analyzers/Entropy.scala:28-42)."""

    column: str

    metric_name = "Entropy"

    @property
    def group_columns(self) -> List[str]:
        return [self.column]

    def compute_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        n = state.num_rows
        if n == 0:
            return float("nan")
        counts = state.counts_array().astype(np.float64)
        p = counts / n
        nonzero = p > 0
        return float(-(p[nonzero] * np.log(p[nonzero])).sum())

    def compute_from_count_stats(self, stats) -> float:
        if stats.num_rows == 0:
            return float("nan")
        return stats.entropy


@dataclass(frozen=True)
class MutualInformation(FrequencyBasedAnalyzer):
    """Mutual information of two columns from the joint frequency table
    (reference analyzers/MutualInformation.scala:35-103). Groups where either
    column is null drop out (the reference's equality joins skip null keys)."""

    columns: Tuple[str, str]

    def __init__(self, column_a, column_b=None):
        if column_b is None:
            cols = tuple(column_a)
        else:
            cols = (column_a, column_b)
        object.__setattr__(self, "columns", cols)

    @property
    def group_columns(self) -> List[str]:
        return list(self.columns)

    def preconditions(self):
        return [exactly_n_columns(self.columns, 2)] + super().preconditions()

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> DoubleMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        total = state.num_rows
        if total == 0:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        if _is_spilled(state):
            try:
                mi = self._mi_from_blocks(state)
            except Exception as e:  # noqa: BLE001
                return self.to_failure_metric(e)
            return metric_from_value(
                mi, "MutualInformation", self.instance, Entity.MULTICOLUMN
            )
        # vectorized over the columnar joint table: factorize each key
        # column to dense codes, marginals via bincount, one fused log
        # expression — no per-group python objects, so MI over millions of
        # distinct pairs stays in array ops (reference computes this with
        # two aggregation+join jobs, MutualInformation.scala:35-103)
        code_a, code_b = state._code_columns()
        counts = state.counts.astype(np.float64)
        marginal_a = np.bincount(code_a, weights=counts)
        marginal_b = np.bincount(code_b, weights=counts)
        valid = (code_a > 0) & (code_b > 0)
        c = counts[valid]
        px = marginal_a[code_a[valid]] / total
        py = marginal_b[code_b[valid]] / total
        pxy = c / total
        mi = float(np.sum(pxy * np.log(pxy / (px * py))))
        return metric_from_value(mi, "MutualInformation", self.instance, Entity.MULTICOLUMN)

    @staticmethod
    def _mi_from_blocks(state) -> float:
        """MI over a spilled joint table in two streaming passes: pass 1
        accumulates the per-column marginals (dict of distinct value ->
        count — memory O(|A| + |B|), the joint's G never materializes),
        pass 2 folds the pxy*log(pxy/(px*py)) terms per block. Float sums
        associate blockwise, so values match the in-RAM path to ulp-level
        (the same caveat any distributed fold carries)."""
        total = state.num_rows
        marginals: List[Dict[object, int]] = [{}, {}]
        for kv, kn, counts in state.blocks():
            for side in (0, 1):
                valid = ~kn[side]
                if not valid.any():
                    continue
                vals = kv[side][valid]
                if vals.dtype.kind == "f":
                    uniq, inv = np.unique(
                        vals, return_inverse=True, equal_nan=True
                    )
                else:
                    uniq, inv = np.unique(vals, return_inverse=True)
                sums = np.bincount(
                    inv.reshape(-1), weights=counts[valid].astype(np.float64)
                )
                m = marginals[side]
                for v, c in zip(uniq.tolist(), sums.tolist()):
                    if isinstance(v, float) and v != v:
                        v = _CANONICAL_NAN  # nan != nan breaks dict keys
                    m[v] = m.get(v, 0) + int(c)
        mi = 0.0
        for kv, kn, counts in state.blocks():
            valid = ~(kn[0] | kn[1])
            if not valid.any():
                continue
            a_cells = [
                _CANONICAL_NAN if isinstance(v, float) and v != v else v
                for v in kv[0][valid].tolist()
            ]
            b_cells = [
                _CANONICAL_NAN if isinstance(v, float) and v != v else v
                for v in kv[1][valid].tolist()
            ]
            px = np.array([marginals[0][v] for v in a_cells], np.float64) / total
            py = np.array([marginals[1][v] for v in b_cells], np.float64) / total
            pxy = counts[valid].astype(np.float64) / total
            mi += float(np.sum(pxy * np.log(pxy / (px * py))))
        return mi

    def to_failure_metric(self, exception: Exception) -> DoubleMetric:
        return metric_from_failure(
            exception, "MutualInformation", self.instance, Entity.MULTICOLUMN
        )


MAXIMUM_ALLOWED_DETAIL_BINS = 1000
NULL_FIELD_REPLACEMENT = "NullValue"


def _stringify(value) -> str:
    """Render a group value the way the reference's string cast does."""
    if value is None:
        return NULL_FIELD_REPLACEMENT
    if isinstance(value, bool):
        return "true" if value else "false"
    if isinstance(value, float) and value.is_integer():
        return f"{value:.1f}"
    return str(value)


def _stringify_arrays(values: np.ndarray, nulls: np.ndarray) -> np.ndarray:
    """Vectorized ``_stringify`` over one typed key column (nulls ->
    'NullValue'); must agree cell-for-cell with the scalar version."""
    if values.dtype.kind in ("U", "S", "O"):
        s = values.astype(np.str_)
    elif values.dtype == np.bool_:
        s = np.where(values, "true", "false")
    elif values.dtype.kind in "iu":
        s = values.astype(np.str_)
    else:
        with np.errstate(invalid="ignore"):
            is_int = np.isfinite(values) & (values == np.floor(values))
        s = np.where(
            is_int, np.char.mod("%.1f", np.where(is_int, values, 0.0)),
            values.astype(np.str_),
        )
    return np.where(nulls, NULL_FIELD_REPLACEMENT, s)


@dataclass(frozen=True)
class Histogram(FrequencyBasedAnalyzer):
    """Full value histogram with optional binning function and top-N detail
    (reference analyzers/Histogram.scala:41-117). Unlike the other grouping
    analyzers this runs its own pass (nulls become 'NullValue' and num_rows
    counts ALL rows)."""

    column: str
    binning_udf: Optional[Callable] = None
    max_detail_bins: int = MAXIMUM_ALLOWED_DETAIL_BINS

    @property
    def group_columns(self) -> List[str]:
        return [self.column]

    def preconditions(self):
        def param_check(schema):
            if self.max_detail_bins > MAXIMUM_ALLOWED_DETAIL_BINS:
                raise IllegalAnalyzerParameterException(
                    f"Cannot return histogram values for more than "
                    f"{MAXIMUM_ALLOWED_DETAIL_BINS} values"
                )

        return [param_check, has_column(self.column)]

    def _binned_column(self, col):
        """Apply the binning UDF once per DISTINCT value (O(cardinality)
        host work, like every other per-distinct string op) and remap the
        row codes — not once per row as the reference's UDF does
        (Histogram.scala:41-117). Bin labels are stringified immediately:
        the metric stringifies groups anyway, so grouping by the
        stringified label yields the identical Distribution."""
        from deequ_tpu.data.table import Column
        from deequ_tpu.ops.segment import column_key_codes

        codes, distinct = column_key_codes(col)  # 0 = null
        # the UDF runs only on values some valid row actually references —
        # string dictionaries may hold placeholder entries (e.g. "" for
        # null slots) the reference's per-row UDF would never see
        referenced = np.zeros(len(distinct), dtype=bool)
        valid_codes = codes[codes > 0] - 1
        referenced[valid_codes] = True
        labels = np.array(
            [
                _stringify(self.binning_udf(v)) if referenced[i] else ""
                for i, v in enumerate(distinct)
            ],
            dtype=object,
        )
        if len(labels):
            uniq, inv = np.unique(labels.astype(str), return_inverse=True)
        else:
            uniq, inv = np.array([], dtype=object), np.array([], dtype=np.int64)
        new_codes = np.where(
            codes > 0,
            inv[np.maximum(codes - 1, 0)] if len(inv) else 0,
            -1,
        ).astype(np.int32)
        return Column(
            col.name, DType.STRING, codes=new_codes,
            dictionary=uniq.astype(object),
        )

    def compute_state_from(self, table: ColumnarTable) -> Optional[FrequenciesAndNumRows]:
        total_count = table.num_rows
        col = table[self.column]
        if self.binning_udf is not None:
            binned_table = ColumnarTable([self._binned_column(col)])
            raw = group_counts_state(
                binned_table, [self.column], require_any_non_null=False
            )
        else:
            raw = group_counts_state(
                table, [self.column], require_any_non_null=False
            )
        # stringify group values, nulls -> NullValue (Histogram.scala:
        # 108-111), merging label collisions (1 vs "1") — all vectorized
        labels = _stringify_arrays(raw.key_values[0], raw.key_nulls[0])
        if len(labels):
            uniq, inv = np.unique(labels, return_inverse=True)
            counts = np.bincount(
                inv.reshape(-1), weights=raw.counts
            ).astype(np.int64)
        else:
            uniq = np.empty(0, dtype=np.str_)
            counts = np.zeros(0, dtype=np.int64)
        return FrequenciesAndNumRows(
            (self.column,), (uniq,), (np.zeros(len(uniq), dtype=bool),),
            counts, total_count,
        )

    def _batch_state(self, batch, canonicalize: bool = False):
        # Histogram's own state builder (stringified labels, all-rows
        # num_rows) already emits np.unique-sorted keys — canonical order
        # for free, so spilling folds it without a re-sort
        return self.compute_state_from(batch)

    def takes_top_k_path(self, table, aggregate_with, save_states_with) -> bool:
        """The device top-N fast path: when nobody needs the mergeable
        frequency state and there is no binning UDF, counts are ranked ON
        DEVICE and only max_detail_bins (code, count) pairs are
        fetched/decoded — the engine-side top() of the reference
        (Histogram.scala:97-103). A high-cardinality column never
        materializes its groups on host."""
        return (
            aggregate_with is None
            and save_states_with is None
            and self.binning_udf is None
            and not getattr(table, "is_streaming", False)
        )

    def metric_from_top_k(self, stats) -> HistogramMetric:
        """The metric of a ``segment.TopKCounts``.

        Tie semantics: count ties at the truncation boundary break by
        device rank order here (the reference's own top() is equally
        tie-unstable, Histogram.scala:97-103), while the state path
        breaks them deterministically by stringified key
        (compute_metric_from). An r5 attempt to unify them by falling
        back to the state path on a boundary tie was REVERTED:
        high-cardinality columns (BASELINE config 4) are essentially
        always tied at the boundary, and the fallback turned the
        O(k)-fetch fast path into an O(G) group materialization — a
        measured 10x regression."""
        top = stats.top

        def build_fast() -> Distribution:
            # merge stringified collisions (e.g. 1 vs "1" -> "1") the
            # same way the full path does
            merged: Dict[str, int] = {}
            for value, count in top:
                key = _stringify(value)
                merged[key] = merged.get(key, 0) + count
            details = {
                key: DistributionValue(count, count / stats.num_rows)
                for key, count in merged.items()
            }
            return Distribution(details, number_of_bins=stats.num_groups)

        from deequ_tpu.tryresult import Try

        return HistogramMetric(self.column, Try.of(build_fast))

    def calculate(self, table, aggregate_with=None, save_states_with=None):
        if self.takes_top_k_path(table, aggregate_with, save_states_with):
            from deequ_tpu.analyzers.base import find_first_failing
            from deequ_tpu.ops.segment import group_top_k

            failing = find_first_failing(table.schema, self.preconditions())
            if failing is not None:
                return self.to_failure_metric(failing)
            try:
                stats = group_top_k(table, self.column, self.max_detail_bins)
            except Exception as e:  # noqa: BLE001
                from deequ_tpu.exceptions import wrap_if_necessary

                return self.to_failure_metric(wrap_if_necessary(e))
            return self.metric_from_top_k(stats)
        return super().calculate(table, aggregate_with, save_states_with)

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> HistogramMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        if _is_spilled(state):
            return self._metric_from_blocks(state)

        def build() -> Distribution:
            # top-N by count via argsort over the counts VECTOR; only the
            # selected bins decode to python objects
            counts = state.counts
            k = min(self.max_detail_bins, len(counts))
            order = np.argsort(-counts, kind="stable")
            values = state.key_values[0]
            nulls = state.key_nulls[0]
            if k < len(order) and counts[order[k]] == counts[order[k - 1]]:
                # count ties straddle the truncation boundary: break them
                # by stringified key so the selected bin set is stable
                # across engine paths/versions (repository comparability);
                # only the tied groups pay the python stringification
                c_thr = counts[order[k - 1]]
                above = order[counts[order] > c_thr]
                ties = sorted(
                    order[counts[order] == c_thr].tolist(),
                    key=lambda g: str(
                        _cell_to_python(values[g], bool(nulls[g]))
                    ),
                )
                order = np.concatenate(
                    [above, np.asarray(ties[: k - len(above)], dtype=order.dtype)]
                )
            else:
                order = order[:k]
            details = {}
            for g in order.tolist():
                cell = _cell_to_python(values[g], bool(nulls[g]))
                details[cell] = DistributionValue(
                    int(counts[g]), int(counts[g]) / state.num_rows
                )
            return Distribution(details, number_of_bins=state.num_groups)

        from deequ_tpu.tryresult import Try

        return HistogramMetric(self.column, Try.of(build))

    def _metric_from_blocks(self, state) -> HistogramMetric:
        """Top-N over a spilled state's streamed blocks. Streaming
        truncation under the total order (count desc, stringified key asc)
        is exact — top-N of a union is the top-N of the candidates' union —
        and selects the SAME bin set as the in-RAM path (which takes all
        groups above the boundary count and breaks boundary ties by
        stringified key), so the resulting Distribution is identical."""

        def build() -> Distribution:
            k = self.max_detail_bins
            best = None  # (counts, strkeys, values, nulls), size <= k
            total_bins = 0
            for kv, kn, counts in state.blocks():
                total_bins += len(counts)
                # the same str(cell) order the in-RAM boundary tie-break
                # uses (np's dragon4 float repr matches python str)
                strk = np.where(kn[0], "None", kv[0].astype(np.str_))
                cand = (counts, strk, kv[0], kn[0])
                if best is not None:
                    cand = tuple(
                        np.concatenate([b, c]) for b, c in zip(best, cand)
                    )
                # np.lexsort: LAST key is primary -> count desc, key asc
                order = np.lexsort((cand[1], -cand[0]))[:k]
                best = tuple(a[order] for a in cand)
            details = {}
            if best is not None:
                counts, _strk, values, nulls = best
                for g in range(len(counts)):
                    cell = _cell_to_python(values[g], bool(nulls[g]))
                    details[cell] = DistributionValue(
                        int(counts[g]), int(counts[g]) / state.num_rows
                    )
            return Distribution(details, number_of_bins=total_bins)

        from deequ_tpu.tryresult import Try

        return HistogramMetric(self.column, Try.of(build))

    def to_failure_metric(self, exception: Exception) -> HistogramMetric:
        from deequ_tpu.exceptions import wrap_if_necessary

        return HistogramMetric(self.column, Failure(wrap_if_necessary(exception)))


def resident_histograms(
    table, analyzers, aggregate_with=None, save_states_with=None,
    registers_of=(),
) -> Tuple[Dict[Histogram, HistogramMetric], Dict[str, np.ndarray]]:
    """The metrics of the Histograms among ``analyzers`` that take the
    top-N fast path over string columns of a persist()ed table: all of
    them from ONE dispatch and ONE fetch (``segment.resident_top_k``; one
    by one, H Histograms were H dispatches and 3H round trips a run). An
    analyzer that gets no metric here goes through ``calculate``, which
    also turns whatever failed here into its failure metric.

    Beside them, the HLL registers of those columns of ``registers_of``
    that are in the batch, folded in the same dispatch out of the entries
    PRESENT in the counts: ``{column: registers}``, empty when the batch
    did not run (the asker then scans, as ever)."""
    from deequ_tpu.analyzers.base import find_first_failing
    from deequ_tpu.ops.segment import resident_string_columns, resident_top_k

    candidates = [
        a for a in analyzers
        if isinstance(a, Histogram)
        and a.takes_top_k_path(table, aggregate_with, save_states_with)
    ]
    if not candidates:
        return {}, {}
    resident = resident_string_columns(table)
    batch = [
        a for a in candidates
        if a.column in resident
        and find_first_failing(table.schema, a.preconditions()) is None
    ]
    if not batch:
        return {}, {}
    try:
        served = resident_top_k(
            table, [(a.column, a.max_detail_bins) for a in batch],
            registers_of=registers_of,
        )
    # deequ-lint: ignore[bare-except] -- a failed batch falls back to calculate(), which re-raises into each analyzer's typed failure metric
    except Exception:  # noqa: BLE001
        return {}, {}
    if served is None:
        return {}, {}
    stats, registers = served
    return (
        {a: a.metric_from_top_k(s) for a, s in zip(batch, stats)}, registers
    )
