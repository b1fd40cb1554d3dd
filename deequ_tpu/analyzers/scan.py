"""Scan-shareable single-pass analyzers (reference §2.3 of SURVEY.md).

Each analyzer contributes a ScanOp to the fused device pass. Null/where
semantics mirror the reference exactly:

- denominators use "conditional count" = number of rows satisfying the
  ``where`` filter (ALL such rows, including nulls in the target column —
  reference analyzers/Analyzer.scala:428-434);
- numerators and value aggregates skip nulls (Spark aggregate semantics).

Numerics: per-chunk moments (stddev/correlation) are computed centered
around the chunk-local mean on device (exact two-pass within a chunk) and
combined across chunks/devices with the reference's Chan/Welford merge
formulas (StandardDeviation.scala:37-44, Correlation.scala:37-52) — this is
numerically stronger than naive sum-of-squares over a 1B-row scan.
"""

from __future__ import annotations

import enum
import re
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from deequ_tpu.analyzers.base import (
    Analyzer,
    ScanShareableAnalyzer,
    State,
    entity_from,
    has_column,
    is_numeric,
    is_string,
    metric_from_failure,
    metric_from_value,
)
from deequ_tpu.analyzers.states import (
    CorrelationState,
    DataTypeHistogram,
    MaxState,
    MeanState,
    MinState,
    NumMatches,
    NumMatchesAndCount,
    StandardDeviationState,
    SumState,
)
from deequ_tpu.data.table import ColumnarTable, DType
from deequ_tpu.exceptions import EmptyStateException
from deequ_tpu.expr.eval import compile_predicate
from deequ_tpu.metrics import (
    Distribution,
    DistributionValue,
    DoubleMetric,
    Entity,
    HistogramMetric,
)
from deequ_tpu.ops import df32 as dfops
from deequ_tpu.ops.scan_engine import ScanOp
from deequ_tpu.tryresult import Failure, Success


def _compile_where(where: Optional[str], table: ColumnarTable):
    """Compile an optional where filter -> (predicate fn or None, columns)."""
    if where is None:
        return None, set()
    return compile_predicate(where, table)


def _string_baked(table, cols) -> bool:
    """True when a compiled predicate touches a string column: its
    dictionary LUTs are baked into the trace at compile time, making the
    program table-specific (excluded from cross-table program caches)."""
    return any(c in table and table[c].dtype == DType.STRING for c in cols)


def _rows(vals, row_valid, xp, n, predicate):
    if predicate is None:
        return row_valid
    return row_valid & predicate(vals, xp, n)


def _col_mask(val, xp):
    """Validity mask of a column Val (string columns: code >= 0)."""
    if val.kind == "str":
        return val.data >= 0
    return val.mask


def _plane(where, column, stats, from_stats):
    """The plane declaration of a ScanOp (ops/scan_engine.py): without a
    ``where``, the op's partial is ``from_stats`` of ONE column's
    statistics, so the planner may take them from the batched reduction
    over the packed planes where the layout has the column there."""
    if where is not None:
        return {}
    return {
        "plane_column": column,
        "plane_stats": stats,
        "plane_update": from_stats,
    }


def _empty_state_failure(analyzer: "StandardScanAnalyzer"):
    return EmptyStateException(
        f"Empty state for analyzer {analyzer!r}, all input values were NULL."
    )


class StandardScanAnalyzer(ScanShareableAnalyzer):
    """Shortcut base for analyzers producing one DoubleMetric
    (reference StandardScanShareableAnalyzer, Analyzer.scala:200-226)."""

    metric_name: str = ""

    @property
    def instance(self) -> str:
        return getattr(self, "column", "*")

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    def compute_metric_from(self, state: Optional[State]) -> DoubleMetric:
        if state is None:
            return self.to_failure_metric(_empty_state_failure(self))
        return metric_from_value(
            state.metric_value(), self.metric_name, self.instance, self.entity
        )

    def to_failure_metric(self, exception: Exception) -> DoubleMetric:
        return metric_from_failure(
            exception, self.metric_name, self.instance, self.entity
        )


# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Size(StandardScanAnalyzer):
    """Row count, optionally filtered (reference analyzers/Size.scala:23-48)."""

    where: Optional[str] = None

    metric_name = "Size"

    @property
    def instance(self) -> str:
        return "*"

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, cols = _compile_where(self.where, table)

        def update(vals, row_valid, xp, n):
            return {"n": dfops.masked_count(_rows(vals, row_valid, xp, n, pred), xp)}

        return ScanOp(
            tuple(sorted(cols)), update, {"n": "sum"},
            dictionary_baked=_string_baked(table, cols),
        )

    def state_from_scan_result(self, result) -> Optional[NumMatches]:
        return NumMatches(int(result["n"]))


@dataclass(frozen=True)
class Completeness(StandardScanAnalyzer):
    """Fraction of non-null values (reference analyzers/Completeness.scala)."""

    column: str
    where: Optional[str] = None

    metric_name = "Completeness"

    def preconditions(self):
        return [has_column(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            matches = rows & _col_mask(vals[col], xp)
            return {
                "matches": dfops.masked_count(matches, xp),
                "count": dfops.masked_count(rows, xp),
            }

        return ScanOp(
            tuple(sorted(cols)), update, {"matches": "sum", "count": "sum"},
            dictionary_baked=_string_baked(table, wcols),
            **_plane(
                self.where, col, (),
                lambda st: {"matches": st["count"], "count": st["rows"]},
            ),
        )

    def state_from_scan_result(self, result) -> Optional[NumMatchesAndCount]:
        return NumMatchesAndCount(int(result["matches"]), int(result["count"]))


@dataclass(frozen=True)
class Compliance(StandardScanAnalyzer):
    """Fraction of rows satisfying a predicate
    (reference analyzers/Compliance.scala:24-53)."""

    instance_name: str
    predicate: str
    where: Optional[str] = None

    metric_name = "Compliance"

    @property
    def instance(self) -> str:
        return self.instance_name

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        crit, ccols = compile_predicate(self.predicate, table)
        cols = wcols | ccols

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            matches = rows & crit(vals, xp, n)
            return {
                "matches": dfops.masked_count(matches, xp),
                "count": dfops.masked_count(rows, xp),
            }

        return ScanOp(
            tuple(sorted(cols)), update, {"matches": "sum", "count": "sum"},
            dictionary_baked=_string_baked(table, cols),
        )

    def state_from_scan_result(self, result) -> Optional[NumMatchesAndCount]:
        return NumMatchesAndCount(int(result["matches"]), int(result["count"]))


class Patterns:
    """Built-in patterns (reference analyzers/PatternMatch.scala:57-72).

    Equivalent well-known public patterns: RFC-5322-style email, the
    stephenhay URL pattern, US SSN with invalid-range exclusions, and
    major-brand credit card numbers.
    """

    # the full public RFC-5322 pattern (emailregex.com), incl. the
    # quoted-local-part and IP-literal alternatives the reference carries
    # (PatternMatch.scala:61) — e.g. "quoted.local"@example.com,
    # "a\\ b"@example.com (escaped space), user@[192.168.0.1]
    EMAIL = (
        r"""(?:[a-z0-9!#$%&'*+/=?^_`{|}~-]+(?:\.[a-z0-9!#$%&'*+/=?^_`{|}~-]+)*"""
        r"""|"(?:[\x01-\x08\x0b\x0c\x0e-\x1f\x21\x23-\x5b\x5d-\x7f]"""
        r"""|\\[\x01-\x09\x0b\x0c\x0e-\x7f])*")"""
        r"""@(?:(?:[a-z0-9](?:[a-z0-9-]*[a-z0-9])?\.)+"""
        r"""[a-z0-9](?:[a-z0-9-]*[a-z0-9])?"""
        r"""|\[(?:(?:25[0-5]|2[0-4][0-9]|[01]?[0-9][0-9]?)\.){3}"""
        r"""(?:25[0-5]|2[0-4][0-9]|[01]?[0-9][0-9]?|[a-z0-9-]*[a-z0-9]:"""
        r"""(?:[\x01-\x08\x0b\x0c\x0e-\x1f\x21-\x5a\x53-\x7f]"""
        r"""|\\[\x01-\x09\x0b\x0c\x0e-\x7f])+)\])"""
    )
    URL = r"""(https?|ftp)://[^\s/$.?#].[^\s]*"""
    SOCIAL_SECURITY_NUMBER_US = (
        r"""(?!219[- ]?09[- ]?9999|078[- ]?05[- ]?1120)"""
        r"""(?!666|000|9\d{2})\d{3}[- ]?(?!00)\d{2}[- ]?(?!0{4})\d{4}"""
    )
    CREDITCARD = (
        r"""\b(?:3[47]\d{2}([ -]?)\d{6}\1\d|"""
        r"""(?:(?:4\d|5[1-5]|65)\d{2}|6011)([ -]?)\d{4}\2\d{4}\2)\d{4}\b"""
    )


@dataclass(frozen=True)
class PatternMatch(StandardScanAnalyzer):
    """Fraction of values matching a regex (reference PatternMatch.scala).

    TPU-first design: the regex runs ONCE per distinct dictionary value on
    the host (O(cardinality)); the device work is a boolean gather over the
    int32 code array fused into the shared scan (SURVEY.md §7.3 hybrid plan).
    """

    column: str
    pattern: str
    where: Optional[str] = None

    metric_name = "PatternMatch"

    def preconditions(self):
        return [has_column(self.column), is_string(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column
        rx = re.compile(self.pattern)
        lut_kind = f"regex:{self.pattern}"

        def build_lut(dictionary):
            return np.array(
                [rx.search(s) is not None for s in dictionary], dtype=np.bool_
            )

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            hit = v.lut(lut_kind)[xp.maximum(v.data, 0)] & (v.data >= 0)
            return {
                "matches": dfops.masked_count(rows & hit, xp),
                "count": dfops.masked_count(rows, xp),
            }

        return ScanOp(
            tuple(sorted(cols)), update, {"matches": "sum", "count": "sum"},
            luts=((col, lut_kind, build_lut),),
            dictionary_baked=_string_baked(table, wcols),
        )

    def state_from_scan_result(self, result) -> Optional[NumMatchesAndCount]:
        return NumMatchesAndCount(int(result["matches"]), int(result["count"]))


class _ExtremumAnalyzer(StandardScanAnalyzer):
    """Shared machinery for Minimum/Maximum (value) analyzers."""

    _tag: str = "min"

    def preconditions(self):
        return [has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column
        tag = self._tag
        identity = np.inf if tag == "min" else -np.inf

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            ok = rows & v.mask
            agg = dfops.masked_extremum(v.data, v.lo, ok, xp, tag)
            return {"value": agg, "n": dfops.masked_count(ok, xp)}

        return ScanOp(
            tuple(sorted(cols)), update, {"value": tag, "n": "sum"},
            dictionary_baked=_string_baked(table, wcols),
            **_plane(
                self.where, col, (tag,),
                lambda st: {"value": st[tag], "n": st["count"]},
            ),
        )

    def state_from_scan_result(self, result):
        if int(result["n"]) == 0:
            return None
        value = float(result["value"])
        return MinState(value) if self._tag == "min" else MaxState(value)


@dataclass(frozen=True)
class Minimum(_ExtremumAnalyzer):
    column: str
    where: Optional[str] = None
    metric_name = "Minimum"
    _tag = "min"


@dataclass(frozen=True)
class Maximum(_ExtremumAnalyzer):
    column: str
    where: Optional[str] = None
    metric_name = "Maximum"
    _tag = "max"


class _LengthAnalyzer(StandardScanAnalyzer):
    """Shared machinery for MinLength/MaxLength (string length extrema).

    Lengths are a host lookup table over the dictionary; device work is a
    gather + masked min/max fused into the shared scan.
    """

    _tag: str = "min"

    def preconditions(self):
        return [has_column(self.column), is_string(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column
        tag = self._tag
        identity = np.inf if tag == "min" else -np.inf

        def build_lut(dictionary):
            from deequ_tpu import native

            # f32 is exact for lengths (< 2^24) and keeps the gathered
            # plane + min/max on native vector units
            native_lengths = native.utf8_lengths(dictionary)
            if native_lengths is not None:
                return native_lengths.astype(np.float32)
            return np.array(
                [float(len(s)) for s in dictionary], dtype=np.float32
            )

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            lengths = v.lut("utf8len")[xp.maximum(v.data, 0)]
            ok = rows & (v.data >= 0)
            guarded = xp.where(ok, lengths, xp.asarray(np.float32(identity)))
            agg = (xp.min(guarded) if tag == "min" else xp.max(guarded)).astype(
                xp.float64
            )
            return {"value": agg, "n": dfops.masked_count(ok, xp)}

        return ScanOp(
            tuple(sorted(cols)), update, {"value": tag, "n": "sum"},
            luts=((col, "utf8len", build_lut),),
            dictionary_baked=_string_baked(table, wcols),
        )

    def state_from_scan_result(self, result):
        if int(result["n"]) == 0:
            return None
        value = float(result["value"])
        return MinState(value) if self._tag == "min" else MaxState(value)


@dataclass(frozen=True)
class MinLength(_LengthAnalyzer):
    column: str
    where: Optional[str] = None
    metric_name = "MinLength"
    _tag = "min"


@dataclass(frozen=True)
class MaxLength(_LengthAnalyzer):
    column: str
    where: Optional[str] = None
    metric_name = "MaxLength"
    _tag = "max"


@dataclass(frozen=True)
class Mean(StandardScanAnalyzer):
    """Mean over non-null values (reference analyzers/Mean.scala:25-54)."""

    column: str
    where: Optional[str] = None

    metric_name = "Mean"

    def preconditions(self):
        return [has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            ok = rows & v.mask
            return {
                "sum": dfops.masked_sum(v.data, v.lo, ok, xp),
                "count": dfops.masked_count(ok, xp),
            }

        return ScanOp(
            tuple(sorted(cols)), update, {"sum": "sum", "count": "sum"},
            dictionary_baked=_string_baked(table, wcols),
            **_plane(
                self.where, col, ("sum",),
                lambda st: {"sum": st["sum"], "count": st["count"]},
            ),
        )

    def state_from_scan_result(self, result) -> Optional[MeanState]:
        if int(result["count"]) == 0:
            return None
        return MeanState(float(result["sum"]), int(result["count"]))


@dataclass(frozen=True)
class Sum(StandardScanAnalyzer):
    column: str
    where: Optional[str] = None

    metric_name = "Sum"

    def preconditions(self):
        return [has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            ok = rows & v.mask
            return {
                "sum": dfops.masked_sum(v.data, v.lo, ok, xp),
                "n": dfops.masked_count(ok, xp),
            }

        return ScanOp(
            tuple(sorted(cols)), update, {"sum": "sum", "n": "sum"},
            dictionary_baked=_string_baked(table, wcols),
            **_plane(
                self.where, col, ("sum",),
                lambda st: {"sum": st["sum"], "n": st["count"]},
            ),
        )

    def state_from_scan_result(self, result) -> Optional[SumState]:
        if int(result["n"]) == 0:
            return None
        return SumState(float(result["sum"]))


def _chunk_moments(vals, row_valid, xp, n, pred, col):
    """Per-chunk (n, local mean, centered m2) — exact within a chunk
    (two-float compute, ops/df32.py:masked_moments)."""
    rows = _rows(vals, row_valid, xp, n, pred)
    v = vals[col]
    ok = rows & v.mask
    cnt, s, mean, m2 = dfops.masked_moments(v.data, v.lo, ok, xp)
    return ok, cnt, mean, m2


@dataclass(frozen=True)
class StandardDeviation(StandardScanAnalyzer):
    """Population stddev via mergeable (n, avg, m2) moments
    (reference analyzers/StandardDeviation.scala:25-73)."""

    column: str
    where: Optional[str] = None

    metric_name = "StandardDeviation"

    def preconditions(self):
        return [has_column(self.column), is_numeric(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column

        def update(vals, row_valid, xp, n):
            ok, cnt, mean, m2 = _chunk_moments(vals, row_valid, xp, n, pred, col)
            return {"n": cnt, "avg": mean, "m2": m2}

        return ScanOp(
            tuple(sorted(cols)), update,
            {"n": "gather", "avg": "gather", "m2": "gather"},
            dictionary_baked=_string_baked(table, wcols),
            **_plane(
                self.where, col, ("m2",),
                lambda st: {"n": st["count"], "avg": st["mean"], "m2": st["m2"]},
            ),
        )

    def state_from_scan_result(self, result) -> Optional[StandardDeviationState]:
        ns = np.atleast_1d(result["n"])
        avgs = np.atleast_1d(result["avg"])
        m2s = np.atleast_1d(result["m2"])
        state = StandardDeviationState(0.0, 0.0, 0.0)
        for n, avg, m2 in zip(ns, avgs, m2s):
            state = state.sum(StandardDeviationState(float(n), float(avg), float(m2)))
        if state.n == 0:
            return None
        return state


@dataclass(frozen=True)
class Correlation(StandardScanAnalyzer):
    """Pearson correlation via mergeable co-moment state
    (reference analyzers/Correlation.scala:26-105). Only rows where BOTH
    columns are non-null participate (Spark Corr semantics)."""

    first_column: str
    second_column: str
    where: Optional[str] = None

    metric_name = "Correlation"

    @property
    def instance(self) -> str:
        return f"{self.first_column},{self.second_column}"

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    def preconditions(self):
        return [
            has_column(self.first_column),
            is_numeric(self.first_column),
            has_column(self.second_column),
            is_numeric(self.second_column),
        ]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.first_column, self.second_column}
        ca, cb = self.first_column, self.second_column

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            va, vb = vals[ca], vals[cb]
            ok = rows & va.mask & vb.mask
            cnt, ma, mb, ck, x_mk, y_mk = dfops.masked_comoments(
                va.data, va.lo, vb.data, vb.lo, ok, xp
            )
            return {
                "n": cnt,
                "x_avg": ma,
                "y_avg": mb,
                "ck": ck,
                "x_mk": x_mk,
                "y_mk": y_mk,
            }

        tags = {k: "gather" for k in ("n", "x_avg", "y_avg", "ck", "x_mk", "y_mk")}
        return ScanOp(
            tuple(sorted(cols)), update, tags,
            dictionary_baked=_string_baked(table, wcols),
        )

    def state_from_scan_result(self, result) -> Optional[CorrelationState]:
        fields = ["n", "x_avg", "y_avg", "ck", "x_mk", "y_mk"]
        arrays = [np.atleast_1d(result[f]) for f in fields]
        state = CorrelationState(0.0, 0.0, 0.0, 0.0, 0.0, 0.0)
        for row in zip(*arrays):
            state = state.sum(CorrelationState(*(float(x) for x in row)))
        if state.n == 0:
            return None
        return state


class DataTypeInstances(enum.Enum):
    """Inferred value types (reference analyzers/DataType.scala:25-30)."""

    UNKNOWN = "Unknown"
    FRACTIONAL = "Fractional"
    INTEGRAL = "Integral"
    BOOLEAN = "Boolean"
    STRING = "String"


# value-classification regexes mirroring StatefulDataType.scala:36-38
_FRACTIONAL_RE = re.compile(r"^(-|\+)? ?\d*\.\d*$")
_INTEGRAL_RE = re.compile(r"^(-|\+)? ?\d*$")
_BOOLEAN_RE = re.compile(r"^(true|false)$")

_TYPE_SLOTS = ["null", "fractional", "integral", "boolean", "string"]


def _classify_string(s: str) -> int:
    """Slot index for one string value (0 is reserved for null)."""
    if _FRACTIONAL_RE.match(s):
        return 1
    if _INTEGRAL_RE.match(s):
        return 2
    if _BOOLEAN_RE.match(s):
        return 3
    return 4


def _classify_dictionary(values) -> np.ndarray:
    """Classify all distinct values: C++ batch kernel when available,
    regex fallback otherwise (identical outputs, asserted by tests)."""
    from deequ_tpu import native

    classes = native.classify_strings(values)
    if classes is not None:
        return classes
    return np.array([_classify_string(s) for s in values], dtype=np.int32)


@dataclass(frozen=True)
class DataType(ScanShareableAnalyzer):
    """Per-value type inference histogram (reference analyzers/DataType.scala).

    The reference regex-classifies each row's string representation inside
    the scan. Here classification runs once per distinct dictionary value on
    host; the device aggregates a 5-slot count vector in the fused scan. For
    columns already typed numeric/boolean the class is constant per column.
    """

    column: str
    where: Optional[str] = None

    def preconditions(self):
        return [has_column(self.column)]

    def scan_op(self, table: ColumnarTable) -> ScanOp:
        pred, wcols = _compile_where(self.where, table)
        cols = wcols | {self.column}
        col = self.column
        dtype = table[col].dtype

        def update(vals, row_valid, xp, n):
            rows = _rows(vals, row_valid, xp, n, pred)
            v = vals[col]
            if dtype == DType.STRING:
                classes = xp.where(
                    v.data >= 0,
                    v.lut("datatype")[xp.maximum(v.data, 0)],
                    0,
                )
            else:
                const = {
                    DType.FRACTIONAL: 1,
                    DType.INTEGRAL: 2,
                    DType.BOOLEAN: 3,
                }[dtype]
                classes = xp.where(v.mask, const, 0)
            counts = xp.stack(
                [dfops.masked_count(rows & (classes == k), xp) for k in range(5)]
            )
            return {"counts": counts}

        luts = (
            ((col, "datatype", _classify_dictionary),)
            if dtype == DType.STRING
            else ()
        )
        return ScanOp(
            tuple(sorted(cols)), update, {"counts": "sum"},
            luts=luts,
            dictionary_baked=_string_baked(table, wcols),
        )

    def state_from_scan_result(self, result) -> Optional[DataTypeHistogram]:
        c = np.asarray(result["counts"]).astype(np.int64)
        return DataTypeHistogram(int(c[0]), int(c[1]), int(c[2]), int(c[3]), int(c[4]))

    def compute_metric_from(self, state: Optional[DataTypeHistogram]) -> HistogramMetric:
        if state is None:
            return self.to_failure_metric(
                EmptyStateException(f"Empty state for analyzer {self!r}.")
            )
        return HistogramMetric(self.column, Success(to_distribution(state)))

    def to_failure_metric(self, exception: Exception) -> HistogramMetric:
        from deequ_tpu.exceptions import wrap_if_necessary

        return HistogramMetric(self.column, Failure(wrap_if_necessary(exception)))


def to_distribution(hist: DataTypeHistogram) -> Distribution:
    """DataTypeHistogram -> 5-bin Distribution (DataType.scala:95-115).
    Nulls are reported under 'Unknown'; ratios over ALL observations."""
    total = max(hist.total, 1) if hist.total > 0 else 0
    counts = {
        DataTypeInstances.UNKNOWN.value: hist.num_null,
        DataTypeInstances.FRACTIONAL.value: hist.num_fractional,
        DataTypeInstances.INTEGRAL.value: hist.num_integral,
        DataTypeInstances.BOOLEAN.value: hist.num_boolean,
        DataTypeInstances.STRING.value: hist.num_string,
    }
    values = {
        k: DistributionValue(v, (v / total) if total else 0.0)
        for k, v in counts.items()
    }
    return Distribution(values, number_of_bins=5)


def determine_type(dist: Distribution) -> DataTypeInstances:
    """Type-decision lattice (reference DataType.scala:116-143)."""

    def ratio_of(key: DataTypeInstances) -> float:
        dv = dist.values.get(key.value)
        return dv.ratio if dv else 0.0

    if ratio_of(DataTypeInstances.UNKNOWN) == 1.0:
        return DataTypeInstances.UNKNOWN
    if ratio_of(DataTypeInstances.STRING) > 0.0 or (
        ratio_of(DataTypeInstances.BOOLEAN) > 0.0
        and (
            ratio_of(DataTypeInstances.INTEGRAL) > 0.0
            or ratio_of(DataTypeInstances.FRACTIONAL) > 0.0
        )
    ):
        return DataTypeInstances.STRING
    if ratio_of(DataTypeInstances.BOOLEAN) > 0.0:
        return DataTypeInstances.BOOLEAN
    if ratio_of(DataTypeInstances.FRACTIONAL) > 0.0:
        return DataTypeInstances.FRACTIONAL
    return DataTypeInstances.INTEGRAL
