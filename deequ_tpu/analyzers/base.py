"""Analyzer and State core (reference layer L3, analyzers/Analyzer.scala).

The single most important idea preserved from the reference design:
**State is a commutative monoid** (`sum` merges two states,
analyzers/Analyzer.scala:30-48) and every analyzer is

    map -> partial state per shard,  merge across shards,  finalize to metric.

On TPU that is one fused XLA reduction per scan + collective merges; across
time it is incremental computation (merging yesterday's persisted state is
the same operation as merging another device's partial state).
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Callable, List, Optional, Sequence, TypeVar

from deequ_tpu.data.table import ColumnarTable, DType, Schema
from deequ_tpu.exceptions import (
    MetricCalculationException,
    NoColumnsSpecifiedException,
    NoSuchColumnException,
    NumberOfSpecifiedColumnsException,
    PlanLintError,
    RunBudgetExhaustedException,
    WrongColumnTypeException,
    wrap_if_necessary,
)
from deequ_tpu.metrics import DoubleMetric, Entity, Metric
from deequ_tpu.obs.recorder import seam
from deequ_tpu.tryresult import Failure, Success

S = TypeVar("S", bound="State")


class State(ABC):
    """A sufficient statistic forming a commutative monoid under ``sum``."""

    @abstractmethod
    def sum(self, other: "State") -> "State":
        """Merge two states (commutative, associative)."""

    def __add__(self, other: "State") -> "State":
        return self.sum(other)


class DoubleValuedState(State):
    """A state that can finalize directly to a double metric value."""

    @abstractmethod
    def metric_value(self) -> float:
        ...


# -- Preconditions (reference analyzers/Analyzer.scala:285-359) -------------


def has_column(column: str) -> Callable[[Schema], None]:
    def check(schema: Schema) -> None:
        if not schema.has_column(column):
            raise NoSuchColumnException(column)

    return check


def is_numeric(column: str) -> Callable[[Schema], None]:
    def check(schema: Schema) -> None:
        if schema.has_column(column) and not schema[column].dtype.is_numeric:
            raise WrongColumnTypeException(
                f"Expected type of column {column} to be one of numeric types, "
                f"but found {schema[column].dtype.value} instead!"
            )

    return check


def is_string(column: str) -> Callable[[Schema], None]:
    def check(schema: Schema) -> None:
        if schema.has_column(column) and schema[column].dtype != DType.STRING:
            raise WrongColumnTypeException(
                f"Expected type of column {column} to be string, but found "
                f"{schema[column].dtype.value} instead!"
            )

    return check


def at_least_one(columns: Sequence[str]) -> Callable[[Schema], None]:
    def check(schema: Schema) -> None:
        if len(columns) == 0:
            raise NoColumnsSpecifiedException(
                "At least one column needs to be specified!"
            )

    return check


def exactly_n_columns(columns: Sequence[str], n: int) -> Callable[[Schema], None]:
    def check(schema: Schema) -> None:
        if len(columns) != n:
            raise NumberOfSpecifiedColumnsException(
                f"{n} columns have to be specified! Currently, columns contains "
                f"only {len(columns)} column(s): {','.join(columns)}!"
            )

    return check


def find_first_failing(
    schema: Schema, conditions: Sequence[Callable[[Schema], None]]
) -> Optional[Exception]:
    """Return the first failing precondition's exception, if any."""
    for condition in conditions:
        try:
            condition(schema)
        except Exception as e:  # noqa: BLE001 — precondition failure is data
            return e
    return None


# -- Analyzer ---------------------------------------------------------------


class Analyzer(ABC):
    """Computes a state S from data and a metric M from the state.

    Mirrors reference Analyzer[S <: State[S], +M <: Metric[_]]
    (analyzers/Analyzer.scala:56-165). Analyzers are immutable, hashable
    values used as dictionary keys in AnalyzerContext and the repository.
    """

    # -- abstract surface --

    @abstractmethod
    def compute_state_from(self, table: ColumnarTable) -> Optional[State]:
        ...

    @abstractmethod
    def compute_metric_from(self, state: Optional[State]) -> Metric:
        ...

    @abstractmethod
    def to_failure_metric(self, exception: Exception) -> Metric:
        ...

    def preconditions(self) -> List[Callable[[Schema], None]]:
        return []

    # -- orchestration (state load/merge/persist), reference L88-165 --

    def calculate(
        self,
        table: ColumnarTable,
        aggregate_with=None,  # StateLoader
        save_states_with=None,  # StatePersister
    ) -> Metric:
        failing = find_first_failing(table.schema, self.preconditions())
        if failing is not None:
            return self.to_failure_metric(failing)
        try:
            if getattr(table, "is_streaming", False):
                state = self.compute_state_from_stream(table)
            else:
                state = self.compute_state_from(table)
        except PlanLintError:
            # static contract violations raise typed through every
            # surface (the plan_lint="error" contract): planner drift is
            # a programming error, never a data-quality failure metric
            raise
        except RunBudgetExhaustedException as e:
            if not e.degraded:
                # on_budget_exhausted="raise": a run-level outcome must
                # reach the caller typed, never hide in one analyzer's
                # failure metric
                raise
            # "degrade": complete gracefully as a typed failure metric —
            # grouping/own-pass states have no row-range partial surface
            return self.to_failure_metric(e)
        except Exception as e:  # noqa: BLE001
            return self.to_failure_metric(wrap_if_necessary(e))
        return self.calculate_metric(state, aggregate_with, save_states_with)

    def compute_state_from_stream(self, stream) -> Optional[State]:
        """Out-of-core state: fold the monoid per batch — the same
        ``State.sum`` merge used across devices and incremental runs,
        applied across stream batches as a TREE (StreamStateFolder).
        Scan-shareable analyzers override this (the fused scan engine
        streams them in one pipelined pass)."""
        folder = StreamStateFolder()
        for batch in stream.batches(columns=self._stream_columns()):
            folder.add(self.compute_state_from(batch))
        return folder.result()

    def _stream_columns(self) -> Optional[List[str]]:
        """Columns to read when streaming (None = all); overridden by
        analyzers that know their column set, enabling storage-side
        column pruning."""
        return None

    def calculate_metric(
        self, state: Optional[State], aggregate_with=None, save_states_with=None
    ) -> Metric:
        return metrics_from_states(
            [(self, state)], aggregate_with, save_states_with
        )[0]

    def aggregate_state_to(self, source_a, source_b, target) -> None:
        """Merge states from two loaders into a persister (reference L130-147)."""
        state_a = source_a.load(self)
        state_b = source_b.load(self)
        merged = merge_states(state_a, state_b)
        if merged is not None:
            target.persist(self, merged)

    def load_state_and_compute_metric(self, source) -> Metric:
        """Compute a metric purely from a persisted state — no data scan."""
        try:
            return self.compute_metric_from(source.load(self))
        except Exception as e:  # noqa: BLE001
            return self.to_failure_metric(wrap_if_necessary(e))

    def copy_state_to(self, source, target) -> None:
        state = source.load(self)
        if state is not None:
            target.persist(self, state)

    @property
    def name(self) -> str:
        return type(self).__name__


def metrics_from_states(
    pairs, aggregate_with=None, save_states_with=None
) -> list:
    """One metric per ``(analyzer, state)`` pair, in order, in two
    phases so that each is ONE seam whatever the number of analyzers:
    ``states`` (load what ``aggregate_with`` holds, merge, persist to
    ``save_states_with``; not opened when there is neither) and
    ``evaluate`` (the metric from the merged state). A failure anywhere
    is that analyzer's failure metric, as data."""
    pairs = list(pairs)
    states = [state for _, state in pairs]
    metrics: List[Optional[Metric]] = [None] * len(pairs)
    if aggregate_with is not None or save_states_with is not None:
        with seam("states", analyzers=len(pairs)):
            for i, (analyzer, state) in enumerate(pairs):
                try:
                    if aggregate_with is not None:
                        state = merge_states(
                            state, aggregate_with.load(analyzer)
                        )
                    if save_states_with is not None and state is not None:
                        save_states_with.persist(analyzer, state)
                    states[i] = state
                except Exception as e:  # noqa: BLE001
                    metrics[i] = analyzer.to_failure_metric(
                        wrap_if_necessary(e)
                    )
    with seam("evaluate", analyzers=len(pairs)):
        for i, (analyzer, _) in enumerate(pairs):
            if metrics[i] is not None:
                continue
            try:
                metrics[i] = analyzer.compute_metric_from(states[i])
            except Exception as e:  # noqa: BLE001
                metrics[i] = analyzer.to_failure_metric(wrap_if_necessary(e))
    return metrics


def merge_states(a: Optional[State], b: Optional[State]) -> Optional[State]:
    """Option-aware monoid merge (reference analyzers/Analyzer.scala:366-386)."""
    if a is not None and b is not None:
        return a.sum(b)
    return a if a is not None else b


class StreamStateFolder:
    """Mergesort-style TREE fold of monoid states across stream batches.

    A linear chain (``merged = merged.sum(batch_state)``) re-merges the
    full growing state per batch — for frequency states that is
    O(B * G log G) and measured HOURS at 100 batches / 33M groups. The
    tree (a binary-counter stack of power-of-two partials) merges each
    state O(log B) times instead — the streaming analogue of the
    reference's treeReduce (KLLRunner.scala:104-112). States whose merge
    is set-like (frequency tables: re-sorted by key every merge) are
    bit-identical under any association; scalar float states differ only
    at the ulp level, the same variation any distributed fold has.

    With ``spill_store`` set (a spill.SpillingFrequencyStore), states
    route into the store instead: the store runs its own tree fold under
    a byte budget and spills sorted runs to disk past it, so the fold's
    host memory stays bounded even when the merged state itself is not
    (high-cardinality frequency tables). ``assume_canonical`` asserts
    every added state is already in canonical key order (letting the
    store's flushes skip a re-sort)."""

    def __init__(self, spill_store=None, assume_canonical: bool = False):
        self._stack: list = []  # (level, state); levels strictly decrease toward the top
        self._spill_store = spill_store
        self._assume_canonical = assume_canonical

    def add(self, state: Optional[State]) -> None:
        if state is None:  # all-null batches contribute no state
            return
        if self._spill_store is not None:
            self._spill_store.add(state, canonical=self._assume_canonical)
            return
        level = 0
        while self._stack and self._stack[-1][0] == level:
            _, prev = self._stack.pop()
            state = prev.sum(state)
            level += 1
        self._stack.append((level, state))

    def result(self) -> Optional[State]:
        if self._spill_store is not None:
            return self._spill_store.result()
        merged: Optional[State] = None
        for _, s in reversed(self._stack):
            merged = s if merged is None else s.sum(merged)
        return merged


class ScanShareableAnalyzer(Analyzer):
    """An analyzer whose state computation can fuse into one shared scan.

    The reference expresses this as Spark aggregation Columns with offset
    bookkeeping (analyzers/Analyzer.scala:169-197). Here each analyzer
    contributes a ``ScanOp`` — a pure JAX chunk-update function plus tagged
    reduction spec — and the planner concatenates all ops into ONE jitted
    device program per analysis run (ops/scan_engine.py).
    """

    @abstractmethod
    def scan_op(self, table: ColumnarTable):
        """Build this analyzer's device ScanOp for the given table."""

    @abstractmethod
    def state_from_scan_result(self, result) -> Optional[State]:
        """Convert the op's reduced numpy pytree into a host State."""

    def compute_state_from(self, table: ColumnarTable) -> Optional[State]:
        from deequ_tpu.ops.scan_engine import run_scan

        op = self.scan_op(table)
        (result,) = run_scan(table, [op])
        return self.state_from_scan_result(result)

    def compute_state_from_stream(self, stream) -> Optional[State]:
        # the fused scan engine streams batches itself (one pipelined pass,
        # pinned packer layout) — no per-batch state fold needed
        return self.compute_state_from(stream)


def metric_from_value(
    value: float, name: str, instance: str, entity: Entity
) -> DoubleMetric:
    return DoubleMetric(entity, name, instance, Success(float(value)))


def metric_from_failure(
    exception: Exception, name: str, instance: str, entity: Entity
) -> DoubleMetric:
    return DoubleMetric(
        entity, name, instance, Failure(wrap_if_necessary(exception))
    )


def entity_from(columns: Sequence[str]) -> Entity:
    return Entity.COLUMN if len(columns) == 1 else Entity.MULTICOLUMN
