"""``append_loop``: one caller appends partition after partition. Each
append is one ``VerificationSuite.on_data(part)`` run that merges the
running states (``aggregate_with`` / ``save_states_with`` on one
``InMemoryStateProvider``), saves its metrics under a fresh result key and
asks the anomaly check about the history of the appends before it. Append
k+1 starts when the verdict of append k is back. Every append hands the
program a table it has not seen (the same rows wrapped anew), as every
partition of a stream is: whatever the program keeps on a table object
after its first scan cannot make a later append cheaper."""

from __future__ import annotations

import time

from chipbench import suite_build
from chipbench.drivers.common import annotated
from chipbench.columns import slice_rows


def slices(config: dict, data: dict, records: list):
    """The reference's view: the partitions, and per record how often each
    has been appended once that record's append is in."""
    part_rows = config["partition_rows"]
    n_parts = data["rows"] // part_rows
    parts = [slice_rows(data, i * part_rows, (i + 1) * part_rows)
             for i in range(n_parts)]
    multiplicity = []
    for r in records:
        done = r["k"] + 1
        multiplicity.append([done // n_parts + (1 if i < done % n_parts else 0)
                             for i in range(n_parts)])
    return parts, multiplicity


def rows_per_operation(config: dict) -> int:
    return config["partition_rows"]


class Driver:
    def __init__(self, config: dict, traffic: dict, suite: dict, data: dict):
        self.suite = suite
        self.part_rows = config["partition_rows"]
        self.n_parts = data["rows"] // self.part_rows
        self.parts = [
            slice_rows(data, i * self.part_rows, (i + 1) * self.part_rows)
            for i in range(self.n_parts)
        ]
        self.analyzers = suite_build.analyzers_of(suite)
        self.check = suite_build.check_of(suite, self.part_rows)
        self._fresh_stores()

    def _fresh_stores(self) -> None:
        from deequ_tpu.repository.memory import InMemoryMetricsRepository
        from deequ_tpu.states import InMemoryStateProvider

        self.states = InMemoryStateProvider()
        self.repository = InMemoryMetricsRepository()

    def _append(self, k: int):
        from deequ_tpu import VerificationSuite
        from deequ_tpu.repository import ResultKey

        part = suite_build.table_of(self.parts[k % self.n_parts])
        builder = (
            VerificationSuite.on_data(part)
            .add_check(self.check)
            .add_required_analyzers(self.analyzers)
            .aggregate_with(self.states)
            .save_states_with(self.states)
            .use_repository(self.repository)
            .save_or_append_result(ResultKey(k, {"stream": "append"}))
        )
        anomaly = suite_build.anomaly_of(self.suite)
        if anomaly:
            builder = builder.add_anomaly_check(*anomaly)
        result = builder.run()
        return self.part_rows, suite_build.answers_of(result, self.analyzers)

    def prepare(self) -> dict:
        """Two warm-up appends (one onto empty states, one onto states that
        exist), then fresh stores: the window starts from nothing."""
        t0 = time.perf_counter()
        with annotated("warmup"):
            self._append(0)
            self._append(1)
        self._fresh_stores()
        return {"warmup_s": time.perf_counter() - t0}

    def window(self, window) -> None:
        window.drive("append.run", self._append)

    def release(self) -> None:
        self.parts = None
        self.states = None
        self.repository = None
