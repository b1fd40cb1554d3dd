"""``resident_loop``: one caller verifies one persisted table back to back
through ``VerificationSuite.on_data(table).add_check(...)
.add_required_analyzers(...).run()``."""

from __future__ import annotations

import time

from chipbench import suite_build
from chipbench.drivers.common import annotated


def slices(config: dict, data: dict, records: list):
    """The reference's view: the slices of rows, and per record the
    multiplicity of each slice in the data set its answers are about."""
    return [data], [[1] for _ in records]


def rows_per_operation(config: dict) -> int:
    return config["rows"]


class Driver:
    def __init__(self, config: dict, traffic: dict, suite: dict, data: dict):
        self.suite = suite
        self.rows = data["rows"]
        self.table = suite_build.table_of(data)
        self.analyzers = suite_build.analyzers_of(suite)
        self.check = suite_build.check_of(suite, self.rows)

    def _run(self, _k: int = 0):
        from deequ_tpu import VerificationSuite

        result = (
            VerificationSuite.on_data(self.table)
            .add_check(self.check)
            .add_required_analyzers(self.analyzers)
            .run()
        )
        return self.rows, suite_build.answers_of(result, self.analyzers)

    def prepare(self) -> dict:
        """persist() and one warm-up of the cell's own shapes: set-up."""
        t0 = time.perf_counter()
        with annotated("persist"):
            self.table.persist()
        t1 = time.perf_counter()
        with annotated("warmup"):
            self._run()
        return {"persist_s": t1 - t0, "warmup_s": time.perf_counter() - t1}

    def window(self, window) -> None:
        window.drive("suite.run", self._run)

    def release(self) -> None:
        self.table.unpersist()
        self.table = None
