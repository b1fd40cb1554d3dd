"""What the two loops share: the clock, the span around each call into the
program, the snapshot of its counters, and the record of one operation."""

from __future__ import annotations

import time
from contextlib import contextmanager


@contextmanager
def annotated(name: str):
    """A span in the profiler's own trace (a no-op cost when none runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def counters() -> dict:
    """The program's counters, numbers only (lists become their length)."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    out = {}
    for k, v in SCAN_STATS.snapshot().items():
        if isinstance(v, bool) or v is None:
            continue
        if isinstance(v, (int, float)):
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = len(v)
    return out


def degradation_events() -> list:
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    return [dict(e) for e in SCAN_STATS.degradation_events]


TRACE_SECONDS = 1.0  # the traced operations last at least this long


class Window:
    """Starts operations until ``seconds`` have passed and lets the one in
    flight finish: no partial operation is counted and none is dropped.
    With ``trace_ops`` it then drives that many more operations (and at
    least ``TRACE_SECONDS`` of them) inside the profiler's session
    (``on_trace_start`` / ``on_trace_stop``): behind the window, so that a
    traced run's window and counters are those of an untraced run. The
    traced operations are compared like the others and counted in nothing."""

    def __init__(self, seconds: float, trace_ops: int = 0,
                 on_trace_start=None, on_trace_stop=None):
        self.seconds = seconds
        self.trace_ops = trace_ops
        self.on_trace_start = on_trace_start
        self.on_trace_stop = on_trace_stop
        self.op_name = None
        self.records = []
        self.attempted = 0
        self.failed = 0
        self.errors = []

    def _one(self, operation, k: int, traced: bool) -> None:
        self.attempted += 1
        t_op = time.perf_counter()
        try:
            with annotated(self.op_name):
                rows, answers = operation(k)
        except Exception as e:  # a failed operation fails the run, later
            self.failed += 1
            self.errors.append(f"{self.op_name} {k}: {type(e).__name__}: {e}")
        else:
            self.records.append({
                "k": k, "rows": rows, "answers": answers, "traced": traced,
                "span_s": time.perf_counter() - t_op,
            })

    def drive(self, op_name: str, operation) -> None:
        """``operation(k) -> (rows, answers)`` is one suite or one append."""
        self.op_name = op_name
        self.counters_before = counters()
        self.t0 = time.perf_counter()
        k = 0
        while time.perf_counter() - self.t0 < self.seconds and self.failed < 3:
            self._one(operation, k, False)
            k += 1
        self.elapsed_s = time.perf_counter() - self.t0
        self.counters_after = counters()
        if self.trace_ops and self.failed < 3:
            self.on_trace_start()
            t_trace, last = time.perf_counter(), k + self.trace_ops
            while k < last or (time.perf_counter() - t_trace
                               < TRACE_SECONDS and self.failed < 3):
                self._one(operation, k, True)
                k += 1
            self.on_trace_stop()

    def totals(self) -> dict:
        """The harness's own counts, beside the program's counter deltas."""
        delta = {
            k: self.counters_after[k] - self.counters_before.get(k, 0)
            for k in self.counters_after
        }
        timed = [r for r in self.records if not r["traced"]]
        delta["suites"] = len(timed)
        delta["rows"] = sum(r["rows"] for r in timed)
        delta["window_seconds"] = self.elapsed_s
        delta["run_span_seconds"] = sum(r["span_s"] for r in timed)
        delta["one"] = 1
        return delta

    def span_ms(self) -> dict:
        """How the operations' spans spread: a steadier statistic beside
        the rate, for whoever reads a noisy run."""
        spans = sorted(1000.0 * r["span_s"] for r in self.records
                       if not r["traced"])
        if not spans:
            return {}
        at = lambda q: spans[min(len(spans) - 1, int(q * len(spans)))]
        return {"min": spans[0], "p50": at(0.5), "p90": at(0.9),
                "max": spans[-1]}
