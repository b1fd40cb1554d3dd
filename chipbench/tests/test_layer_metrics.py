"""A counter metric given as data evaluates against a snapshot pair."""

from chipbench import layer_metrics
from chipbench.drivers.common import Window


def _window(before, after, spans, rows, elapsed):
    w = Window(1.0)
    w.counters_before, w.counters_after = before, after
    w.records = [{"k": i, "rows": rows, "answers": None, "span_s": s,
                  "traced": False}
                 for i, s in enumerate(spans)]
    w.elapsed_s = elapsed
    return w


def test_counter_ratio_from_a_fake_snapshot_pair():
    before = {"device_fetches": 10, "bytes_packed": 1_000_000,
              "dispatch_seconds": 1.0, "drain_wait_seconds": 2.0}
    after = {"device_fetches": 16, "bytes_packed": 481_000_000,
             "dispatch_seconds": 1.25, "drain_wait_seconds": 4.0,
             "brand_new_counter": 4}
    totals = _window(before, after, [1.5, 1.5], 1000, 4.0).totals()
    assert totals["suites"] == 2 and totals["rows"] == 2000
    assert totals["brand_new_counter"] == 4
    ctx = {"counters": totals}
    fetches = {"name": "f", "kind": "counter_ratio",
               "terms": [["device_fetches", 1]], "per": "suites"}
    assert layer_metrics.evaluate(fetches, ctx) == 3.0
    packed = {"name": "p", "kind": "counter_ratio", "scale": 1e-6,
              "terms": [["bytes_packed", 1]], "per": "window_seconds"}
    assert layer_metrics.evaluate(packed, ctx) == 120.0
    host = {"name": "h", "kind": "counter_ratio", "scale": 1000.0,
            "terms": [["run_span_seconds", 1], ["dispatch_seconds", -1],
                      ["drain_wait_seconds", -1]], "per": "suites"}
    assert layer_metrics.evaluate(host, ctx) == 375.0


def test_nothing_to_read_returns_nothing():
    totals = _window({}, {"device_fetches": 1}, [], 0, 1.0).totals()
    missing = {"name": "m", "kind": "counter_ratio",
               "terms": [["no_such_counter", 1]], "per": "suites"}
    assert layer_metrics.evaluate(missing, {"counters": totals}) is None
    per_suite = {"name": "f", "kind": "counter_ratio",
                 "terms": [["device_fetches", 1]], "per": "suites"}
    assert layer_metrics.evaluate(per_suite, {"counters": totals}) is None
    for reader in ("device_idle_pct", "scan_hbm_roofline"):
        spec = {"name": reader, "kind": "reader", "reader": reader}
        assert layer_metrics.evaluate(spec, {"trace": {}}) is None
