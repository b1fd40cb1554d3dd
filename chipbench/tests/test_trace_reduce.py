"""The reduction from trace to numbers, on a synthetic trace worked by hand."""

import pytest

from chipbench import trace_reduce as tr
from chipbench.readers import device_idle_pct, scan_hbm_roofline

# window [0, 10]; device ops: a loop 1..4 around two bodies, a copy 6..7,
# one op that straddles the window's end (9.5..11 -> 0.5 inside)
OPS = [("while.1", 1.0, 4.0), ("fusion.2", 1.5, 2.5), ("fusion.3", 3.0, 3.5),
       ("copy.4", 6.0, 7.0), ("fusion.2", 9.5, 11.0)]
HOST = [("chipbench.traced", 0.0, 10.0), ("suite.run", 0.5, 5.0),
        ("suite.run", 5.5, 9.0), ("repository.save", 4.2, 4.8)]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": OPS, "modules": [("jit_step(1)", 1.0, 4.0), ("jit_copy(2)", 6.0, 7.0)]}},
    "host": HOST}


def test_busy_union_and_idle_share():
    assert tr.union([(1, 3), (2, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert tr.busy_seconds(OPS, 0.0, 10.0) == pytest.approx(3.0 + 1.0 + 0.5)
    r = tr.reduce_trace(TRACE, "suite.run")
    assert r["busy_s"] == pytest.approx(4.5)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["traced_ops"] == 2
    assert device_idle_pct.read({"trace": r}) == pytest.approx(55.0)


def test_self_time_leaves_the_loop_what_its_body_does_not_take():
    own = tr.self_seconds(OPS, 0.0, 10.0)
    assert own["while.1"] == pytest.approx(3.0 - 1.0 - 0.5)
    assert own["fusion.2"] == pytest.approx(1.0 + 0.5)
    assert own["fusion.3"] == pytest.approx(0.5)
    assert sum(own.values()) == pytest.approx(4.5)
    r = tr.reduce_trace(TRACE, "suite.run")
    assert r["device_ops"][0] == ["while.1", pytest.approx(1.5)] or \
        r["device_ops"][0][0] in ("while.1", "fusion.2")
    assert dict(r["device_modules"])["jit_step(1)"] == pytest.approx(3.0)


def test_gaps_go_to_the_innermost_host_span():
    gaps = tr.idle_gaps(OPS, 0.0, 10.0)
    assert gaps == [(0.0, 1.0), (4.0, 6.0), (7.0, 9.5)]
    by = tr.attribute(gaps, HOST)
    # 0..0.5 traced only; 0.5..1 suite.run; 4..4.2 suite.run; 4.2..4.8 save;
    # 4.8..5 suite.run; 5..5.5 traced; 5.5..6 suite.run; 7..9 suite.run;
    # 9..9.5 traced
    assert by["repository.save"] == pytest.approx(0.6)
    assert by["suite.run"] == pytest.approx(0.5 + 0.2 + 0.2 + 0.5 + 2.0)
    assert by["chipbench.traced"] == pytest.approx(0.5 + 0.5 + 0.5)
    assert sum(by.values()) == pytest.approx(5.5)
    assert tr.attribute([(20.0, 21.0)], HOST) == {"(no span)": 1.0}


def test_roofline_share_from_shapes_peak_and_busy_time():
    cell = {"config": {"schema": [
        {"columns": "c0..c1", "kind": "fractional", "bytes": 8, "nullable": True},
        {"columns": "key", "kind": "integral", "bytes": 8, "nullable": False}]},
        "suite": {"analyzers": [{"analyzer": "Mean", "args": ["c0"]},
                                {"analyzer": "Mean", "args": ["c1"],
                                 "where": "key >= 5"}],
                  "check": {"constraints": []}}}
    ctx = {"cell": cell, "rows_per_op": 1_000_000,
           "peaks": {"hbm_bytes_per_s": 26e9},
           "trace": {"busy_s": 0.02, "window_s": 1.0, "traced_ops": 2}}
    # (9 + 9 + 8) B x 1M rows = 26 MB -> 1 ms at 26 GB/s; busy 10 ms an op
    assert scan_hbm_roofline.read(ctx) == pytest.approx(10.0)
    ctx["trace"] = {}
    assert scan_hbm_roofline.read(ctx) is None


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce_trace({"devices": {}, "host": HOST}, "suite.run") == {}


def test_two_clocks_or_no_host_span_is_an_error_not_an_idle_share():
    devices = TRACE["devices"]
    with pytest.raises(tr.TraceError, match="no 'chipbench.traced' span"):
        tr.reduce_trace({"devices": devices, "host": HOST[1:]}, "suite.run")
    apart = [("chipbench.traced", 500.0, 510.0), ("suite.run", 500.5, 505.0)]
    with pytest.raises(tr.TraceError, match="two clocks"):
        tr.reduce_trace({"devices": devices, "host": apart}, "suite.run")
    # the traced operation's name is the caller's: another loop, no edit here
    other = [("chipbench.traced", 0.0, 10.0), ("window.close", 0.5, 5.0)]
    r = tr.reduce_trace({"devices": devices, "host": other}, "window.close")
    assert r["traced_ops"] == 1
