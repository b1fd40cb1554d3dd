"""From the profiler's trace (``.xplane.pb``) to numbers: the union of the
intervals in which an operation ran on the device, the idle share, device
time by operation, and the longest idle gaps by what the host was doing
(the benchmark's own ``TraceAnnotation`` spans, which the profiler writes
on the same clock). The arithmetic works on plain ``(name, start, end)``
tuples in seconds, so a small synthetic trace checks it by hand."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACED = "chipbench.traced"  # the harness's span around the traced operations


class TraceError(Exception):
    """The trace cannot be reduced to a number that means what it says."""


def read_xplane(trace_dir: str, span_names) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}``; every event ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    profile = ProfileData.from_file(paths[-1])
    devices, host, seen = {}, [], []
    for plane in profile.planes:
        seen.append(plane.name)
        if plane.name.startswith(DEVICE_PLANE):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "host": host, "planes": seen}


def union(intervals) -> list:
    """Sorted disjoint ``(start, end)`` covering the same time."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(
        [(s, e) for _, s, e in events], lo, hi)))


def self_seconds(events, lo: float, hi: float) -> dict:
    """Exclusive device time by name: an operation that encloses others
    (a loop around its body) keeps only what its children leave."""
    spans = sorted(
        ((max(s, lo), min(e, hi), n) for n, s, e in events
         if min(e, hi) > max(s, lo)),
        key=lambda x: (x[0], -x[1]))
    out, stack = {}, []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, n, child = stack.pop()
            out[n] = out.get(n, 0.0) + (e - s) - child
            if stack:
                stack[-1][3] += e - s

    for s, e, n in spans:
        close(s)
        if stack and e > stack[-1][1]:
            e = stack[-1][1]
        stack.append([s, e, n, 0.0])
    close(float("inf"))
    return out


def idle_gaps(events, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch of the window with no device op."""
    gaps, at = [], lo
    for s, e in union(clip([(s, e) for _, s, e in events], lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute(gaps, host_spans) -> dict:
    """Idle seconds by the host span that covers each stretch of a gap:
    the innermost (shortest) span open there, ``(no span)`` elsewhere."""
    out = {}
    for gs, ge in gaps:
        cuts = sorted({gs, ge} | {t for _, s, e in host_spans
                                  for t in (s, e) if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            open_ = [(e - s, n) for n, s, e in host_spans if s <= mid < e]
            name = min(open_)[1] if open_ else "(no span)"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def short_name(name: str, cap: int = 120) -> str:
    """An HLO operation's text without its layouts, cut to ``cap``."""
    return re.sub(r"\{[^{}]*\}", "", name)[:cap]


def _top(table: dict, n: int = 10) -> list:
    return [[short_name(k), v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(trace: dict, op_name: str, chips: int = 1) -> dict:
    """``busy_s`` (averaged over the chips used), ``window_s``, device time
    by operation and idle time by host span, over the traced window: the
    harness's ``chipbench.traced`` span. ``op_name`` is the span around each
    traced operation. A trace with no device plane (a CPU) reduces to
    nothing; one whose host span is missing, or does not overlap the
    device's events (two clocks), is an error: an idle share read off the
    device's own span would be about 0 whatever happened."""
    planes = sorted(trace["devices"].items())[:chips]
    if not planes or not any(p["ops"] for _, p in planes):
        return {}
    traced = [(s, e) for n, s, e in trace["host"] if n == TRACED]
    all_ops = [ev for _, p in planes for ev in p["ops"]]
    dev_lo = min(s for _, s, _ in all_ops)
    dev_hi = max(e for _, _, e in all_ops)
    if not traced:
        raise TraceError(f"the trace has no {TRACED!r} span")
    lo, hi = traced[0]
    if min(hi, dev_hi) <= max(lo, dev_lo):
        raise TraceError(
            f"host span {lo:.3f}..{hi:.3f} s and device events "
            f"{dev_lo:.3f}..{dev_hi:.3f} s do not overlap: two clocks")
    busy = [busy_seconds(p["ops"], lo, hi) for _, p in planes]
    ops, gaps, modules = {}, {}, {}
    for _, p in planes:
        for name, secs in self_seconds(p["ops"], lo, hi).items():
            ops[name] = ops.get(name, 0.0) + secs / len(planes)
        for n, s, e in p["modules"]:
            if min(e, hi) > max(s, lo):
                modules[n] = modules.get(n, 0.0) + (
                    min(e, hi) - max(s, lo)) / len(planes)
        for name, secs in attribute(
                idle_gaps(p["ops"], lo, hi), trace["host"]).items():
            gaps[name] = gaps.get(name, 0.0) + secs / len(planes)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "device_ops": _top(ops),
        "device_modules": _top(modules),
        "idle_gaps": _top(gaps),
        "traced_ops": sum(1 for n, s, e in trace["host"]
                          if n == op_name and s >= lo and e <= hi),
    }
