"""Where one operation of a chipbench cell spends its time, by the
program's own names (PERF.md section 5 is written from this):

    python benchmarks/seam_split.py --workload <cell> --seed <n> --seconds <s>

One process on the chip: the cell's set-up, a window like the benchmark's,
then a few traced operations behind it. Prints and writes (``--out``) one
JSON object:

- ``seam_ms_per_op`` / ``seam_count_per_op``: the exclusive milliseconds and
  the openings of every seam per operation of the window
  (``SCAN_STATS.seam_*`` deltas; ``fetch.copy`` is a part of ``fetch``), and
  beside them ``run_own_ms_per_op`` (the two enclosing seams' own time),
  ``harness_ms_per_op`` (the benchmark's span less the root seams' wall) and
  ``unfed_ms_per_op`` (``SCAN_STATS.unfed_seconds``: seam time with nothing
  dispatched to the device);
- ``persist``: ``persist.pack`` / ``persist.stage`` seconds of the set-up;
- ``idle_gap_s_by_seam``: the device's idle stretches inside the traced
  window by the innermost ``deequ.*`` host span open there (the seams are
  ``TraceAnnotation``s on the device's clock; ``deequ.fetch.copy`` is a span
  of its own inside ``deequ.fetch``);
- ``unfed``: the check that the host's belief and the device's clock agree.
  The feed gauge rebuilt from the trace (up at the end of each
  ``deequ.dispatch``, down at the start of each ``deequ.fetch.copy``: every
  fetch of a chipbench cell is its scan's last) gives the unfed stretches of
  the ``deequ.run`` spans; ``trace_idle_ms_per_op`` is the device's idle time
  inside them per traced operation, ``counter_ms_per_op`` the window's
  ``unfed_ms_per_op``, ``difference_ms_per_op`` the first less the second;
- ``host_span_s_by_seam``: the seams' own durations in the trace;
- ``device_s_by_scope`` / ``device_s_by_family``: exclusive device seconds by
  the ``jax.named_scope`` of each XLA op (``deequ.<Analyzer>.<column>``),
  read from the ``tf_op`` stat of each ``XLA Ops`` event's metadata: the
  INNERMOST ``deequ.*`` scope of the name (``deequ.select.pass2`` inside the
  coalesced op's ``deequ.select.<columns>``); a fusion carries ONE op's
  name, so a fused pass over several analyzers' reductions counts under
  one of them.

It reads the benchmark's cells and its trace arithmetic
(``chipbench.trace_reduce``) and changes nothing of either. Off a TPU it
refuses, like the benchmark."""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPE = re.compile(r"deequ\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")


SCOPE_STAT = "tf_op"  # the XLA op's op_name metadata, named scopes included


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of one protobuf message's fields: a varint as an
    int, a length-delimited field as a memoryview of its bytes (fixed-width
    fields are skipped: nothing read here has one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wire}")


def _map_entry(buf):
    """``(key, value bytes)`` of one ``map<int64, Message>`` entry."""
    key = value = None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def device_events_by_scope(trace_dir: str):
    """``(events, census, sample)``: the ``XLA Ops`` of the first device
    plane as ``(scope, start_s, end_s)``; how many events had a
    ``deequ.*`` scope in the ``tf_op`` stat of their METADATA
    (``XEventMetadata.stats`` — ``jax.profiler.ProfileData`` shows an
    event's own stats only, so the file's wire format is read here:
    XSpace.planes=1; XPlane name=2 lines=3 event_metadata=4
    stat_metadata=5; XLine name=2 timestamp_ns=3 events=4; XEvent
    metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata name=2
    stats=5; XStat metadata_id=1 str_value=5 ref_value=7); and the first
    few ``tf_op`` values as they stand."""
    from chipbench import trace_reduce

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in _fields(space):
        if number != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in parts if n == 2), "")
        if name.startswith(trace_reduce.DEVICE_PLANE):
            break
    else:
        return [], {}, []
    stat_names = {}
    for n, v in parts:
        if n == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (bytes(x).decode() for m, x in _fields(meta) if m == 2), "")
    op_names = {}  # event metadata id -> its tf_op
    for n, v in parts:
        if n != 4:
            continue
        key, meta = _map_entry(v)
        for m, stat in _fields(meta):
            if m != 5:
                continue
            fields = dict(_fields(stat))
            if stat_names.get(fields.get(1)) != SCOPE_STAT:
                continue
            if 5 in fields:
                op_names[key] = bytes(fields[5]).decode()
            elif 7 in fields:
                op_names[key] = stat_names.get(fields[7], "")
    events, census, sample = [], {"scoped": 0, "unscoped": 0}, []
    for n, line in parts:
        if n != 3:
            continue
        line_fields = list(_fields(line))
        if next((bytes(v).decode() for m, v in line_fields if m == 2),
                "") != trace_reduce.OPS_LINE:
            continue
        t0_ps = 1000 * next((v for m, v in line_fields if m == 3), 0)
        for m, event in line_fields:
            if m != 4:
                continue
            e = dict(_fields(event))
            op_name = op_names.get(e.get(1), "")
            found = SCOPE.findall(op_name)  # outermost first
            census["scoped" if found else "unscoped"] += 1
            if len(sample) < 5 and op_name not in sample:
                sample.append(op_name)
            start = (t0_ps + e.get(2, 0)) * 1e-12
            events.append((found[-1] if found else "(no scope)",
                           start, start + e.get(3, 0) * 1e-12))
    return events, census, sample


def overlap(a, b) -> list:
    """The time two sorted lists of disjoint ``(start, end)`` share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def unfed_stretches(host, lo: float, hi: float) -> list:
    """The stretches of the root seams between ``lo`` and ``hi`` in which
    the program's feed gauge stood at zero, rebuilt from the seams' spans
    on the profiler's clock."""
    from chipbench import trace_reduce

    marks = sorted(
        [(e, True) for n, _, e in host if n == "deequ.dispatch"]
        + [(s, False) for n, s, _ in host if n == "deequ.fetch.copy"])
    out, fed, at = [], False, lo
    for t, up in marks:
        if up and not fed:
            out.append((at, t))
        elif not up and fed:
            at = t
        fed = up
    if not fed:
        out.append((at, hi))
    roots = trace_reduce.union(
        (s, e) for n, s, e in host if n == "deequ.run")
    return overlap(trace_reduce.clip(out, lo, hi), roots)


def family(scope: str) -> str:
    """``deequ.Mean.c3`` -> ``Mean``; ``deequ.unpack`` -> ``unpack``."""
    parts = scope.split(".")
    return parts[1] if len(parts) > 1 else scope


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--keep-xplane", default=None,
                        help="copy the trace's .xplane.pb to this path")
    args = parser.parse_args(argv)

    import deequ_tpu  # noqa: F401 — x64 and the compile cache
    from chipbench import cells, run as bench, trace_reduce
    from chipbench.drivers.common import Window, counters
    from deequ_tpu.obs import SEAM_NAMES, seam_fields

    cell = cells.load_cell(args.workload)
    bench.find_device(cell["workload"]["chips"])
    config, traffic = cell["config"], cell["traffic"]
    data = cells.plugin("generators", config["generator"]).generate(
        config["rows"], args.seed, config["generator_params"])
    driver = cells.plugin("drivers", traffic["driver"]).Driver(
        config, traffic, cell["suite"], data)
    before = counters()
    phases = driver.prepare()
    after = counters()
    persist = {name: after[seam_fields(name)[0]] - before[seam_fields(name)[0]]
               for name in ("persist.pack", "persist.stage")}

    tracer = bench.Tracer()
    window = Window(args.seconds, bench.TRACE_OPS, tracer.start, tracer.stop)
    driver.window(window)
    totals = window.totals()
    ops = totals["suites"]
    seam_ms = {name: 1000.0 * totals[seam_fields(name)[0]] / ops
               for name in SEAM_NAMES}
    seam_count = {name: totals[seam_fields(name)[1]] / ops
                  for name in SEAM_NAMES}
    span_ms = 1000.0 * totals["run_span_seconds"] / ops

    names = ["deequ." + n for n in SEAM_NAMES]
    names += [trace_reduce.TRACED, window.op_name]
    trace = trace_reduce.read_xplane(tracer.dir, names)
    lo, hi = next((s, e) for n, s, e in trace["host"]
                  if n == trace_reduce.TRACED)
    device_ops = [ev for _, p in sorted(trace["devices"].items())[:1]
                  for ev in p["ops"]]
    idle = trace_reduce.idle_gaps(device_ops, lo, hi)
    gaps = trace_reduce.attribute(idle, trace["host"])
    unfed = unfed_stretches(trace["host"], lo, hi)
    seconds = lambda spans: sum(e - s for s, e in spans)  # noqa: E731
    host_spans = {}
    for n, s, e in trace["host"]:
        if s >= lo and e <= hi:
            host_spans[n] = host_spans.get(n, 0.0) + (e - s)
    traced_ops = sum(1 for n, s, e in trace["host"]
                     if n == window.op_name and s >= lo and e <= hi)
    unfed_ms = 1000.0 * totals["unfed_seconds"] / ops
    trace_idle_ms = 1000.0 * seconds(overlap(idle, unfed)) / max(traced_ops, 1)
    events, census, sample = device_events_by_scope(tracer.dir)
    by_scope = trace_reduce.self_seconds(events, lo, hi)
    by_family = {}
    for scope, secs in by_scope.items():
        by_family[family(scope)] = by_family.get(family(scope), 0.0) + secs
    busy = trace_reduce.busy_seconds(device_ops, lo, hi)
    driver.release()
    if args.keep_xplane:
        import shutil

        os.makedirs(os.path.dirname(os.path.abspath(args.keep_xplane)),
                    exist_ok=True)
        shutil.copy(sorted(glob.glob(os.path.join(
            tracer.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1],
            args.keep_xplane)
    tracer.reduce(window.op_name, 1)  # removes the trace directory

    ordered = lambda table: dict(sorted(table.items(), key=lambda kv: -kv[1]))
    result = {
        "workload": args.workload, "seed": args.seed,
        "operations": ops, "span_ms_per_op": span_ms,
        "host_ms_per_op": span_ms - 1000.0 * (
            totals["dispatch_seconds"] + totals["drain_wait_seconds"]) / ops,
        "seam_ms_per_op": seam_ms, "seam_count_per_op": seam_count,
        "run_own_ms_per_op": seam_ms["run"] + seam_ms["scan_attempt"],
        "harness_ms_per_op": span_ms - 1000.0 * totals["run_seconds"] / ops,
        "unfed_ms_per_op": unfed_ms,
        "setup": dict(phases), "persist": persist,
        "traced": {"window_s": hi - lo, "busy_s": busy,
                   "operations": traced_ops},
        "idle_gap_s_by_seam": ordered(gaps),
        "unfed": {
            "counter_ms_per_op": unfed_ms,
            "trace_unfed_span_ms_per_op": (
                1000.0 * seconds(unfed) / max(traced_ops, 1)),
            "trace_idle_ms_per_op": trace_idle_ms,
            "difference_ms_per_op": trace_idle_ms - unfed_ms,
        },
        "host_span_s_by_seam": ordered(host_spans),
        "device_s_by_family": ordered(by_family),
        "device_s_by_scope": dict(list(ordered(by_scope).items())[:40]),
        "scope_stat": SCOPE_STAT, "scope_census": census,
        "tf_op_sample": sample,
    }
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
