"""One run of one cell of BENCHMARK.json on the chip:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, data from ``--seed``, ``persist()``, compile or cache load,
one warm-up of the cell's own shapes), then the measured window, then —
with the program's device state freed and the memory peak read — the
plain reference and the comparison that decides ``correct``. With
``--trace 1`` a few more operations run behind the window inside the
profiler's session: the window and its counters are an untraced run's.
One process; no CPU continuation: off a TPU it exits non-zero and prints
no result. The last line of stdout is the result object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up counts from here: before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import cells, compare, layer_metrics, trace_reduce  # noqa: E402

TRACE_OPS = 3          # operations a --trace 1 run traces, behind its window
EXIT_NO_CHIP = 3
EXIT_BAD_CELL = 4


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def find_device(chips: int) -> dict:
    """Name the device; refuse anything but a TPU with enough chips."""
    import jax

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    if device["platform"] != "tpu":
        raise SystemExit(_refuse(
            f"no TPU: jax.devices()[0].platform is {first.platform!r}; "
            "the benchmark has no CPU continuation"))
    if device["count"] < chips:
        raise SystemExit(_refuse(
            f"the cell asks {chips} chip(s), {device['count']} visible"))
    return device


def _refuse(why: str) -> int:
    _say(f"chipbench: {why}")
    return EXIT_NO_CHIP


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(_ROOT, "chipbench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise cells.CellError(
            f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


class Tracer:
    """The profiler around the operations behind the window, its output at
    a fixed place inside the checkout (emptied before each trace)."""

    def __init__(self):
        self.dir = os.path.join(_ROOT, ".chipbench_trace")
        self.span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans and device ops, no Python calls
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.span = jax.profiler.TraceAnnotation(trace_reduce.TRACED)
        self.span.__enter__()

    def stop(self) -> None:
        import jax

        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, op_name: str, chips: int) -> dict:
        try:
            return trace_reduce.reduce_trace(
                trace_reduce.read_xplane(
                    self.dir, (trace_reduce.TRACED, op_name)),
                op_name, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def compile_census() -> dict:
    """Counts jax's compile requests, persistent-cache hits and the seconds
    spent compiling, through its own monitoring events: after a cell's
    first run in a checkout a run should compile next to nothing."""
    import jax

    counts = {"requests": 0, "cache_hits": 0, "compile_s": 0.0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t0: float = None, census: dict = None) -> dict:
    """Everything after the look for a chip: set-up, window, reference,
    comparison. Returns the result object (tests drive this on the CPU)."""
    from chipbench.drivers.common import Window, degradation_events

    t0 = _T0 if t0 is None else t0
    config, traffic, suite = cell["config"], cell["traffic"], cell["suite"]
    generator = cells.plugin("generators", config["generator"])
    t_gen = time.perf_counter()
    data = generator.generate(config["rows"], seed, config["generator_params"])
    t_data = time.perf_counter()
    driver_module = cells.plugin("drivers", traffic["driver"])
    driver = driver_module.Driver(config, traffic, suite, data)
    t_driver = time.perf_counter()
    phases = {"import_and_chip_s": t_gen - t0, "data_s": t_data - t_gen,
              "tables_s": t_driver - t_data}
    phases.update(driver.prepare())
    setup_s = time.perf_counter() - t0
    if census is not None:
        phases["compile"] = dict(census)
    _say(f"chipbench: set-up {setup_s:.2f} s: " + json.dumps(phases))

    tracer = Tracer() if trace else None
    window = Window(
        seconds, TRACE_OPS if trace else 0,
        tracer.start if trace else None, tracer.stop if trace else None)
    driver.window(window)
    _say("chipbench: span_ms of each operation: " + json.dumps(
        [round(1000.0 * r["span_s"], 1) for r in window.records]))
    degradations = degradation_events()
    peak = memory_peak_bytes()
    totals = window.totals()
    driver.release()

    t_ref = time.perf_counter()
    answers, verdicts = compare.reference_for(
        driver_module.slices, config, suite, data, window.records)
    verdict = compare.decide(
        config, suite, window.records, answers, verdicts, window.failed,
        len(degradations), window.errors + [str(e) for e in degradations])
    reference_s = time.perf_counter() - t_ref

    metrics = {}
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": verdict["correct"], "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": dev}
    ctx = {"cell": cell, "counters": totals, "trace": {},
           "peaks": load_peaks(device["kind"]),
           "rows_per_op": (totals["rows"] / totals["suites"]
                           if totals["suites"] else 0)}
    if not trace:
        rate = totals["rows"] / totals["window_seconds"] if totals["rows"] else 0.0
        values = {"rows_per_s": rate, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        # the counter metrics need no profiler: beside the rate they explain,
        # under a key of their own (the per-layer line is the --trace 1 run's)
        result["layer_counters"] = {
            spec["name"]: layer_metrics.evaluate(spec, ctx)
            for spec in cell["layer_metrics"] if spec["kind"] == "counter_ratio"}
    else:
        reduced = ctx["trace"] = tracer.reduce(window.op_name,
                                               cell["workload"]["chips"])
        for spec in cell["layer_metrics"]:
            value = layer_metrics.evaluate(spec, ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        if reduced:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            result["device_modules"] = reduced["device_modules"]
            result["traced_operations"] = reduced["traced_ops"]
    result["window"] = {"operations": totals["suites"], "rows": totals["rows"],
                        "seconds": totals["window_seconds"],
                        "span_ms": window.span_ms(),
                        "reference_s": reference_s, "setup": phases}
    result["notes"] = verdict["notes"]
    result["checks"] = verdict["checks"]  # comes last: numbers beside limits
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        _say(f"chipbench: {e}")
        return EXIT_BAD_CELL

    import deequ_tpu  # noqa: F401 — x64, and the compile cache at its fixed place

    census = compile_census()
    device = find_device(cell["workload"]["chips"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                      census=census)
    for note in result["notes"]:
        _say(f"chipbench: {note}")
    _say("chipbench: " + "  ".join(
        f"{k}={c['value']!r}(limit {c['limit']!r})"
        for k, c in result["checks"].items()))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
