"""The device's idle time while the host had work enqueued for it, in
milliseconds a suite: the idle time per traced operation (the trace's
window less the union of the device-op intervals), less what the
window's counters say the host spent with nothing dispatched
(``unfed_seconds``: the program's own seams) and what the benchmark spent
inside its span around the run (``run_span_seconds`` less
``run_seconds``). What is left is idle the host did not cause by
withholding work: transfers in, launch latency, bubbles between chunk
steps. The traced operations run behind the window the counters cover, so
the difference may read a little under zero: it is reported as it
stands. Nothing to read without a trace, or from a program that lacks
one of the counters."""

COUNTERS = ("unfed_seconds", "run_span_seconds", "run_seconds", "suites")


def read(ctx: dict):
    t = ctx.get("trace") or {}
    c = ctx.get("counters") or {}
    if not t.get("window_s") or not t.get("traced_ops"):
        return None
    if any(name not in c for name in COUNTERS) or not c["suites"]:
        return None
    idle_ms = 1000.0 * (t["window_s"] - t["busy_s"]) / t["traced_ops"]
    host_s = c["unfed_seconds"] + c["run_span_seconds"] - c["run_seconds"]
    return idle_ms - 1000.0 * host_s / c["suites"]
