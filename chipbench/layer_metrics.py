"""Evaluates a per-layer metric from its data file
(``chipbench/layer_metrics/<name>.json``). ``counter_ratio``: a weighted
sum of counter deltas over the window (the program's ``SCAN_STATS`` and
the harness's own ``suites``, ``rows``, ``window_seconds``,
``run_span_seconds``, ``one``), scaled, over one of them — pure data, so a
PR that may add only data files can bring one. ``reader``: a module under
``chipbench/readers/`` that takes the number from the trace. Either
returns None where there is nothing to read, and the metric is left out."""

from __future__ import annotations

from chipbench import cells


def evaluate(spec: dict, ctx: dict):
    kind = spec["kind"]
    if kind == "counter_ratio":
        deltas = ctx["counters"]
        names = [n for n, _ in spec["terms"]] + [spec["per"]]
        if any(n not in deltas for n in names) or not deltas[spec["per"]]:
            return None
        total = sum(w * deltas[n] for n, w in spec["terms"])
        return spec.get("scale", 1.0) * total / deltas[spec["per"]]
    if kind == "reader":
        return cells.plugin("readers", spec["reader"]).read(ctx)
    raise cells.CellError(f"layer metric {spec['name']!r}: kind {kind!r}")
