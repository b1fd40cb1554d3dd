"""A suite file (``chipbench/suites/<name>.json``) made into the program's
objects: analyzers by class name from ``deequ_tpu.analyzers``, constraints
by method name on ``Check`` with the assertion ``lo <= v <= hi``, the
anomaly strategy by class name from ``deequ_tpu.anomaly``. The only module
besides the drivers that touches the program."""

from __future__ import annotations


def _analyzer(entry: dict):
    from deequ_tpu import analyzers

    cls = getattr(analyzers, entry["analyzer"])
    kwargs = {"where": entry["where"]} if entry.get("where") else {}
    return cls(*entry["args"], **kwargs)


def _between(lo, hi):
    return lambda v: (lo is None or v >= lo) and (hi is None or v <= hi)


def analyzers_of(suite: dict) -> list:
    """One analyzer per entry of ``suite["analyzers"]``, in order."""
    return [_analyzer(e) for e in suite["analyzers"]]


def check_of(suite: dict, rows: int):
    from deequ_tpu import Check, CheckLevel

    spec = suite["check"]
    check = Check(CheckLevel[spec["level"]], spec["description"])
    for c in spec["constraints"]:
        lo, hi = (rows if b == "rows" else b for b in (c["lo"], c["hi"]))
        check = getattr(check, c["method"])(*c["args"], _between(lo, hi))
        if c.get("where"):
            check = check.where(c["where"])
    return check


def anomaly_of(suite: dict):
    """``(strategy, analyzer, config)`` for ``add_anomaly_check``, or None."""
    spec = suite.get("anomaly_check")
    if not spec:
        return None
    from deequ_tpu import CheckLevel, anomaly
    from deequ_tpu.verification import AnomalyCheckConfig

    strategy = getattr(anomaly, spec["strategy"])(**spec["params"])
    return (strategy, _analyzer(spec["analyzer"]),
            AnomalyCheckConfig(CheckLevel[spec["level"]],
                               f"anomaly {spec['strategy']}"))


def table_of(data: dict):
    """Generated columns wrapped as the program's ColumnarTable."""
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    kinds = {"fractional": DType.FRACTIONAL, "integral": DType.INTEGRAL}
    cols = []
    for c in data["columns"]:
        if c["kind"] == "string":
            cols.append(Column(c["name"], DType.STRING, codes=c["codes"],
                               dictionary=c["dictionary"]))
        elif c["mask"] is None:
            cols.append(Column(c["name"], kinds[c["kind"]], values=c["values"]))
        else:
            cols.append(Column(c["name"], kinds[c["kind"]], values=c["values"],
                               mask=c["mask"]))
    return ColumnarTable(cols)


def _plain(value):
    """A metric's value as a float, or ``{bin: count}`` for a histogram."""
    if hasattr(value, "values"):
        return {k: int(v.absolute) for k, v in value.values.items()}
    return float(value)


def answers_of(result, analyzers: list) -> dict:
    """What one ``.run()`` returned, as plain values: per analyzer entry the
    value (None where the metric failed), and the verdict rows."""
    values, failed = [], []
    for a in analyzers:
        metric = result.metrics.get(a)
        ok = metric is not None and metric.value.is_success
        values.append(_plain(metric.value.get()) if ok else None)
        if not ok:
            failed.append(str(a))
    rows = []
    for check_result in result.check_results.values():
        for cr in check_result.constraint_results:
            rows.append((check_result.status.value, cr.status.value))
    return {"values": values, "failed": failed, "verdict_rows": rows}
