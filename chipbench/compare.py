"""The comparison that decides ``correct``: what the timed operations
returned against the plain reference, every answer of every operation of
the window. Each number compared has a limit of its own (PERF.md gives the
readings each was set from); ``correct`` is true where every number is
within its limit."""

from __future__ import annotations

from chipbench import reference


def limits_of(config: dict) -> dict:
    """The limits, from the guarantees the configuration states."""
    g = config["guarantees"]
    limits = {
        "operations_failed": 0,
        "failed_metrics": 0,
        "exact_mismatches": 0,
        "moment_rel": g["moment_rel_error"],
        "verdict_mismatches": 0,
        "degradation_events": g["degradation_events"],
    }
    if "quantile_rank_error" in g:  # ApproxQuantile's relative_error, as stated
        limits["quantile_rank"] = g["quantile_rank_error"]
    return limits


def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(1.0, abs(want))


def compare_answers(suite: dict, got: list, want: list, numbers: dict,
                    notes: list, what: str) -> None:
    """One operation's values against the reference's, into ``numbers``."""
    for entry, g, w in zip(suite["analyzers"], got, want):
        kind = entry["analyzer"]
        name = f"{what}: {kind}{tuple(entry['args'])}"
        if g is None:
            numbers["failed_metrics"] += 1
            notes.append(f"{name} gave no value")
        elif isinstance(w, tuple):
            if "quantile_rank" not in numbers:
                raise KeyError("the suite has an ApproxQuantile and the "
                               "configuration states no quantile_rank_error")
            _, count_le, n, q = w[:4]
            err = abs(count_le(g) / n - q)
            numbers["quantile_rank"] = max(numbers["quantile_rank"], err)
        elif kind in reference.EXACT:
            if g != w:
                numbers["exact_mismatches"] += 1
                notes.append(f"{name}: {g!r} != {w!r}")
        else:
            err = _rel(g, w)
            if err > numbers["moment_rel"]:
                numbers["moment_rel"] = err
                numbers["_worst_moment"] = f"{name}: {g!r} vs {w!r}"


def decide(config: dict, suite: dict, records: list, reference_answers: list,
           expected_verdicts: list, failed_ops: int, degradations: int,
           errors=()) -> dict:
    """``{"correct": bool, "checks": {name: {"value", "limit"}}, "notes"}``
    over all operations of the window. ``reference_answers[i]`` and
    ``expected_verdicts[i]`` belong to ``records[i]``."""
    limits = limits_of(config)
    numbers = dict.fromkeys(limits, 0)
    numbers.update(moment_rel=0.0, operations_failed=failed_ops,
                   degradation_events=degradations)
    notes = list(errors)
    for rec, want, verdicts in zip(records, reference_answers,
                                   expected_verdicts):
        what = f"op {rec['k']}"
        compare_answers(suite, rec["answers"]["values"], want, numbers,
                        notes, what)
        got_rows = [tuple(r) for r in rec["answers"]["verdict_rows"]]
        if got_rows != [tuple(r) for r in verdicts]:
            numbers["verdict_mismatches"] += 1
            notes.append(f"{what}: verdicts {got_rows} != {verdicts}")
    worst = numbers.pop("_worst_moment", None)
    if worst:
        notes.append(f"worst moment: {worst}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(records) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return {"correct": correct, "checks": checks, "notes": notes[:20]}


def reference_for(slices_of, config: dict, suite: dict, data: dict,
                  records: list, precision: str = "float64"):
    """The reference's answers and the verdicts they imply, per record (in
    the records' order, which is the operations' order); ``slices_of`` is
    the driver module's ``slices``."""
    slices, multiplicity = slices_of(config, data, records)
    summaries = [reference.summarize(s, suite, precision) for s in slices]
    answers, verdicts, history, cache = [], [], [], {}
    for rec, mult in zip(records, multiplicity):
        key = tuple(mult)
        if key not in cache:
            cache[key] = reference.combine(suite, summaries, list(mult))
        answers.append(cache[key])
        verdicts.append(reference.verdict_rows(
            suite, cache[key], rec["rows"], history=list(history)))
        # what the next append's anomaly check finds in the repository
        history.append(reference.anomaly_value(suite, cache[key]))
    return answers, verdicts


def control_verdict(slices_of, config: dict, suite: dict, data: dict,
                    records: list) -> dict:
    """The CONTROL: the reference computed in float32 put in the program's
    place (its answers as the operations' answers, the verdicts as they
    should be) and held to the same comparison. It has to come out not
    correct. ``records`` need ``k`` and ``rows`` only."""
    want, verdicts = reference_for(slices_of, config, suite, data, records)
    control, _ = reference_for(slices_of, config, suite, data, records,
                               precision="float32")
    placed = [
        dict(rec, answers={
            "values": [reference.quantile_value(a) if isinstance(a, tuple)
                       else a for a in answers],
            "verdict_rows": v})
        for rec, answers, v in zip(records, control, verdicts)
    ]
    return decide(config, suite, placed, want, verdicts, 0, 0)
