"""The bytes a suite needs, from the configuration's shapes alone: each
column an analyzer, a ``where`` or a constraint names is read once per
operation at its logical width (``bytes`` of the schema, plus one validity
byte per row where the column is nullable). It reads the same work
whatever implements it: a program that reads a column twice, or wider, has
not done more work."""

from __future__ import annotations

import re

_RANGE = re.compile(r"^([A-Za-z_]+)(\d+)\.\.([A-Za-z_]+)(\d+)$")


def schema_columns(config: dict) -> dict:
    """``{column: bytes per row}`` from the configuration's schema."""
    out = {}
    for group in config["schema"]:
        width = group["bytes"] + (1 if group["nullable"] else 0)
        for spec in group["columns"].split(","):
            spec = spec.strip()
            m = _RANGE.match(spec)
            if m:
                for i in range(int(m.group(2)), int(m.group(4)) + 1):
                    out[f"{m.group(1)}{i}"] = width
            else:
                out[spec] = width
    return out


def columns_named(suite: dict, columns) -> set:
    """The schema's columns that the suite's entries name anywhere."""
    words = set()
    texts = []
    for e in suite["analyzers"]:
        texts += [str(a) for a in e["args"]] + [e.get("where") or ""]
    for c in suite["check"]["constraints"]:
        texts += [str(a) for a in c["args"]] + [c.get("where") or ""]
    for t in texts:
        words.update(re.findall(r"[A-Za-z_]\w*", t))
    return {c for c in columns if c in words}


def suite_bytes(config: dict, suite: dict, rows: int) -> int:
    """Least bytes one operation over ``rows`` rows has to read."""
    widths = schema_columns(config)
    return rows * sum(widths[c] for c in columns_named(suite, widths))
