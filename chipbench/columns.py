"""The plain form of a table between generator, drivers and reference:
``{"rows": n, "columns": [{"name", "kind", ...arrays}]}``; numpy only."""

from __future__ import annotations

import os


def pool_size() -> int:
    """Threads for numpy work that releases the GIL: the cores, capped."""
    return max(2, min(16, os.cpu_count() or 8))


def slice_rows(data: dict, start: int, stop: int) -> dict:
    """Rows ``[start, stop)`` of a generated table (views, shared dictionaries)."""
    columns = []
    for c in data["columns"]:
        out = {"name": c["name"], "kind": c["kind"]}
        if c["kind"] == "string":
            out["codes"] = c["codes"][start:stop]
            out["dictionary"] = c["dictionary"]
        else:
            out["values"] = c["values"][start:stop]
            out["mask"] = None if c["mask"] is None else c["mask"][start:stop]
        columns.append(out)
    return {"rows": stop - start, "columns": columns}
