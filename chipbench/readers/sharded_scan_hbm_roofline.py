"""``scan_hbm_roofline`` for a cell whose table is sharded over several
chips: the least time to read the suite's bytes (``chipbench/work.py``)
once at the peak HBM bandwidth of ALL the cell's chips together
(``chips`` x ``chipbench/peaks.json``), over the device busy time per
traced operation (``trace_reduce`` averages it over the chips). The same
work whatever implements it; bound by bytes."""

from chipbench import work


def read(ctx: dict):
    t = ctx.get("trace") or {}
    if not t.get("busy_s") or not t.get("traced_ops"):
        return None
    cell = ctx["cell"]
    need = work.suite_bytes(cell["config"], cell["suite"], ctx["rows_per_op"])
    peak = cell["workload"]["chips"] * ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (t["busy_s"] / t["traced_ops"])
