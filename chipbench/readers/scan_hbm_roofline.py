"""The least time to read, once and at the chip's peak HBM bandwidth
(``chipbench/peaks.json``), the bytes the suite needs
(``chipbench/work.py``), over the device's busy time per traced
operation. Bound by bytes: the suite's arithmetic per byte is far below
the chip's FLOP/byte."""

from chipbench import work


def read(ctx: dict):
    t = ctx.get("trace") or {}
    if not t.get("busy_s") or not t.get("traced_ops"):
        return None
    cell = ctx["cell"]
    need = work.suite_bytes(cell["config"], cell["suite"], ctx["rows_per_op"])
    least_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / t["traced_ops"])
