"""1 - (union of device-op intervals) / (traced window), from the trace."""


def read(ctx: dict):
    t = ctx.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
