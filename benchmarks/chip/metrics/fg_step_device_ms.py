"""fg_step_device_ms: device time of the foreground step program per
traced step, the mean over the cell's chips."""
from chipbench import trace


def read(rec):
    if rec["trace"] is None:
        return None
    fg = trace.step_programs(rec["trace"], rec["max_inflight"]).get("fg")
    if not fg:
        return None
    return sum(e - s for s, e in fg) / len(fg) / 1e6
