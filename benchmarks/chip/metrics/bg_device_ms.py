"""bg_device_ms: device time of one background tenant step, the mean over
its executions in the traced window.  The fg and bg steps share a program
name; the bg program is the one run max_inflight times per traced step."""
from chipbench import trace


def read(rec):
    if rec["trace"] is None:
        return None
    bg = trace.step_programs(rec["trace"], rec["max_inflight"]).get("bg")
    if not bg:
        return None
    return sum(e - s for s, e in bg) / len(bg) / 1e6
