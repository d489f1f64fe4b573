"""collective_exposed_ms: collective time on a chip during which no other op
of that chip runs (``trace.exposed``), inside the foreground step
program's runs, per traced step, the mean over the cell's chips.  None
where the fg program ran no collective, as on one chip."""
from chipbench import trace


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    total, seen = 0.0, False
    for dev in red["devices"]:
        fg = trace.step_programs({"devices": [dev], "steps": red["steps"]},
                                 rec["max_inflight"]).get("fg", [])
        ops = [o for o in dev["ops"] if any(a <= o[0] and o[1] <= b
                                             for a, b in fg)]
        seen = seen or any(trace.is_collective(o[2]) for o in ops)
        total += trace.exposed(ops)
    if not seen:
        return None
    return total / len(red["devices"]) / red["steps"] / 1e6
