"""Reduction of a profiler trace (``.xplane.pb``) to intervals and sums.

The harness marks the traced window with a host span (``WINDOW_SPAN``) and
each traced step with another (``STEP_SPAN``), on the trace's own clock.
Within the window, each chip's op events (the ``XLA Ops`` line of its
``/device:TPU:<n>`` plane) give its busy time, idle gaps and collectives,
and its program events (the ``XLA Modules`` line) give each compiled
program's executions, told apart by program id where two share a name.
Everything here but ``load`` works on plain tuples, so the tests build
their traces by hand.
"""
from __future__ import annotations

import re
from collections import defaultdict

WINDOW_SPAN = "chipbench.traced_window"
STEP_SPAN = "chipbench.step"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# op names of collectives: XLA's, and the TPU's asynchronous collective
# fusions (``%async-collective-start.6 = (..) fusion(..)``, then ``-done``)
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all", "async-collective-")
CONTROL_FLOW = ("while", "conditional", "call")


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> list:
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name,
             dict(e.stats)) for e in line.events]


def planes(pd) -> dict:
    """{plane name: {line name: [(start_ns, end_ns, name, stats)]}}."""
    out = {}
    for p in pd.planes:
        lines = defaultdict(list)
        for ln in p.lines:
            lines[ln.name] += _events(ln)
        out[p.name] = dict(lines)
    return out


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def covered(intervals, lo, hi) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi) -> list:
    """Idle (start, end) stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(name: str) -> str:
    """An op event's instruction: ``%all-gather.3 = bf16[..] all-gather(..)``
    (the event is named by its whole HLO instruction) gives
    ``all-gather.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def is_collective(name: str) -> bool:
    return op_name(name).lower().startswith(COLLECTIVES)


def leaves(ops) -> list:
    """The ops that hold no other op: an XLA control-flow op's event
    (``while``, ``conditional``, ``call``) spans the events of the ops it
    runs."""
    return [o for o in ops if not op_name(o[2]).lower().startswith(
        CONTROL_FLOW)]


def exposed(ops) -> float:
    """Time of collective ops during which no other op runs (of the ops
    that hold no other op)."""
    ops = leaves(ops)
    coll = [o for o in ops if is_collective(o[2])]
    other = union([o for o in ops if not is_collective(o[2])])
    total = 0.0
    for s, e in union(coll):
        total += (e - s) - covered(other, s, e)
    return total


def program_runs(modules) -> dict:
    """{(name, program id): [(start, end)]} of the program events."""
    runs = defaultdict(list)
    for s, e, name, stats in modules:
        pid = stats.get("program_id", name)
        runs[(re.sub(r"\(\d+\)$", "", name), pid)].append((s, e))
    return dict(runs)


def reduce(pd, n_devices: int) -> dict:
    """The traced window of ``pd`` (a ProfileData, or ``planes()``'s dict)
    on its first ``n_devices`` chips."""
    ps = pd if isinstance(pd, dict) else planes(pd)
    window = host = None
    for name, lines in ps.items():
        if not name.startswith("/host:"):
            continue
        for evs in lines.values():
            spans = [ev for ev in evs if ev[2] == WINDOW_SPAN]
            if spans:
                window, host = spans[0], evs
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window[0], window[1]
    chips = sorted((int(m.group(1)), name) for name in ps
                   if (m := DEVICE_PLANE.match(name)))
    if len(chips) < n_devices:
        raise ValueError(f"{len(chips)} device planes for {n_devices} chips")
    devices = []
    for _, name in chips[:n_devices]:
        lines = ps[name]
        ops = clip(lines.get(OPS_LINE, []), lo, hi)
        devices.append({"ops": ops,
                        "modules": clip(lines.get(MODULES_LINE, []), lo, hi),
                        "busy_ns": covered(ops, lo, hi)})
    if not any(d["ops"] for d in devices):
        raise ValueError("no device op ran in the traced window")
    host = [ev for ev in clip(host, lo, hi)
            if ev[2] not in (WINDOW_SPAN, STEP_SPAN)]
    busy = sum(d["busy_ns"] for d in devices) / len(devices)
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
            "busy_s": busy / 1e9, "devices": devices, "host": host}


def breakdown(red: dict, top: int = 10) -> dict:
    """The device ops that took most time (mean over chips), and the
    longest idle gaps of the first chip, each named after the host event
    that overlaps it most."""
    n = len(red["devices"])
    per_op = defaultdict(float)
    for d in red["devices"]:
        for s, e, name, *_ in d["ops"]:
            # an op event is named by its whole HLO instruction
            per_op[name.split(" = ", 1)[0]] += (e - s) / 1e9 / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(red["devices"][0]["ops"], red["lo"], red["hi"]),
                  key=lambda g: g[0] - g[1])[:top]
    tagged = []
    for s, e in idle:
        best, name = 0.0, "no host event"
        for hs, he, hname, *_ in red["host"]:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hname
        tagged.append([name, (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": tagged}


STEP_PROGRAM = "jit_step_with_fsdp"  # the program's jitted training step


def step_programs(red: dict, inflight: int) -> dict:
    """The training step programs of the traced window, fg apart from bg.

    Both are ``jit(step_with_fsdp)`` in the program and share its name.  Per
    chip, the fg program runs once per traced step; a bg program runs
    ``inflight`` times per traced step.  Returns {"fg": [(start_ns,
    end_ns)], "bg": [...]} over all chips, each only where it ran."""
    out = {}
    for dev in red["devices"]:
        for (name, _), runs in program_runs(dev["modules"]).items():
            if not name.startswith(STEP_PROGRAM):
                continue
            if len(runs) == red["steps"]:
                out.setdefault("fg", []).extend(runs)
            elif inflight and len(runs) == inflight * red["steps"]:
                out.setdefault("bg", []).extend(runs)
    return out
