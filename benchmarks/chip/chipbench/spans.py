"""The training loop's host spans, read from a reduced trace.

The loop marks each phase of a step with a host span ``train.<phase>``
(``jax.profiler.TraceAnnotation``, with the step as its ``step`` stat) on
the thread that drives the chips, the thread ``trace.reduce`` keeps.  The
chip's idle time that lies outside every program run (the ``XLA Modules``
line) is the time it waited on the host; the span it falls under says for
what.  A bubble between ops inside a program is the device's own and is
not counted.  Against a program without the spans, each reading is None.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Optional

from chipbench import trace

SPAN_PREFIX = "train."


def idle_by_span(red: dict) -> Optional[dict]:
    """{host span name: chip-idle ms per traced step under it} of the idle
    time outside every program run of a chip, the mean over chips; idle
    under no ``train.*`` span counts under None.  None where the host has
    no ``train.*`` span."""
    spans = defaultdict(list)
    for s, e, name, *_ in red["host"]:
        if name.startswith(SPAN_PREFIX):
            spans[name].append((s, e))
    if not spans:
        return None
    out = defaultdict(float)
    scale = 1e6 * len(red["devices"]) * red["steps"]
    for dev in red["devices"]:
        for s, e in trace.gaps(dev["modules"] + dev["ops"], red["lo"],
                               red["hi"]):
            rest = e - s
            for name, ivs in spans.items():
                under = trace.covered(ivs, s, e)
                out[name] += under / scale
                rest -= under
            out[None] += max(rest, 0.0) / scale
    return dict(out)


def idle_ms(rec: dict, names) -> Optional[float]:
    """A per-layer reading: the chip-idle ms per traced step outside every
    program run that falls under the host spans ``names``."""
    if rec["trace"] is None:
        return None
    split = idle_by_span(rec["trace"])
    if split is None:
        return None
    return sum(split.get(n, 0.0) for n in names)
