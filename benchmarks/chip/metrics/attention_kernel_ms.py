"""attention_kernel_ms: device time of the Pallas flash attention kernels
(``flash_attention_fwd``, ``flash_attention_bwd_dq``,
``flash_attention_bwd_dkv``: an op event is named by its HLO instruction,
which takes the kernel's name) inside the foreground step program's runs,
per traced step, the mean over the cell's chips.  None where no such op
ran, as in a program that does not call the kernels."""
import re

from chipbench import trace

KERNEL = re.compile(r"%?flash_attention_(fwd|bwd_dq|bwd_dkv)(\.\d+)*$")


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    total = 0.0
    for dev in red["devices"]:
        fg = trace.step_programs({"devices": [dev], "steps": red["steps"]},
                                 rec["max_inflight"]).get("fg", [])
        for s, e, name, *_ in dev["ops"]:
            if KERNEL.fullmatch(name.split(" = ", 1)[0].strip()) and any(
                    a <= s and e <= b for a, b in fg):
                total += e - s
    if not total:
        return None
    return total / len(red["devices"]) / red["steps"] / 1e6
