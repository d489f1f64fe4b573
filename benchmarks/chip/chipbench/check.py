"""The comparison that decides ``correct``: the program's first training
steps against the reference's, number by number, each under its limit."""
from __future__ import annotations

import statistics

# A leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone (a key's bias under softmax is one):
# it is left out of the change after three steps.
QUIET_LEAF = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple:
    """Largest |prog norm - ref norm| over the leaves, each against the
    larger of its reference norm and the median leaf's.  Returns (gap,
    leaf)."""
    med = statistics.median(ref.values())
    keys = [k for k in ref if leaves is None or k in leaves]
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def moving_leaves(ref_grad_norms: dict) -> set:
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v >= QUIET_LEAF * med}


def compare(prog: dict, ref: dict, prefix: str = "") -> dict:
    """Numbers compared for one training role: the first clipped
    gradient's worst-leaf norm gap, the worst leaf's norm of the difference
    of the two first gradients (``ref["grad_diff_norms"]``), and the
    worst-leaf gap of the change after the steps.  Each is measured against
    the larger of the leaf's reference norm and the median leaf's.  Returns
    {name: (value, where)}."""
    out = {}
    g, leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out[f"{prefix}grad_gap"] = (g, leaf)
    med = statistics.median(ref["grad_norms"].values())
    diff = {k: v / max(ref["grad_norms"][k], med)
            for k, v in ref["grad_diff_norms"].items()}
    leaf = max(diff, key=diff.get)
    out[f"{prefix}grad_diff"] = (diff[leaf], leaf)
    d, leaf = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"],
                             moving_leaves(ref["grad_norms"]))
    out[f"{prefix}update_gap"] = (d, leaf)
    return out
