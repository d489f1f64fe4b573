"""host_gap_ms: host time between foreground steps, per step, over the
untraced window: the window's wall time less the loop's own step times
(dispatch to the loss being ready), over the window's steps."""


def read(rec):
    if not rec["window_steps"]:
        return None
    gap = rec["window_s"] - sum(rec["step_times"])
    return 1e3 * gap / rec["window_steps"]
