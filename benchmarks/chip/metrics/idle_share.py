"""idle_share: 1 - (union of op intervals on a chip / traced window), the
mean over the cell's chips, in %."""


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    return 100 * (1 - red["busy_s"] / red["window_s"])
