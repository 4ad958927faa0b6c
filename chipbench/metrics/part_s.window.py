"""Seconds per window in the per-stream hybrid step (``executor.on_part``:
weight solve, combine, RMSEs and the window record), from the program's
spans."""
from chipbench.metrics._spans import per_window, seconds


def read(rd):
    got = per_window(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "executor.on_part") / n
