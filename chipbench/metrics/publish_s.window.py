"""Seconds per window in the model publish fan-out
(``executor.publish_models``: host copy of the stacked fit, checksums,
signatures and per-stream publishes), from the program's spans."""
from chipbench.metrics._spans import per_window, seconds


def read(rd):
    got = per_window(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "executor.publish_models") / n
