"""Seconds per tick waiting for the fleet predict's answers to reach the
host (``fleet.predict.wait``), from the program's spans."""
from chipbench.metrics._spans import per_tick, seconds


def read(rd):
    got = per_tick(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "fleet.predict.wait") / n
