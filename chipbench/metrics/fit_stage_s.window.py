"""Seconds per window staging the fleet fit's inputs on the host
(``fleet.fit.stage``: buffer fill, key derivation, transfers), from the
program's spans."""
from chipbench.metrics._spans import per_window, seconds


def read(rd):
    got = per_window(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "fleet.fit.stage") / n
