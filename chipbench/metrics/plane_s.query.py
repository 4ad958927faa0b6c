"""Host seconds per tick in the query plane (``plane.admit``,
``plane.build_batch``, ``plane.apply``, ``plane.retire``), from the
program's spans."""
from chipbench.metrics._spans import per_tick, seconds


def read(rd):
    got = per_tick(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "plane.admit", "plane.build_batch", "plane.apply",
                   "plane.retire") / n
