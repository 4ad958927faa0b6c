"""Host seconds per tick staging the fleet predict (``fleet.predict.stage``:
stacking, buffer copies, the transfer), from the program's spans."""
from chipbench.metrics._spans import per_tick, seconds


def read(rd):
    got = per_tick(rd)
    if got is None:
        return None
    tot, n = got
    return seconds(tot, "fleet.predict.stage") / n
