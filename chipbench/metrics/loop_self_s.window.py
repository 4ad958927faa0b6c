"""Host seconds per window in the executor's event loop that no program
span covers (the ``loop`` record's self time): event dispatch, the bus,
and the handlers without a span of their own."""
from chipbench.metrics._spans import per_window


def read(rd):
    got = per_window(rd)
    if got is None or "loop" not in got[0]:
        return None
    tot, n = got
    return tot["loop"].self_s / n
