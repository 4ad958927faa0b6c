"""Programs compiled or loaded among the timed ticks (the program's
``compile:<function>`` records); 0 once set-up warmed every shape."""
from chipbench.metrics._spans import compiles, per_tick


def read(rd):
    got = per_tick(rd)
    return None if got is None else compiles(got[0])
