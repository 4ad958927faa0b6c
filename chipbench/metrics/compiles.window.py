"""Programs compiled or loaded inside the timed windows (the program's
``compile:<function>`` records); 0 once set-up warmed every shape."""
from chipbench.metrics._spans import compiles, per_window


def read(rd):
    got = per_window(rd)
    return None if got is None else compiles(got[0])
