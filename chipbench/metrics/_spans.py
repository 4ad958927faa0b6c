"""The program's own span records (``repro.tracing``) over a cell's
timed window, for the readers beside this file.  A program that keeps no
records reads as nothing."""


def _recorder():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def per_window(rd):
    """``(totals, windows)`` over a windows cell's timed run: the last
    ``loop`` record, the executor's event loop, which lies inside the
    benchmark's ``cb:window``.  None when there is nothing to read."""
    tracing = _recorder()
    if tracing is None:
        return None
    loops = [r for r in tracing.records() if r.name == "loop"]
    if not loops:
        return None
    tot = tracing.totals(loops[-1].start, loops[-1].end)
    return None if tot is None else (tot, rd["n_windows"])


def per_tick(rd):
    """``(totals, ticks)`` over a queries cell's timed ticks: from the start
    of the n-th last ``stage.serving`` record to the end of the last, n the
    ticks the benchmark timed.  None when there is nothing to read."""
    tracing = _recorder()
    n = len(rd["spans"].walls.get("tick", []))
    if tracing is None or not n:
        return None
    serving = [r for r in tracing.records() if r.name == "stage.serving"]
    if len(serving) < n:
        return None
    tot = tracing.totals(serving[-n].start, serving[-1].end)
    return None if tot is None else (tot, n)


def seconds(tot, *names):
    return sum(tot[k].seconds for k in names if k in tot)


def compiles(tot):
    """Compile records in the window; their functions go to standard
    error, so a run names what compiled."""
    found = {k: t.count for k, t in tot.items() if k.startswith("compile:")}
    if found:
        import sys

        print(f"compiled in the window: {found}", file=sys.stderr)
    return sum(found.values())
