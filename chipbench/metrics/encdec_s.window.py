"""Seconds a window of the fleet in ``windows.encdec``, building the
streams' encoder-decoder samples (``core/windows.py``), from the program's
spans over the timed ``run`` call: the mean build times the streams.  The
executor builds every window's samples before its event loop starts, so
the build lies in the run but outside ``window_s``'s clock.  A program
that keeps no such records reads as nothing."""
from chipbench.metrics._spans import _recorder


def read(rd):
    tracing = _recorder()
    span = rd.get("run_span")
    if tracing is None or span is None:
        return None
    tot = tracing.totals(*span)
    t = tot and tot.get("windows.encdec")
    if not t:
        return None
    return t.seconds / t.count * rd["streams"]
