"""The program's host spans and compile records, on the profiler's clock.

``span(name)`` marks one piece of host work: it opens a
``jax.profiler.TraceAnnotation`` of that name, so a profiler trace shows it
on the same clock as the device's operations, and on exit appends one
record to a bounded ring kept for the whole process:

    (name, start, dur, self_s)

``start`` is ``time.perf_counter()`` seconds, ``dur`` the span's seconds and
``self_s`` those that no span opened inside it (in the same thread)
covered.  Every backend compile (or load from the persistent cache) becomes
a record ``compile:<function>`` that ends when JAX reports it.

Records are appended as spans close, so the ring holds them in order of
their end.  When it is full the oldest go; a reader asking for a window the
ring may no longer hold whole gets ``None``, never a partial sum.  Always
on: a span costs one to two microseconds of host time.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

# one traced window of the largest fleet (1,024 streams, 4 windows, five
# records per stream and window) with room to spare
CAPACITY = 1 << 17
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    name: str
    start: float
    dur: float
    self_s: float

    @property
    def end(self) -> float:
        return self.start + self.dur


class Total(NamedTuple):
    count: int
    seconds: float
    self_s: float


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_local = threading.local()
_now = time.perf_counter
_Annotation = jax.profiler.TraceAnnotation


class span:
    """``with span("executor.on_part"): ...`` records the block.  After the
    block, ``start`` and ``dur`` hold what was recorded."""

    __slots__ = ("name", "start", "dur", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        # the annotation costs about a microsecond even with no profiler
        # to see it, so it is made only while one is
        if _Annotation.is_enabled():
            self._ann = ann = _Annotation(self.name)
            ann.__enter__()
        else:
            self._ann = None
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        # seconds of child spans closed inside this one so far
        stack.append(0.0)
        self.start = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = dur = _now() - self.start
        stack = _local.stack
        children = stack.pop()
        if stack:
            stack[-1] += dur
        _ring.append((self.name, self.start, dur, dur - children))
        if self._ann is not None:
            self._ann.__exit__(*exc)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _ring.append(("compile:" + str(kw.get("fun_name", "?")),
                      _now() - duration, duration, duration))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def records(since: Optional[float] = None,
            until: Optional[float] = None) -> Optional[List[Record]]:
    """The records that lie within ``[since, until]`` (``perf_counter``
    seconds; open where None), in order of their end.  None when the ring
    may have dropped a record that ended after ``since``: the window is no
    longer whole.  With ``since`` None, whatever the ring holds."""
    held = list(_ring)
    # records enter in order of their end, so every dropped one ended
    # before the oldest held one did
    if (since is not None and len(held) == _ring.maxlen
            and since < held[0][1] + held[0][2]):
        return None
    lo = float("-inf") if since is None else since
    hi = float("inf") if until is None else until
    return [Record(*r) for r in held if r[1] >= lo and r[1] + r[2] <= hi]


def totals(since: Optional[float] = None,
           until: Optional[float] = None) -> Optional[Dict[str, Total]]:
    """Count, seconds and self seconds per name over ``records(since,
    until)``; None where that is None."""
    recs = records(since, until)
    if recs is None:
        return None
    acc: Dict[str, List[float]] = {}
    for r in recs:
        a = acc.setdefault(r.name, [0, 0.0, 0.0])
        a[0] += 1
        a[1] += r.dur
        a[2] += r.self_s
    return {k: Total(int(c), s, x) for k, (c, s, x) in acc.items()}
