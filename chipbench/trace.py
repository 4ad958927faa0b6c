"""From a profiler trace to numbers: the device's busy time (the union of
the intervals in which an operation ran), each XLA module's device time,
the operations that took most time, and the idle gaps named by what the
benchmark's host span was doing at the time.

``load`` turns the profiler's ``.xplane.pb`` into a small plain form (lists
of ``[name, start_ns, duration_ns]``) that ``reduce`` reads, so the
reduction can be tested on a recorded trace without the chip.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "cb:"
DEVICE_PREFIX = "/device:TPU:"
NAME_CHARS = 96


def load(trace_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in plain form:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}``
    where host holds only the benchmark's ``cb:`` spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                # an op's name is its HLO line: keep the instruction and
                # the start of its shape, enough to tell ops apart
                dev[key].extend([e.name[:NAME_CHARS], e.start_ns,
                                 e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(evs, t0: float, t1: float) -> List[Tuple[float, float]]:
    return [(max(s, t0), min(s + d, t1)) for _, s, d in evs
            if s + d > t0 and s < t1]


def _module_name(name: str) -> str:
    """``jit_fleet_fit(12)`` -> ``jit_fleet_fit``."""
    return name.split("(")[0].strip()


def reduce(tr: Dict, window_span: str = SPAN_PREFIX + "window",
           top: int = 10) -> Optional[Dict]:
    """Numbers of the traced window (the first host span named
    ``window_span``), or None when the trace has no device or no window.

    ``busy_s`` and the module times are averages over the device planes;
    gaps are read on the first device."""
    wins = [e for e in tr["host"] if e[0] == window_span]
    devices = [tr["devices"][k] for k in sorted(tr["devices"])]
    if not wins or not devices:
        return None
    _, t0, d = wins[0]
    t1 = t0 + d
    busy, modules, ops = [], {}, {}
    for dev in devices:
        iv = _union(_clip(dev["ops"] or dev["modules"], t0, t1))
        busy.append(sum(b - a for a, b in iv))
        for name, s, dur in dev["modules"]:
            if s + dur > t0 and s < t1:
                m = _module_name(name)
                modules[m] = modules.get(m, 0.0) + dur / len(devices)
        for name, s, dur in dev["ops"]:
            if s + dur > t0 and s < t1:
                ops[name] = ops.get(name, 0.0) + dur / len(devices)
    iv = _union(_clip(devices[0]["ops"] or devices[0]["modules"], t0, t1))
    edges = [t0] + [x for ab in iv for x in ab] + [t1]
    spans = [(n, s, s + dur) for n, s, dur in tr["host"]
             if n != window_span and s + dur > t0 and s < t1]
    gaps: Dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        inner = [(s, n) for n, s, e in spans if s <= mid < e]
        name = max(inner)[1] if inner else window_span
        gaps[name] = gaps.get(name, 0.0) + (b - a)
    ns = 1e-9
    rank = lambda dct: [[k, v * ns] for k, v in
                        sorted(dct.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (t1 - t0) * ns,
            "busy_s": sum(busy) / len(busy) * ns,
            "modules_s": {k: v * ns for k, v in modules.items()},
            "device_ops": rank(ops),
            "idle_gaps": rank(gaps)}
