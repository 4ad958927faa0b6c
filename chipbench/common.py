"""What every cell shares: the compile cache, the compile counter, the
benchmark's own host spans, the device record and the clock from process
start."""
from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so set-up
    counts the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else ``<checkout>/.jax_cache`` (a fixed path: the path
    is part of what a later run must find again).  Every executable is
    cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """Programs built (seconds and count) and how many of them came from
    the persistent cache, from JAX's monitoring events.  A program loaded
    from the cache counts as built, with the seconds it took to load."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.BACKEND:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


class Spans:
    """The benchmark's host spans around its calls into the program.  Each
    is a ``jax.profiler.TraceAnnotation`` (so a traced run sees it on the
    profiler's clock, named ``cb:<name>``) and is also summed here."""

    def __init__(self):
        self.total: Dict[str, float] = {}
        self.walls: Dict[str, List[float]] = {}
        self.keep = set()

    @contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("cb:" + name):
            yield
        dt = time.perf_counter() - t0
        self.total[name] = self.total.get(name, 0.0) + dt
        if name in self.keep:
            self.walls.setdefault(name, []).append(dt)


class Spanned:
    """A program stage behind a span: calls go through ``spans.span`` and,
    while ``record`` is set, hand their inputs and output to it.  Every
    other attribute reads through to the stage."""

    def __init__(self, stage, name: str, spans: Spans):
        self.__dict__.update(_stage=stage, _name=name, _spans=spans,
                             record=None)

    def __call__(self, **kw):
        with self._spans.span(self._name):
            out = self._stage(**kw)
        if self.record is not None:
            self.record(kw, out)
        return out

    def __getattr__(self, name):
        return getattr(self._stage, name)

    def __setattr__(self, name, value):
        if name == "record":
            self.__dict__["record"] = value
        else:
            setattr(self._stage, name, value)


def program_model(cfg: Dict):
    """The program's configuration of ``cfg["model"]``, once it has the
    widths and the parameter count that ``cfg`` states."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import get_model

    mcfg = get_config(cfg["model"])
    for k in ("hidden", "dense", "n_features", "lag"):
        if getattr(mcfg.lstm, k) != cfg[k]:
            raise ValueError(f"program's {cfg['model']} has {k}="
                             f"{getattr(mcfg.lstm, k)}, not {cfg[k]}")
    shapes = jax.eval_shape(get_model(mcfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    if n != cfg["parameters"]:
        raise ValueError(f"program's {cfg['model']} has {n} parameters, "
                         f"not {cfg['parameters']}")
    return mcfg


def devices_for(chips: int, require_chip: bool):
    """The chips a cell runs on.  Refuses a host whose JAX finds no
    accelerator or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise SystemExit(
            f"this cell needs {chips} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def device_record(devs) -> Dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}
