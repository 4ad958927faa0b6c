"""The chip benchmark's one entry point.

    python3 chipbench/run.py --workload t64-windows --seed 7 --seconds 10 --trace 0

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
names a configuration (``chipbench/configs/<config>.json``) and a traffic
mix (``chipbench/traffic/<traffic>.json``, whose ``driver`` names the code
in ``chipbench/drivers/`` that runs it); its limits are in
``chipbench/limits/<workload>.json``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``, each compared number beside its
limit.  The same numbers end standard error.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_files(workload: str, root: str = ROOT) -> Dict:
    """The cell's entry, configuration, traffic and limits, by name, from
    the checkout at ``root``."""
    here = os.path.join(root, "chipbench")
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "bench": bench, "cell": cell,
        "cfg": _json(os.path.join(root, conf["file"])),
        "traffic": _json(os.path.join(here, "traffic",
                                      cell["traffic"] + ".json")),
        "limits": _json(os.path.join(here, "limits", workload + ".json")),
        "root": root,
    }


def read_metric(name: str, rd: Dict, root: str = ROOT):
    """Per-layer metric ``name`` by its reader ``metrics/<name>.py``; None
    when the reader finds nothing to read."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    return _load(path, "chipbench_metric_" + name.replace(".", "_")).read(rd)


def metrics_of(bench: Dict, workload: str, kind: str, reported=()):
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics:
    those that list the cell, or list no cells and move a metric the cell
    reports."""
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


class Ctx:
    """What a driver gets: the run's arguments and files, the chips, the
    spans and the compile counter, and the hooks that open and close the
    timed window."""

    def __init__(self, args, files, devs, meter, spans, age0, perf0):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.cfg, self.traffic = files["cfg"], files["traffic"]
        self.devs, self.meter, self.spans = devs, meter, spans
        self._age0, self._perf0 = age0, perf0
        self.setup_s = None
        self.setup_compile = None
        self.window_compile = None
        self.trace_dir: Optional[str] = None
        self.trace_summary = None

    def window_starts(self) -> None:
        """Start the profiler (traced runs) before the timed call."""
        import jax

        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            # host events at level 1 only: the benchmark's spans and the
            # runtime's main ones, so tracing slows the host less
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def mark_window(self) -> None:
        """The timed window opens: compiles from here on are in it."""
        self.setup_compile = self.meter.snapshot()

    def window_ends(self, t0: float, t1: float) -> None:
        import jax

        from chipbench import trace

        if self.trace:
            jax.profiler.stop_trace()
            try:
                self.trace_summary = trace.reduce(trace.load(self.trace_dir))
            finally:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.setup_s = self._age0 + (t0 - self._perf0)
        now = self.meter.snapshot()
        self.window_compile = tuple(b - a for a, b in
                                    zip(self.setup_compile, now))

    def device_record(self) -> Dict:
        from chipbench.common import device_record

        return device_record(self.devs)

    def dtype(self, name: str):
        import jax.numpy as jnp

        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]

    def check_rows(self, n_streams: int):
        """The streams the reference follows: all of them, or
        ``check_streams`` drawn from the seed, as many from each chip's
        block of streams (the stacked stream axis is split over the chips
        in contiguous blocks), so a fault on one chip's streams reaches
        its share of the rows."""
        import numpy as np

        k = int(self.cfg.get("check_streams", n_streams))
        if k >= n_streams:
            return list(range(n_streams))
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 0x5A3B]))
        chips = len(self.devs)
        size, per = n_streams // chips, max(1, k // chips)
        return sorted(c * size + int(i) for c in range(chips)
                      for i in rng.choice(size, per, replace=False))


def run_cell(argv=None, require_chip: bool = True,
             files: Optional[Dict] = None, cache: bool = True) -> Dict:
    """Run one cell; returns the result line's object.  ``files`` replaces
    the cell's files (tests run a small copy of a cell this way, on the CPU
    and without the persistent compile cache)."""
    from chipbench.common import process_age_s

    age0, perf0 = process_age_s(), time.perf_counter()
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    files = files or cell_files(args.workload)
    bench, cell = files["bench"], files["cell"]

    from chipbench.common import (CompileMeter, Spans, devices_for,
                                  enable_compile_cache)

    cache = enable_compile_cache() if cache else None
    meter = CompileMeter()
    devs = devices_for(int(cell["chips"]), require_chip)
    spans = Spans()
    ctx = Ctx(args, files, devs, meter, spans, age0, perf0)
    driver = _load(os.path.join(files["root"], "chipbench", "drivers",
                                files["traffic"]["driver"] + ".py"),
                   "chipbench_driver_" + files["traffic"]["driver"])
    out = driver.run(ctx)

    s = spans.total
    sec, progs, hits = ctx.setup_compile
    print(f"setup_s {ctx.setup_s:.3f}: compile {sec:.3f} s ({progs} "
          f"programs, {hits} from the cache {cache}, {progs - hits} "
          f"compiled), data {s.get('data', 0):.3f} s, pretrain "
          f"{s.get('pretrain', 0):.3f} s, warm-up {s.get('warmup', 0):.3f} s; "
          f"in the window: {ctx.window_compile[1]} programs compiled or "
          f"loaded", file=sys.stderr)
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": out["device"]}
    e2e = metrics_of(bench, args.workload, "end_to_end")
    values = dict(out["metrics"], setup_s=ctx.setup_s)
    if not args.trace:
        for m in e2e:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        rd = dict(out["readings"], trace=ctx.trace_summary, spans=spans,
                  setup_compile=ctx.setup_compile, chips=len(devs),
                  peaks=files.get("peaks") or _json(
                      os.path.join(HERE, "peaks.json")),
                  device_kind=devs[0].device_kind)
        for m in metrics_of(bench, args.workload, "per_layer",
                            [x["name"] for x in e2e]):
            v = read_metric(m["name"], rd, files["root"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        red = ctx.trace_summary
        if red is not None:
            result["device"]["busy_s"] = red["busy_s"]
            result["device"]["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    checks = {}
    for name, value in out["checks"].items():
        # a number the run could not produce (an answer that never came)
        # reads as the largest float, so the line stays valid JSON
        value = value if math.isfinite(value) else sys.float_info.max
        checks[name] = {"value": value, "limit": files["limits"][name]}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    result = run_cell(argv)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
