"""The readings that ``limits/tft8-windows.json`` is set from: for each
seed the cell's compared numbers for the program, for the control (the
reference one precision step down, bfloat16, in the program's place), for
a sound computation with other rounding (the reference with each LSTM
step's gate products fused), and for faults planted in the reference's fit
(``reference_tft.VARIANTS``).  Not part of a benchmark run.

    python3 chipbench/readings_tft.py --seeds 11,12 --control-seeds 11 \\
        --variant-seeds 11 --fault-seeds 11 --seconds 10 \\
        --out readings_tft.jsonl

Each seed of ``--seeds`` runs the cell as a benchmark run does (set-up,
the timed windows, the reference); any other seed makes the cell's data
and computes the references alone, over the windows that a run of
``--windows`` windows checks.  All run in one process, so the programs
compile once.

The program's line says whether every number is within its limit, as a
benchmark run's ``correct`` does; every other line adds the median gap of
its step losses to the reference's at a few steps along the fit, which
shows how far a change of rounding compounds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKLOAD = "tft8-windows"


# fit steps at which a line gives its step-loss gap to the reference
ALONG = (0, 1, 2, 4, 9, 19, 49, 99)


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def step_gaps(other, ref):
    """Median over streams and checked fits of the relative gap of each
    step's loss to the reference's, at the steps of ``ALONG``."""
    import numpy as np

    gaps = np.concatenate([
        np.abs(other["fit"][v] - r) / np.maximum(np.abs(r), 1e-12)
        for v, r in ref["fit"].items()])
    return {str(s): float(np.median(gaps[:, s])) for s in ALONG
            if s < gaps.shape[1]}


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax.numpy as jnp

    from chipbench import run
    from chipbench.common import (CompileMeter, Spans, devices_for,
                                  enable_compile_cache, process_age_s)
    from chipbench.drivers import tft_windows

    p = argparse.ArgumentParser()
    for name in ("seeds", "control-seeds", "variant-seeds", "fault-seeds"):
        p.add_argument("--" + name, type=_seeds, default=[])
    p.add_argument("--faults", default="state_unchanged,half_batch")
    p.add_argument("--seconds", type=float, default=10.0)
    # the windows a run of --seconds times, for seeds read without the
    # program: a v5e's window takes over 10 s, so a 10 s run times 3
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    files = run.cell_files(WORKLOAD)
    enable_compile_cache()
    meter = CompileMeter()
    devs = devices_for(int(files["cell"]["chips"]), True)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)

    with open(a.out, "a") as out:
        def emit(kind, seed, checks, extra=None):
            line = {"workload": WORKLOAD, "kind": kind, "seed": seed,
                    "checks": checks, **(extra or {})}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)

        for seed in sorted(set(a.seeds) | set(a.control_seeds)
                           | set(a.variant_seeds) | set(a.fault_seeds)):
            t0 = time.perf_counter()
            args = argparse.Namespace(workload=WORKLOAD, seed=seed,
                                      seconds=a.seconds, trace=0)
            ctx = run.Ctx(args, files, devs, meter, Spans(),
                          process_age_s(), time.perf_counter())
            if seed in a.seeds:
                cell = tft_windows.Cell(ctx)
                m = tft_windows.measure(ctx, cell,
                                        cell.windows_for(a.seconds))
                del cell.ex, cell.stages, cell.fc
                checked = m["checked"]
            else:
                # the reference alone, over the windows a run checks
                cell = tft_windows.Cell(ctx, program=False)
                checked = cell.check_windows(a.windows)
            ref = cell.reference(checked, jnp.float32)

            def other(kind, o):
                emit(kind, seed, tft_windows.compare(
                    cell.as_program(o, checked), ref, checked),
                    {"step_gaps": step_gaps(o, ref)})

            if seed in a.seeds:
                checks = tft_windows.compare(m["prog"], ref, checked)
                emit("program", seed, checks,
                     {"correct": all(v <= files["limits"][k]
                                     for k, v in checks.items()),
                      "seconds": time.perf_counter() - t0,
                      "window_s": m["window_s"], "device": m["device"]})
            if seed in a.control_seeds:
                other("control", cell.reference(checked, jnp.bfloat16))
            if seed in a.variant_seeds:
                other("variant:fused", cell.reference(
                    checked, jnp.float32, variant="fused_gates"))
            if seed in a.fault_seeds:
                for fault in a.faults.split(","):
                    other("fault:" + fault, cell.reference(
                        checked, jnp.float32, variant=fault))
    return 0


if __name__ == "__main__":
    sys.exit(main())
