"""The readings that the limits in ``limits/<cell>.json`` are set from:
for each seed, the cell's compared numbers for the program, for the control
(the reference in the program's place, one precision step down: bfloat16
parameters, activations and gradients), for each planted fault of
``faults.py``, for the program with one chip's exchange left out (cells on
several chips), and for sound computations with other rounding (the
reference with its gate products fused, the reference at full float32
matmul precision, and the program on its Pallas kernels).  Not part of a
benchmark run.

    python3 chipbench/readings.py --workload t64-windows \\
        --seeds 11,12,13 --control-seeds 11,12,13 --fault-seeds 11 \\
        --variant-seeds 11 --seconds 2 --out chiprun_out/readings.jsonl

Each seed runs the cell as a benchmark run does (set-up, a short window at
the cell's own load, the reference), all in one process so the programs
compile once.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import faults, run
    from chipbench.check import compare_answers, compare_windows
    from chipbench.common import (CompileMeter, Spans, devices_for,
                                  enable_compile_cache, process_age_s)

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_seeds, default=[])
    p.add_argument("--control-seeds", type=_seeds, default=[])
    p.add_argument("--fault-seeds", type=_seeds, default=[])
    p.add_argument("--variant-seeds", type=_seeds, default=[])
    p.add_argument("--variants", default="fused,highest,pallas")
    p.add_argument("--exchange-seeds", type=_seeds, default=[])
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    files = run.cell_files(a.workload)
    enable_compile_cache()
    meter = CompileMeter()
    devs = devices_for(int(files["cell"]["chips"]), True)
    driver = run._load(os.path.join(HERE, "drivers",
                                    files["traffic"]["driver"] + ".py"),
                       "chipbench_driver")
    windows = files["traffic"]["driver"] == "windows"
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    out = open(a.out, "a")

    def emit(kind, seed, checks, extra=None):
        line = {"workload": a.workload, "kind": kind, "seed": seed,
                "checks": checks, **(extra or {})}
        out.write(json.dumps(line) + "\n")
        out.flush()
        print(json.dumps(line), flush=True)

    f32, bf16 = jnp.float32, jnp.bfloat16

    def measure(seed, pallas=False, lost=0):
        """One seed as a run makes it: the cell's set-up, a short window,
        the reference.  Returns the program's numbers and what the
        stand-ins need."""
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        ctx = run.Ctx(args, files, devs, meter, Spans(), process_age_s(),
                      time.perf_counter())
        import repro.configs as rc

        orig = rc.get_config
        if pallas:
            rc.get_config = lambda name: dataclasses.replace(
                orig(name), use_pallas=True)
        try:
            cell = driver.Cell(ctx)
        finally:
            rc.get_config = orig
        ctx.mark_window()
        if windows:
            n = cell.windows_for(a.seconds)
            checked = cell.check_windows(n)
            with (faults.exchange_left_out(lost, len(devs)) if lost
                  else contextlib.nullcontext()):
                t = cell.timed(n, checked)
            prog = driver.program_outputs(cell, t)
            del cell.ex, cell.stages, cell.fc, t
            rows = ctx.check_rows(cell.S)
            ref = cell.reference(checked, f32, rows)
            cmp = lambda o: compare_windows(o, ref, checked)
            stand_in = lambda r: cell.as_program(r, checked)
            run_ref = lambda dt, fit=None: cell.reference(
                checked, dt, rows, **({"fit": fit} if fit else {}))
        else:
            arr = driver.generator.arrivals(seed, ctx.traffic, cell.S,
                                            a.seconds)
            rng = np.random.default_rng(np.random.SeedSequence(
                [seed, 0x5A4D]))
            n = len(arr["due"])
            sample = set(int(u) for u in rng.choice(
                n, min(n, int(ctx.traffic["check_queries"])), replace=False))
            res = cell.loop(arr, a.seconds, sample)
            prog = {"answers": res["answers"],
                    "unanswered": int((~np.isfinite(res["lat"])).sum())}
            del cell.fc, cell.params, cell.serving
            uids = sorted(res["answers"])
            ref = cell.reference(arr, uids, f32)
            cmp = lambda o: compare_answers(o, ref)
            stand_in = lambda r: {"answers": r["answers"], "unanswered": 0}
            run_ref = lambda dt, fit=None: cell.reference(
                arr, uids, dt, **({"fit": fit} if fit else {}))
        return cmp(prog), cmp, stand_in, run_ref, ref

    variants = a.variants.split(",")
    for seed in sorted(set(a.seeds) | set(a.control_seeds)
                       | set(a.fault_seeds) | set(a.variant_seeds)):
        t0 = time.perf_counter()
        got, cmp, stand_in, run_ref, ref = measure(seed)
        if seed in a.seeds:
            emit("program", seed, got, {"seconds": time.perf_counter() - t0})
        if seed in a.control_seeds:
            emit("control", seed, cmp(stand_in(run_ref(bf16))))
        if seed in a.fault_seeds:
            for fault in faults.FAULTS:
                if fault == "answer_altered":
                    o = faults.alter(stand_in(ref))
                elif windows:
                    o = stand_in(run_ref(f32, faults.fit_for(fault)))
                else:
                    continue
                emit("fault:" + fault, seed, cmp(o))
        if seed in a.variant_seeds:
            # sound computations with other rounding
            if "fused" in variants:
                emit("variant:fused", seed, cmp(stand_in(
                    run_ref(f32, faults.fit_for("fused_gates")))))
            if "highest" in variants:
                with jax.default_matmul_precision("highest"):
                    emit("variant:highest", seed,
                         cmp(stand_in(run_ref(f32))))
            if "pallas" in variants:
                try:
                    emit("variant:pallas", seed,
                         measure(seed, pallas=True)[0])
                except Exception as e:  # another program path
                    emit("variant:pallas", seed, {},
                         {"error": repr(e)[:300]})
    for seed in a.exchange_seeds:
        emit("fault:exchange_one_chip", seed, measure(seed, lost=1)[0])
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
