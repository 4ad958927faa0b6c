"""A cell of the benchmark cut to a size a CPU test run can hold."""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run  # noqa: E402

SEED = 2_500_000_017  # above 2**31, as the driver's seeds are


def files(workload: str):
    f = run.cell_files(workload)
    f["cfg"].update(streams=4, speed_epochs=2, batch_epochs=1,
                    check_streams=3)
    f["traffic"].update(max_windows=4, warmup_windows=2, trace_windows=3,
                        check_windows=2, rate_per_s=200, slots=8,
                        warmup_s=0.1, trace_seconds=0.5, drain_s=1.0,
                        check_queries=40)
    f["peaks"] = {"cpu": {"bf16_flops_per_s": 1e12}}
    return f


def run_small(workload: str, trace: int = 0, seconds: float = 0.5):
    return run.run_cell(["--workload", workload, "--seed", str(SEED),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_chip=False, files=files(workload),
                        cache=False)


def cell(workload: str, seed: int = SEED):
    """The driver's cell, built as a run builds it (for the control)."""
    import jax

    from chipbench.common import CompileMeter, Spans

    f = files(workload)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=0)
    ctx = run.Ctx(args, f, jax.devices()[:1], CompileMeter(), Spans(), 0.0,
                  time.perf_counter())
    drv = run._load(os.path.join(ROOT, "chipbench", "drivers",
                                 f["traffic"]["driver"] + ".py"),
                    "chipbench_test_driver_" + f["traffic"]["driver"])
    return drv, drv.Cell(ctx), ctx, f
