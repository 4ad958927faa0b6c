"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # fast mode (default)
    PYTHONPATH=src python -m benchmarks.run --full    # paper-scale settings

Emits each report plus a ``name,us_per_call,derived`` CSV summary line per
benchmark (us_per_call = the benchmark's primary latency; derived = its
primary derived metric).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="paper-scale settings (slow)")
    p.add_argument("--only", default=None,
                   help="comma list: table3,fig7,fig8,roofline")
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None
    csv_rows = [("name", "us_per_call", "derived")]

    def want(name):
        return only is None or name in only

    if want("table3"):
        from benchmarks import table3_deployment_latency as t3

        t0 = time.perf_counter()
        rep = t3.report(fast=fast)
        print("=" * 72)
        print(rep)
        res = t3.run(fast=fast)
        integ = res["edge-cloud-integrated"]["rows"]["hybrid_inference"]
        csv_rows.append(("table3_deployment_latency",
                         f"{integ.get('total', 0) * 1e6:.0f}",
                         f"oom_edge={res['edge-centric']['oom']}"))
        print(f"[table3 took {time.perf_counter()-t0:.1f}s]")

    if want("fig7"):
        from benchmarks import fig7_weighting_latency as f7

        t0 = time.perf_counter()
        rep = f7.report(fast=fast)
        print("=" * 72)
        print(rep)
        res = f7.run(fast=fast)
        dyn = res["dynamic_scipy"]["hybrid_infer"]
        sta = res["static"]["hybrid_infer"]

        def tot(m):
            return (res[m]["speed_infer"] + res[m]["batch_infer"]
                    + res[m]["hybrid_infer"])

        pct = (tot("dynamic_scipy") - tot("static")) / max(tot("static"),
                                                           1e-12) * 100
        csv_rows.append(("fig7_weighting_latency", f"{dyn * 1e6:.0f}",
                         f"dyn_overhead_of_total_pct={pct:.1f}"))
        print(f"[fig7 took {time.perf_counter()-t0:.1f}s]")

    if want("fig8"):
        from benchmarks import fig8_accuracy_drift as f8

        t0 = time.perf_counter()
        rep = f8.report(fast=fast)
        print("=" * 72)
        print(rep)
        res = f8.run(fast=fast)
        dyn = res["gradual"]["dynamic"]["rmse_hybrid"]
        csv_rows.append(("fig8_accuracy_drift", "0",
                         f"gradual_dynamic_rmse={dyn:.4f}"))
        print(f"[fig8 took {time.perf_counter()-t0:.1f}s]")

    if want("ablation") and only is not None:
        # beyond-paper; only when explicitly requested (slow)
        from benchmarks import ablation_window as ab

        t0 = time.perf_counter()
        print("=" * 72)
        print(ab.report(fast=fast))
        csv_rows.append(("ablation_window", "0", "see report"))
        print(f"[ablation took {time.perf_counter()-t0:.1f}s]")

    if want("roofline"):
        from benchmarks import roofline_report as rr

        t0 = time.perf_counter()
        print("=" * 72)
        try:
            print(rr.report())
            print()
            print(rr.report(mesh="2x16x16"))
            print()
            try:
                print(rr.perf_report())
            except Exception as e:  # noqa: BLE001
                print("(no §Perf artifacts:", e, ")")
            rows = [rr.recompute(r) for r in rr.load()
                    if r["status"] == "ok" and r["mesh"] == "16x16"]
            n_fit = sum(r["fits_hbm"] for r in rows)
            csv_rows.append(("roofline", "0",
                             f"n_ok={len(rows)};fits_hbm={n_fit}"))
        except FileNotFoundError:
            print("no dry-run artifacts; run: python -m repro.launch.dryrun --all")
        print(f"[roofline took {time.perf_counter()-t0:.1f}s]")

    print("=" * 72)
    for row in csv_rows:
        print(",".join(str(x) for x in row))


if __name__ == "__main__":
    main()
