"""Elastic placement benchmark: static vs reactive vs proactive placement
under a scripted load spike, tracked as ``BENCH_elastic.json``.

Sections:

* ``parity`` — an elastic run under calm load against static placement:
  the controller observes every tick but never acts, so per-stream window
  RMSE and served answers must match static placement exactly (<= 1e-6);
  train/predict stay at one aggregated dispatch per window.
* ``spike`` — the same scripted spike (heavy serving load + inflated stage
  walls on the 1-worker edge) run three ways: ``static`` (no controller),
  ``reactive`` (queue-EWMA scaling + migration), ``proactive`` (the same
  plus the LSTM load forecaster scaling ahead of the ramp).  Gates:
  p99 answer latency proactive <= reactive <= static, at least one stream
  migrates edge->cloud in the elastic runs, zero dropped windows across
  the migration, and the fleet's aggregated train/predict dispatch
  counters stay at exactly one dispatch per window.
* ``determinism`` — the proactive spike run twice must be byte-identical
  (ledger, forecasts, migration schedule): elastic decisions replay.

    PYTHONPATH=src python -m benchmarks.bench_elastic            # full
    PYTHONPATH=src python -m benchmarks.bench_elastic --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
from typing import Dict

PERIOD = 5.0
CALM_QPS = 6.0
SPIKE_QPS = 12.0


def _spike_costs() -> Dict[str, float]:
    """The scripted spike: serving ticks and per-window inference walls
    heavy enough to saturate the 1-worker edge (deterministic virtual
    walls, identical for all three modes)."""
    from repro.core.scenarios import CHAOS_STAGE_COSTS

    costs = dict(CHAOS_STAGE_COSTS)
    costs["serving"] = 0.2
    costs["speed_inference"] = 0.4
    costs["batch_inference"] = 0.4
    return costs


def _controller_factory(mode: str):
    from repro.runtime import LoadForecaster, PlacementController

    def build():
        return PlacementController(
            proactive=(mode == "proactive"), migrate_up_s=0.8,
            migrate_down_s=0.05, scale_up_s=1.5, scale_down_s=0.05,
            persistence=1, cooldown=2, max_workers=3, min_residency=2,
            forecaster=(LoadForecaster(horizon=3)
                        if mode == "proactive" else None))

    return build


def _executor(pipeline, *, elastic, qps, stage_costs, factory=None):
    from repro.runtime import FleetBusExecutor, paper_topology
    from repro.runtime.deployment import edge_cloud_integrated

    stages, bp, streams, cost = pipeline
    ex = FleetBusExecutor(
        stages, edge_cloud_integrated(), paper_topology(), cost,
        window_period_s=PERIOD, qps=qps, serve_slots=4,
        stage_costs=stage_costs, elastic=elastic,
        controller_factory=factory)
    return ex, streams, bp


def _mode_metrics(res, n_windows: int) -> Dict:
    s = res.serving or {}
    scored = {sid: len(r.records) for sid, r in res.results.items()}
    expected = n_windows - 1  # warmup window is not scored
    p = res.placement or {}
    ctl = p.get("controller") or {}
    return {
        "p99_s": s.get("p99_s", float("inf")),
        "mean_s": s.get("mean_s", None),
        "n_answered": s.get("n_answered", 0),
        "n_starved": s.get("n_starved", 0),
        "windows_scored": sum(scored.values()),
        "dropped_windows": sum(max(0, expected - n) for n in scored.values()),
        "train_dispatches": res.train_dispatches,
        "infer_dispatches": res.infer_dispatches,
        "migrations": p.get("migrations", []),
        "n_migrations": len(p.get("migrations", [])),
        "scale_events": ctl.get("scale_events", 0),
        "proactive_scale_events": ctl.get("proactive_scale_events", 0),
        "final_workers": p.get("final_workers", {}),
        "stream_site": p.get("stream_site", {}),
    }


def run(smoke: bool) -> Dict:
    import jax

    from repro.core.scenarios import forecast_signature, ledger_signature
    from repro.launch.edge_cloud import build_fleet_pipeline

    n_streams, n_windows, rpw = (2, 5, 80) if smoke else (3, 6, 120)
    print(f"building fleet pipeline ({n_streams} streams, {n_windows} "
          f"windows) ...")
    pipeline = build_fleet_pipeline(n_streams, n_windows, fast=True,
                                    records_per_window=rpw,
                                    scenario="gradual", verbose=False)
    key = jax.random.PRNGKey(1)
    spike = _spike_costs()
    from repro.core.scenarios import CHAOS_STAGE_COSTS
    calm = dict(CHAOS_STAGE_COSTS)

    out: Dict = {"config": {
        "smoke": smoke, "n_streams": n_streams, "n_windows": n_windows,
        "records_per_window": rpw, "period_s": PERIOD,
        "calm_qps": CALM_QPS, "spike_qps": SPIKE_QPS,
        "spike_stage_costs": spike,
    }}

    # -- parity: calm elastic == static --------------------------------------
    print("parity: static vs elastic under calm load ...")
    ex, streams, bp = _executor(pipeline, elastic=False, qps=CALM_QPS,
                                stage_costs=calm)
    r_static = ex.run(streams, bp, key)
    ex, _, _ = _executor(pipeline, elastic=True, qps=CALM_QPS,
                         stage_costs=calm)
    r_calm = ex.run(streams, bp, key)
    diffs = [abs(a.rmse_hybrid - b.rmse_hybrid)
             for sid in r_static.results
             for a, b in zip(r_static.results[sid].records,
                             r_calm.results[sid].records)]
    out["parity"] = {
        "rmse_max_abs_diff": max(diffs),
        "forecasts_identical": (forecast_signature(r_static)
                                == forecast_signature(r_calm)),
        "calm_migrations": len(r_calm.placement["migrations"]),
        "train_dispatches": r_calm.train_dispatches,
        "infer_dispatches": r_calm.infer_dispatches,
    }

    # -- the spike, three ways -----------------------------------------------
    out["spike"] = {}
    results = {}
    for mode in ("static", "reactive", "proactive"):
        print(f"spike: {mode} ...")
        if mode == "static":
            ex, _, _ = _executor(pipeline, elastic=False, qps=SPIKE_QPS,
                                 stage_costs=spike)
        else:
            ex, _, _ = _executor(pipeline, elastic=mode, qps=SPIKE_QPS,
                                 stage_costs=spike,
                                 factory=_controller_factory(mode))
        res = ex.run(streams, bp, key)
        results[mode] = res
        out["spike"][mode] = _mode_metrics(res, n_windows)

    # -- determinism ---------------------------------------------------------
    print("determinism: proactive spike x2 ...")
    ex, _, _ = _executor(pipeline, elastic="proactive", qps=SPIKE_QPS,
                         stage_costs=spike,
                         factory=_controller_factory("proactive"))
    r2 = ex.run(streams, bp, key)
    r1 = results["proactive"]
    out["determinism"] = {
        "ledger_identical": ledger_signature(r1) == ledger_signature(r2),
        "forecasts_identical": (forecast_signature(r1)
                                == forecast_signature(r2)),
        "migrations_identical": (r1.placement["migrations"]
                                 == r2.placement["migrations"]),
        "depth_series_identical": all(
            r1.ledger.depth_series(s) == r2.ledger.depth_series(s)
            for s in ("edge", "cloud")),
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer streams/windows)")
    ap.add_argument("--devices", type=int, default=None,
                    help="host devices to expose to XLA (default: the "
                         "machine's core count); the fleet's stream axis "
                         "shards across them")
    ap.add_argument("--out", default="BENCH_elastic.json")
    args = ap.parse_args()

    # before the first lazy jax import below: give the fleet a mesh
    from benchmarks._device_env import ensure_host_devices
    ensure_host_devices(args.devices)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    res = run(args.smoke)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)
    print(f"\nwrote {args.out}")

    p = res["parity"]
    print(f"parity: rmse diff {p['rmse_max_abs_diff']:.2e}, forecasts "
          f"identical: {p['forecasts_identical']}, calm migrations: "
          f"{p['calm_migrations']}")
    for mode, m in res["spike"].items():
        print(f"{mode:>10}: p99 {m['p99_s']:.3f}s, answered "
              f"{m['n_answered']} (starved {m['n_starved']}), migrations "
              f"{m['n_migrations']}, scale events {m['scale_events']} "
              f"({m['proactive_scale_events']} proactive), dropped windows "
              f"{m['dropped_windows']}")
    d = res["determinism"]
    print(f"determinism: ledger {d['ledger_identical']}, forecasts "
          f"{d['forecasts_identical']}, migrations "
          f"{d['migrations_identical']}, depth {d['depth_series_identical']}")


if __name__ == "__main__":
    main()
