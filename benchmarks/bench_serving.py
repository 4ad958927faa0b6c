"""Request-plane serving benchmark: continuous-batched user queries answered
from the device-resident fleet state, tracked as ``BENCH_serving.json`` from
this PR onward — the repo's first latency-under-load numbers.

Two sections:

* ``parity`` — correctness of the batched tick path: a handcrafted query mix
  (point / multi-step horizon / perturbed what-if, including several queries
  of the *same* stream sharing a tick, submitted in staggered waves so slots
  recycle mid-run) driven through ``QueryPlane`` + ``ServingStage`` against
  frozen fleet params, compared per answer against the unbatched reference
  (``answer_query_unbatched``: a batch-of-one ``CompiledForecaster.predict``
  per horizon step).  CI gates max |diff| <= 1e-6 (vmap batching tolerance,
  the same bound ``bench_fleet`` holds per-stream predictions to) and
  exactly one vmapped dispatch per serving tick.

* ``open_loop`` — the measured plane: a deterministic open-loop arrival
  trace (uniform 1/qps spacing, seeded kind mix) replayed through a full
  ``FleetBusExecutor`` run on the edge-cloud-integrated deployment, serving
  ticks interleaved with the training windows under the serving site's
  worker occupancy.  Reports p50/p99/mean request latency, offered vs
  sustained QPS, dispatches/tick, starved-request count, and the staleness
  of the models that answered (how many windows each answer's serving model
  trailed its context).  CI gates: no starved requests, sustained >= offered
  at the smoke rate, finite p99, dispatches/tick == 1.

    PYTHONPATH=src python -m benchmarks.bench_serving            # full
    PYTHONPATH=src python -m benchmarks.bench_serving --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
from typing import Dict


def _bench_parity(n_streams: int, records_per_window: int, epochs: int,
                  n_slots: int) -> Dict:
    """Batched-vs-unbatched answer parity on frozen fleet params."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import lstm_fleet_forecaster
    from repro.core.stages import ServingStage
    from repro.runtime import fleet_key_chains
    from repro.serving.query_plane import (
        ForecastQuery,
        QueryPlane,
        answer_query_unbatched,
    )
    from repro.streams.sources import fleet_windowed_streams

    cfg = get_config("lstm-paper")
    streams, _ = fleet_windowed_streams(n_streams, 2, records_per_window,
                                        "gradual")
    ids = list(streams)
    keys = fleet_key_chains(jax.random.PRNGKey(2), ids, 1)
    ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=64)
    params, _ = ff.train_fleet(
        [streams[sid].supervised(0) for sid in ids],
        [keys[sid][0] for sid in ids])
    base_ctx = {sid: np.asarray(streams[sid].supervised(0)["x"])[-1]
                for sid in ids}

    # the query mix: same-stream multiples sharing a tick, horizons that
    # hold a slot for several ticks, perturbed what-ifs — submitted in two
    # waves so the second wave admits into recycled slots mid-run
    specs = [(0, "point", 1, 1.0, 0.0), (0, "horizon", 3, 1.0, 0.0),
             (1, "whatif", 1, 1.1, 0.05), (2, "point", 1, 1.0, 0.0),
             (1, "horizon", 2, 1.0, 0.0), (0, "whatif", 1, 0.9, -0.02),
             (2, "horizon", 3, 1.0, 0.0), (0, "point", 1, 1.0, 0.0),
             (1, "point", 1, 1.0, 0.0), (2, "whatif", 1, 1.05, 0.01)]
    queries = [ForecastQuery(uid=i, stream=ids[s % len(ids)], kind=k,
                             horizon=h, perturb_scale=sc, perturb_offset=of)
               for i, (s, k, h, sc, of) in enumerate(specs)]

    plane = QueryPlane(ids, n_slots)
    for sid in ids:
        plane.observe_window(sid, streams[sid].supervised(0)["x"], 0)
    wave2 = queries[6:]
    for q in queries[:6]:
        plane.submit(q)

    stage = ServingStage(ff)
    model_windows = {sid: 0 for sid in ids}
    tick = 0
    while plane.busy:
        plane.admit(float(tick))
        batch = plane.build_batch()
        if batch is None:
            break
        by_stream, xs = batch
        out = stage(params_seq=params, xs=xs)
        plane.apply(by_stream, out["preds"], model_windows)
        plane.retire(float(tick))
        tick += 1
        if tick == 2 and wave2:
            for q in wave2:
                plane.submit(q)
            wave2 = []

    max_diff = 0.0
    for q in queries:
        ref = answer_query_unbatched(ff.single.predict,
                                     params[ids.index(q.stream)], q,
                                     base_ctx[q.stream])
        assert len(q.answer) == q.horizon, \
            f"query {q.uid} got {len(q.answer)}/{q.horizon} answers"
        max_diff = max(max_diff, max(abs(a - b)
                                     for a, b in zip(q.answer, ref)))
    return {
        "max_abs_diff": max_diff,
        "n_queries": len(queries),
        "ticks": stage.ticks,
        "dispatches": stage.dispatches,
        "dispatches_per_tick": stage.dispatches / max(stage.ticks, 1),
        "n_slots": n_slots,
        "n_streams": len(ids),
    }


def _bench_open_loop(n_streams: int, n_windows: int,
                     records_per_window: int, qps: float, n_slots: int,
                     period_s: float, fast: bool) -> Dict:
    """Open-loop load through a full fleet-executor run: the headline
    latency/QPS numbers."""
    import jax

    from repro.launch.edge_cloud import build_fleet_pipeline
    from repro.runtime import FleetBusExecutor, paper_topology
    from repro.runtime.deployment import edge_cloud_integrated

    stages, bp, streams, cost = build_fleet_pipeline(
        n_streams, n_windows, fast=fast,
        records_per_window=records_per_window)
    ex = FleetBusExecutor(stages, edge_cloud_integrated(), paper_topology(),
                          cost, window_period_s=period_s, qps=qps,
                          serve_slots=n_slots)
    res = ex.run(streams, bp, jax.random.PRNGKey(1))
    answered = [q for q in res.queries if q.finished_at is not None]
    staleness = [q.context_window - q.model_window for q in answered
                 if q.model_window >= 0]
    out = dict(res.serving)
    out.update({
        "deployment": "edge-cloud-integrated",
        "n_streams": n_streams,
        "n_windows": n_windows,
        "window_period_s": period_s,
        "max_staleness_windows": max(staleness) if staleness else 0,
        "mean_staleness_windows": (sum(staleness) / len(staleness)
                                   if staleness else 0.0),
    })
    return out


def run(n_streams: int = 4, n_windows: int = 4,
        records_per_window: int = 120, epochs: int = 3, qps: float = 20.0,
        n_slots: int = 4, period_s: float = 5.0, fast: bool = True) -> Dict:
    return {
        "benchmark": "serving_request_plane",
        "config": {
            "model": "lstm-paper",
            "n_streams": n_streams,
            "n_windows": n_windows,
            "records_per_window": records_per_window,
            "epochs": epochs,
            "qps": qps,
            "n_slots": n_slots,
            "window_period_s": period_s,
        },
        "parity": _bench_parity(n_streams, records_per_window, epochs,
                                n_slots),
        "open_loop": _bench_open_loop(n_streams, n_windows,
                                      records_per_window, qps, n_slots,
                                      period_s, fast),
    }


def report(res: Dict) -> str:
    p, o = res["parity"], res["open_loop"]
    return "\n".join([
        f"# request plane: {o['n_streams']} streams, {o['slots']} slots, "
        f"{o['deployment']}",
        "",
        "# parity (batched ticks vs unbatched per-query reference)",
        f"{p['n_queries']} queries over {p['ticks']} ticks "
        f"({p['dispatches_per_tick']:.2f} dispatches/tick): "
        f"max |diff| = {p['max_abs_diff']:.2e}",
        "",
        f"# open loop ({o['n_requests']} requests at "
        f"{o['offered_qps']:.1f} qps offered)",
        f"answered {o['n_answered']}/{o['n_requests']} "
        f"({o['n_starved']} starved) over {o['ticks']} serving ticks, "
        f"{o['dispatches_per_tick']:.2f} dispatches/tick",
        f"sustained {o['sustained_qps']:.1f} qps  "
        f"p50 {o['p50_s']*1e3:.2f}ms  p99 {o['p99_s']*1e3:.2f}ms  "
        f"mean {o['mean_s']*1e3:.2f}ms  max {o['max_s']*1e3:.2f}ms",
        f"model staleness: max {o['max_staleness_windows']} windows, "
        f"mean {o['mean_staleness_windows']:.2f}",
    ])


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized run: 3 streams, 3 windows, 20 qps")
    p.add_argument("--streams", type=int, default=None)
    p.add_argument("--windows", type=int, default=None)
    p.add_argument("--qps", type=float, default=None)
    p.add_argument("--slots", type=int, default=None)
    p.add_argument("--devices", type=int, default=None,
                   help="host devices to expose to XLA (default: the "
                        "machine's core count); the serving fleet's stream "
                        "axis shards across them")
    p.add_argument("--out", default="BENCH_serving.json")
    args = p.parse_args()

    # before the first lazy jax import below: give the fleet a mesh
    from benchmarks._device_env import ensure_host_devices
    ensure_host_devices(args.devices)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        defaults = dict(n_streams=3, n_windows=3, records_per_window=120,
                        epochs=3, qps=20.0, n_slots=4, period_s=5.0,
                        fast=True)
    else:
        defaults = dict(n_streams=6, n_windows=5, records_per_window=250,
                        epochs=10, qps=50.0, n_slots=8, period_s=10.0,
                        fast=True)
    if args.streams is not None:
        defaults["n_streams"] = args.streams
    if args.windows is not None:
        defaults["n_windows"] = args.windows
    if args.qps is not None:
        defaults["qps"] = args.qps
    if args.slots is not None:
        defaults["n_slots"] = args.slots

    res = run(**defaults)
    print(report(res))
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
