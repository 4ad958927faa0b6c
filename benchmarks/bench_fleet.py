"""Fleet-scale speed-layer benchmark: N streams per node, tracked as
``BENCH_fleet.json`` from this PR onward.

The single-stream hot path (``BENCH_hotpath.json``) made one window's
retrain cheap; serving a *fleet* of sensors from one node multiplies every
per-window cost by N unless the fleet trains — and serves — together.
This benchmark pins the fleet properties the executors rely on:

* ``fleet_training`` — per-window wall of the one-dispatch fleet fit
  (``FleetForecaster.train_fleet``: staged device buffers, donated
  opt-state, stream axis sharded across the local device mesh) vs N
  sequential single-stream ``CompiledForecaster`` fits over the same
  windows and keys, interleaved window by window so host noise biases
  neither side.  Both sides report wall/stream and dispatches/sec **from
  the same per-window clock** (time until trained params are
  device-resident and ready), the dispatch counts (the fleet path must be
  exactly one per window), the retrace + staging-allocation counters (zero
  new traces, zero host re-stacks after each bucket's first window), and
  the max parameter divergence of fleet-vs-sequential fits (vmap batching
  tolerance, not a semantic difference).

* ``fleet_inference`` — the serving counterpart: one vmapped
  ``predict_fleet`` dispatch per window vs N sequential per-stream
  predicts, same clock; per-stream parity (<=1e-6), and the int8 fleet
  sync numbers (per-stream sync bytes float-vs-int8, batched int8 predict
  wall).

* ``executor_parity`` — a full ``InProcessFleetExecutor`` run (ungated)
  against N sequential ``InProcessExecutor`` runs with the same per-stream
  root keys: max per-window RMSE divergence across every stream, plus the
  fleet run's train-dispatch count.

* ``drift_gated`` — drift-gated retraining vs the paper's every-window
  policy on the stationary and abrupt scenarios: the stationary fleet must
  *skip* retrains (>0, counted), and the abrupt fleet's gated accuracy must
  track the every-window accuracy within tolerance.

* ``batch_refresh`` — the cloud-side heavy-retraining path riding the same
  sharded dispatch: a gated run with a ``BatchRefresh`` stage (batch models
  retrained from archived drifted windows on a cadence, one fleet dispatch
  per refresh round) vs the same run without, refresh dispatch accounting
  CI-gated.

* ``weak_scaling`` — the thousand-stream sweep: wall/stream and dispatch
  overhead at S x device-count cells, each cell a fresh CPU-pinned
  subprocess with its XLA host-device count pinned
  (``benchmarks._device_env.subprocess_env``; the count is fixed at backend
  init, so a sweep cannot run in-process).  The cells are host-device
  rehearsals of the stream mesh, never chip measurements: a child never
  asks for the accelerator the parent process holds.  Every
  cell must hold one dispatch per window and zero retraces after its first
  window; sampled streams must match the unsharded sequential path to 1e-6
  and agree across device counts; and wall/stream at the largest S must
  stay within 1.5x of the 8-stream baseline (overhead amortizes, compute
  weak-scales).

The process exposes the host's cores as XLA devices
(``--xla_force_host_platform_device_count``) before touching jax, so the
fleet paths shard their stream axis across the mesh — the configuration a
fleet node actually runs, and the one the tracked numbers come from.

    PYTHONPATH=src python -m benchmarks.bench_fleet            # paper-ish
    PYTHONPATH=src python -m benchmarks.bench_fleet --smoke    # CI: seconds
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from benchmarks._device_env import ensure_host_devices, subprocess_env
from repro.launch.compile_cache import enable_compile_cache


def _fleet_streams(n_streams: int, n_windows: int, records_per_window: int,
                   scenario: str, seed: int = 0):
    """N correlated turbines, each scaled by its own history — the exact
    construction the launcher's fleet mode runs
    (``streams.sources.fleet_windowed_streams``)."""
    import numpy as np

    from repro.streams.sources import fleet_windowed_streams

    alphas = np.full(5, 1.5e-3) if scenario == "gradual" else None
    return fleet_windowed_streams(n_streams, n_windows, records_per_window,
                                  scenario, seed=seed, alphas=alphas)


def _summary(walls: List[float], n_streams: int,
             dispatches_per_window: float) -> Dict:
    """Per-window wall statistics plus the two rates the fleet-vs-sequential
    comparison is made in: wall/stream and dispatches/sec, both derived from
    the same per-window clock (median steady-state wall)."""
    steady = walls[1:] if len(walls) > 1 else walls
    mean_steady = sum(steady) / len(steady)
    median = sorted(steady)[len(steady) // 2]
    return {
        "per_window_wall_s": walls,
        "first_window_wall_s": walls[0],
        "steady_state_wall_s": mean_steady,
        "steady_state_median_s": median,
        "wall_per_stream_steady_s": median / n_streams,
        "dispatches_per_sec_steady": dispatches_per_window / max(median,
                                                                 1e-12),
    }


def _bench_fleet_training(cfg, streams, epochs: int, batch_size: int,
                          key) -> Dict:
    """The training hot path alone: one-dispatch fleet fit vs N sequential
    single-stream fits, window-interleaved, identical per-stream keys."""
    import jax
    import numpy as np

    from repro.core import lstm_fleet_forecaster, lstm_forecaster
    from repro.runtime import fleet_key_chains

    ids = list(streams)
    n_windows = min(len(s) for s in streams.values())
    keys = fleet_key_chains(key, ids, n_windows)

    ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=batch_size)
    seq = {sid: lstm_forecaster(cfg, epochs=epochs, batch_size=batch_size)
           for sid in ids}

    fwalls, swalls, max_param_diff = [], [], 0.0
    for w in range(n_windows):
        datas = [streams[sid].supervised(w) for sid in ids]
        wkeys = [keys[sid][w] for sid in ids]
        t0 = time.perf_counter()
        fleet_params, _ = ff.train_fleet(datas, wkeys)
        fwalls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        seq_params = [seq[sid].train(d, None, k)[0]
                      for sid, d, k in zip(ids, datas, wkeys)]
        swalls.append(time.perf_counter() - t0)
        for fp, sp in zip(fleet_params, seq_params):
            for a, b in zip(jax.tree_util.tree_leaves(fp),
                            jax.tree_util.tree_leaves(sp)):
                max_param_diff = max(max_param_diff, float(np.max(np.abs(
                    np.asarray(a) - np.asarray(b)))))

    fleet = _summary(fwalls, len(ids), ff.train_dispatches / n_windows)
    fleet["dispatches"] = ff.train_dispatches
    fleet["dispatches_per_window"] = ff.train_dispatches / n_windows
    fleet["trace_counts"] = {str(k): v for k, v in ff.trace_counts().items()}
    fleet["retraces_after_first_window"] = ff.retrace_count - len(
        ff.trace_counts())
    fleet["staging_allocs"] = ff.staging_allocs
    sequential = _summary(swalls, len(ids), float(len(ids)))
    sequential["dispatches"] = n_windows * len(ids)
    return {
        "fleet": fleet,
        "sequential": sequential,
        "speedup_fleet_vs_sequential": (
            sequential["steady_state_median_s"]
            / max(fleet["steady_state_median_s"], 1e-12)),
        "max_param_abs_diff": max_param_diff,
        "n_windows": n_windows,
        "n_streams": len(ids),
        "devices": _device_count(),
    }


def _device_count() -> int:
    import jax

    return jax.device_count()


def _bench_fleet_inference(cfg, streams, epochs: int, batch_size: int,
                           key) -> Dict:
    """The serving hot path: one vmapped ``predict_fleet`` dispatch per
    window vs N sequential per-stream predicts (same params, same windows,
    same clock), plus the int8 fleet-sync numbers."""
    import numpy as np

    from repro.core import lstm_fleet_forecaster
    from repro.runtime import fleet_key_chains
    from repro.serving.quantize import quantize_tree, tree_nbytes
    from repro.training.compiled import materialize_params

    ids = list(streams)
    n_windows = min(len(s) for s in streams.values())
    keys = fleet_key_chains(key, ids, n_windows)
    ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=batch_size)
    params, _ = ff.train_fleet(
        [streams[sid].supervised(0) for sid in ids],
        [keys[sid][0] for sid in ids])

    d0 = ff.predict_dispatches
    fwalls, swalls, parity = [], [], 0.0
    for w in range(n_windows):
        xs = [streams[sid].supervised(w)["x"] for sid in ids]
        t0 = time.perf_counter()
        fleet_preds = ff.predict_fleet(params, xs)
        fwalls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        seq_preds = [ff.single.predict(p, x) for p, x in zip(params, xs)]
        swalls.append(time.perf_counter() - t0)
        for a, b in zip(fleet_preds, seq_preds):
            parity = max(parity, float(np.max(np.abs(a - b))))
    float_dispatches = ff.predict_dispatches - d0

    qparams = [quantize_tree(p, min_size=64) for p in params]
    qwalls = []
    for w in range(n_windows):
        xs = [streams[sid].supervised(w)["x"] for sid in ids]
        t0 = time.perf_counter()
        ff.predict_fleet(qparams, xs)
        qwalls.append(time.perf_counter() - t0)

    fleet = _summary(fwalls, len(ids), float_dispatches / n_windows)
    fleet["dispatches"] = float_dispatches
    fleet["dispatches_per_window"] = float_dispatches / n_windows
    sequential = _summary(swalls, len(ids), float(len(ids)))
    sequential["dispatches"] = n_windows * len(ids)
    sequential["dispatches_per_window"] = float(len(ids))
    float_bytes = tree_nbytes(materialize_params(params[0]))
    int8_bytes = tree_nbytes(qparams[0])
    return {
        "fleet": fleet,
        "sequential": sequential,
        "speedup_fleet_vs_sequential": (
            sequential["steady_state_median_s"]
            / max(fleet["steady_state_median_s"], 1e-12)),
        "per_stream_parity_max_abs_diff": parity,
        "predict_trace_counts": {str(k): v
                                 for k, v in ff.predict_trace_counts().items()},
        "int8_sync": {
            "steady_state_median_s": _summary(qwalls, len(ids), 1.0)[
                "steady_state_median_s"],
            "sync_bytes_float_per_stream": float_bytes,
            "sync_bytes_int8_per_stream": int8_bytes,
            "transfer_ratio": float_bytes / max(int8_bytes, 1),
        },
        "n_windows": n_windows,
        "n_streams": len(ids),
        "devices": _device_count(),
    }


def _bench_executor_parity(cfg, streams, bp, epochs: int, batch_size: int,
                           key) -> Dict:
    """Full fleet run vs N sequential single-stream runs: per-window RMSE
    divergence across every stream and record."""
    import jax

    from repro.core import (
        FleetStages,
        PipelineStages,
        lstm_fleet_forecaster,
        lstm_forecaster,
    )
    from repro.runtime import InProcessExecutor, InProcessFleetExecutor

    ids = list(streams)
    ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=batch_size)
    fleet_res = InProcessFleetExecutor(
        FleetStages.build(ff, mode="dynamic")).run(
            streams, bp, key)

    max_diff = 0.0
    for i, sid in enumerate(ids):
        fc = lstm_forecaster(cfg, epochs=epochs, batch_size=batch_size)
        seq = InProcessExecutor(PipelineStages.build(fc, mode="dynamic")).run(
            streams[sid], bp, jax.random.fold_in(key, i))
        for a, b in zip(seq.records, fleet_res.results[sid].records):
            max_diff = max(
                max_diff,
                abs(a.rmse_batch - b.rmse_batch),
                abs(a.rmse_speed - b.rmse_speed),
                abs(a.rmse_hybrid - b.rmse_hybrid))
    return {
        "rmse_max_abs_diff": max_diff,
        "train_dispatches": fleet_res.train_dispatches,
        "n_windows": fleet_res.n_windows,
        "dispatches_per_window": (fleet_res.train_dispatches
                                  / fleet_res.n_windows),
        "fleet_mean_rmse": fleet_res.mean_rmse(),
    }


def _bench_drift_gated(cfg, bp, n_streams: int, n_windows: int,
                       records_per_window: int, epochs: int, batch_size: int,
                       key) -> Dict:
    """Drift-gated vs every-window retraining on the stationary and abrupt
    scenarios."""
    from repro.core import FleetStages, lstm_fleet_forecaster
    from repro.core.drift import DriftGate
    from repro.runtime import InProcessFleetExecutor

    out = {}
    for scenario in ("none", "abrupt"):
        streams, _ = _fleet_streams(n_streams, n_windows, records_per_window,
                                    scenario)
        runs = {}
        for label, gate in (("every_window", None), ("gated", DriftGate())):
            ff = lstm_fleet_forecaster(cfg, epochs=epochs,
                                       batch_size=batch_size)
            ex = InProcessFleetExecutor(FleetStages.build(ff, mode="dynamic"),
                                        gate=gate)
            res = ex.run(streams, bp, key)
            runs[label] = res
        every, gated = runs["every_window"], runs["gated"]
        out[scenario] = {
            "skipped_retrains": gated.skipped_retrains(),
            "total_retrains": gated.total_retrains(),
            "every_window_retrains": every.total_retrains(),
            "train_dispatches_gated": gated.train_dispatches,
            "train_dispatches_every_window": every.train_dispatches,
            "hybrid_rmse_gated": gated.mean_rmse()["hybrid"],
            "hybrid_rmse_every_window": every.mean_rmse()["hybrid"],
            "speed_rmse_gated": gated.mean_rmse()["speed"],
            "speed_rmse_every_window": every.mean_rmse()["speed"],
            "gate_stats": gated.gate_stats,
        }
        out[scenario]["hybrid_rmse_ratio"] = (
            out[scenario]["hybrid_rmse_gated"]
            / max(out[scenario]["hybrid_rmse_every_window"], 1e-12))
    return out


def _bench_batch_refresh(cfg, bp, n_streams: int, n_windows: int,
                         records_per_window: int, epochs: int,
                         batch_size: int, key) -> Dict:
    """The cloud-side heavy-retraining path riding the fleet dispatch: a
    drift-gated run with a ``BatchRefresh`` stage (batch models retrained
    from archived drifted windows, one sharded fleet dispatch per refresh
    round) against the same gated run without one, on the abrupt
    scenario."""
    from repro.core import FleetStages, lstm_fleet_forecaster
    from repro.core.drift import DriftGate
    from repro.core.stages import BatchRefresh
    from repro.runtime import InProcessFleetExecutor

    streams, _ = _fleet_streams(n_streams, n_windows, records_per_window,
                                "abrupt")
    runs = {}
    for label in ("gated", "gated_refresh"):
        ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=batch_size)
        rf = (BatchRefresh(ff, every=2, min_windows=2)
              if label == "gated_refresh" else None)
        ex = InProcessFleetExecutor(FleetStages.build(ff, mode="dynamic"),
                                    gate=DriftGate(), batch_refresh=rf)
        runs[label] = ex.run(streams, bp, key)
    base, ref = runs["gated"], runs["gated_refresh"]
    rounds = max(ref.refresh["rounds"], 1)
    return {
        "refresh": ref.refresh,
        "dispatches_per_round": ref.refresh["dispatches"] / rounds,
        "train_dispatches": ref.train_dispatches,
        "train_dispatches_baseline": base.train_dispatches,
        "n_windows": ref.n_windows,
        "hybrid_rmse_refresh": ref.mean_rmse()["hybrid"],
        "hybrid_rmse_baseline": base.mean_rmse()["hybrid"],
        "batch_rmse_refresh": ref.mean_rmse()["batch"],
        "batch_rmse_baseline": base.mean_rmse()["batch"],
    }


# ---------------------------------------------------------------------------
# Weak scaling: wall/stream and dispatch overhead, S x host devices, one
# CPU-pinned subprocess per cell (XLA fixes its device count at backend
# init) — a host-device rehearsal of the stream mesh
# ---------------------------------------------------------------------------


def _weak_cell(spec: Dict) -> Dict:
    """One (n_streams, devices) cell, run inside a CPU-pinned child process
    whose host-device count the parent pinned via ``subprocess_env``: W
    windows of the one-dispatch fleet fit over synthetic per-stream windows
    (deterministic per (seed, stream, window) — identical data in every
    cell), plus the two per-cell correctness probes the sweep gates on:

    * parity vs the unsharded path — sampled streams (first/middle/last)
      refit sequentially through ``CompiledForecaster`` with the same keys;
    * probe predictions — the sampled streams' materialized params predict
      a fixed probe batch, serialized so the parent can compare the *same*
      stream's prediction across device counts."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import lstm_fleet_forecaster, lstm_forecaster
    from repro.runtime import fleet_key_chains
    from repro.training.compiled import (
        bucket_streams,
        materialize_params,
        stream_mesh_devices,
    )

    S, W = spec["n_streams"], spec["n_windows"]
    n, epochs, bs = spec["examples"], spec["epochs"], spec["batch_size"]
    seed = spec["seed"]
    cfg = get_config("lstm-paper")
    ids = [f"s{i:04d}" for i in range(S)]
    keys = fleet_key_chains(jax.random.PRNGKey(seed), ids, W)

    def window(i, w):
        rng = np.random.default_rng(seed * 1_000_003 + i * 9176 + w)
        x = rng.normal(0, 1, (n, 5, 5)).astype(np.float32)
        y = x[:, :, 0].mean(axis=1, keepdims=True).astype(np.float32)
        return {"x": x, "y": y}

    ff = lstm_fleet_forecaster(cfg, epochs=epochs, batch_size=bs)
    walls, last_params = [], None
    for w in range(W):
        datas = [window(i, w) for i in range(S)]
        wkeys = [keys[sid][w] for sid in ids]
        t0 = time.perf_counter()
        last_params, _ = ff.train_fleet(datas, wkeys)
        walls.append(time.perf_counter() - t0)

    sample = sorted({0, S // 2, S - 1})
    parity, w = 0.0, W - 1
    for i in sample:
        fc = lstm_forecaster(cfg, epochs=epochs, batch_size=bs)
        sp, _ = fc.train(window(i, w), None, keys[ids[i]][w])
        for a, b in zip(jax.tree_util.tree_leaves(sp),
                        jax.tree_util.tree_leaves(last_params[i])):
            parity = max(parity, float(np.max(np.abs(
                np.asarray(a) - np.asarray(b)))))

    probe_rng = np.random.default_rng(seed + 777_777)
    probe_x = probe_rng.normal(0, 1, (4, 5, 5)).astype(np.float32)
    probe = [np.asarray(ff.single.predict(
        materialize_params(last_params[i]), probe_x)).tolist()
        for i in sample]

    steady = walls[1:] if len(walls) > 1 else walls
    med = sorted(steady)[len(steady) // 2]
    sb = bucket_streams(S)
    return {
        "n_streams": S,
        "platform": jax.devices()[0].platform,
        "devices": jax.device_count(),
        "mesh_devices": len(stream_mesh_devices(sb)),
        "stream_bucket": sb,
        "per_window_wall_s": walls,
        "steady_state_median_s": med,
        "wall_per_stream_steady_s": med / S,
        "dispatches": ff.train_dispatches,
        "dispatches_per_window": ff.train_dispatches / W,
        "executables": len(ff.trace_counts()),
        "retraces_after_first_window": (ff.retrace_count
                                        - len(ff.trace_counts())),
        "parity_streams": sample,
        "parity_max_abs_diff": parity,
        "probe_preds": probe,
    }


def _run_weak_cell(spec: Dict, n_devices: int) -> Dict:
    """Launch one sweep cell in a fresh CPU-pinned process with its
    host-device count pinned, and parse the cell JSON it prints."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = subprocess_env(n_devices)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_fleet",
         "--weak-cell", json.dumps(spec)],
        env=env, cwd=root, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"weak-scaling cell {spec} on {n_devices} device(s) failed:\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_weak_scaling(streams_list: List[int], devices_list: List[int],
                        *, n_windows: int = 5, epochs: int = 2,
                        batch_size: int = 32, examples: int = 32,
                        seed: int = 0) -> Dict:
    """The thousand-stream weak-scaling sweep: every (S, devices) cell in
    its own process (XLA fixes the device count per process), aggregated
    into the properties CI gates — one dispatch per window at every scale,
    per-stream parity vs the unsharded path, cross-device probe agreement,
    and wall/stream at the largest S within 1.5x of the 8-stream
    baseline."""
    import numpy as np

    cells = []
    for d in devices_list:
        for S in streams_list:
            spec = dict(n_streams=S, n_windows=n_windows, epochs=epochs,
                        batch_size=batch_size, examples=examples, seed=seed)
            cells.append(_run_weak_cell(spec, d))
    by = {(c["n_streams"], c["devices"]): c for c in cells}
    base_S, top_S = min(streams_list), max(streams_list)
    ratios = {
        str(d): (by[(top_S, d)]["wall_per_stream_steady_s"]
                 / max(by[(base_S, d)]["wall_per_stream_steady_s"], 1e-12))
        for d in devices_list}
    cross = {}
    for S in streams_list:
        preds = [np.asarray(by[(S, d)]["probe_preds"], dtype=np.float64)
                 for d in devices_list]
        cross[str(S)] = (float(max(np.max(np.abs(p - preds[0]))
                                   for p in preds[1:]))
                         if len(preds) > 1 else 0.0)
    return {
        "streams": streams_list,
        "devices": devices_list,
        "cell_config": {"n_windows": n_windows, "epochs": epochs,
                        "batch_size": batch_size,
                        "examples_per_window": examples, "seed": seed},
        "cells": cells,
        "wall_per_stream_steady_s": {
            str(d): {str(S): by[(S, d)]["wall_per_stream_steady_s"]
                     for S in streams_list}
            for d in devices_list},
        "weak_scaling_ratio": ratios,
        "weak_scaling_ratio_worst": max(ratios.values()),
        "dispatches_per_window_max": max(c["dispatches_per_window"]
                                         for c in cells),
        "retraces_after_first_window_total": sum(
            c["retraces_after_first_window"] for c in cells),
        "parity_max_abs_diff": max(c["parity_max_abs_diff"] for c in cells),
        "cross_device_probe_max_abs_diff": cross,
        "cross_device_probe_worst": max(cross.values()),
    }


def run(n_streams: int = 8, n_windows: int = 8,
        records_per_window: int = 250, epochs: int = 10,
        batch_size: int = 64,
        weak_streams: Optional[List[int]] = None,
        weak_devices: Optional[List[int]] = None) -> Dict:
    import jax

    from repro.configs import get_config
    from repro.core import lstm_forecaster, pretrain_batch_model

    cfg = get_config("lstm-paper")
    key = jax.random.PRNGKey(1)
    streams, hist0 = _fleet_streams(n_streams, n_windows, records_per_window,
                                    "gradual")
    fc_batch = lstm_forecaster(cfg, epochs=max(epochs // 2, 2),
                               batch_size=256)
    bp, _ = pretrain_batch_model(fc_batch, hist0, jax.random.PRNGKey(0))

    return {
        "benchmark": "fleet_speed_layer",
        "config": {
            "model": "lstm-paper",
            "n_streams": n_streams,
            "n_windows": n_windows,
            "records_per_window": records_per_window,
            "epochs": epochs,
            "batch_size": batch_size,
        },
        "fleet_training": _bench_fleet_training(cfg, streams, epochs,
                                                batch_size, key),
        "fleet_inference": _bench_fleet_inference(cfg, streams, epochs,
                                                  batch_size, key),
        "executor_parity": _bench_executor_parity(cfg, streams, bp, epochs,
                                                  batch_size, key),
        "drift_gated": _bench_drift_gated(cfg, bp, n_streams, n_windows,
                                          records_per_window, epochs,
                                          batch_size, key),
        "batch_refresh": _bench_batch_refresh(cfg, bp, n_streams, n_windows,
                                              records_per_window, epochs,
                                              batch_size, key),
        "weak_scaling": _bench_weak_scaling(
            weak_streams or [8, 64, 256, 1024],
            weak_devices or [1, 2, 4, 8]),
    }


def report(res: Dict) -> str:
    tr, fi, par, dg = (res["fleet_training"], res["fleet_inference"],
                       res["executor_parity"], res["drift_gated"])
    f, s = tr["fleet"], tr["sequential"]
    lines = [
        f"# fleet speed layer: {tr['n_streams']} streams, "
        f"{tr['n_windows']} windows, {tr['devices']} device(s), "
        f"per-window training wall (s)",
        f"{'window':<8}{'fleet(1 dispatch)':>18}{'sequential(xN)':>16}",
    ]
    for w, (fw, sw) in enumerate(zip(f["per_window_wall_s"],
                                     s["per_window_wall_s"])):
        lines.append(f"{w:<8}{fw:>18.4f}{sw:>16.4f}")
    lines += [
        "",
        f"steady state (median): fleet {f['steady_state_median_s']:.4f}s "
        f"({f['wall_per_stream_steady_s'] * 1e3:.1f} ms/stream, "
        f"{f['dispatches_per_sec_steady']:.1f} dispatch/s)  sequential "
        f"{s['steady_state_median_s']:.4f}s "
        f"({s['wall_per_stream_steady_s'] * 1e3:.1f} ms/stream, "
        f"{s['dispatches_per_sec_steady']:.1f} dispatch/s)  "
        f"speedup {tr['speedup_fleet_vs_sequential']:.2f}x",
        f"fleet dispatches: {f['dispatches']} "
        f"({f['dispatches_per_window']:.2f}/window; sequential pays "
        f"{s['dispatches']})",
        f"retraces after first window per bucket: "
        f"{f['retraces_after_first_window']}  (buckets: {f['trace_counts']})",
        f"staging-buffer allocations (whole run): {f['staging_allocs']}",
        f"fleet-vs-sequential max param diff: {tr['max_param_abs_diff']:.2e}",
        "",
        "# fleet inference (one vmapped predict vs N sequential predicts)",
        f"steady state (median): fleet "
        f"{fi['fleet']['steady_state_median_s'] * 1e3:.2f}ms "
        f"(1 dispatch/window)  sequential "
        f"{fi['sequential']['steady_state_median_s'] * 1e3:.2f}ms "
        f"({fi['n_streams']} dispatches/window)  "
        f"speedup {fi['speedup_fleet_vs_sequential']:.2f}x",
        f"per-stream parity: {fi['per_stream_parity_max_abs_diff']:.2e}",
        f"int8 sync: {fi['int8_sync']['sync_bytes_int8_per_stream']:.0f} B"
        f"/stream vs {fi['int8_sync']['sync_bytes_float_per_stream']:.0f} B "
        f"float ({fi['int8_sync']['transfer_ratio']:.1f}x smaller), "
        f"batched int8 predict "
        f"{fi['int8_sync']['steady_state_median_s'] * 1e3:.2f}ms",
        "",
        "# executor parity (fleet run vs N sequential single-stream runs)",
        f"max per-window RMSE divergence: {par['rmse_max_abs_diff']:.2e}",
        f"train dispatches: {par['train_dispatches']} "
        f"({par['dispatches_per_window']:.2f}/window)",
        "",
        "# drift-gated retraining vs every-window",
    ]
    for scenario, d in dg.items():
        lines.append(
            f"{scenario:<10} retrains {d['total_retrains']}"
            f"/{d['every_window_retrains']} (skipped "
            f"{d['skipped_retrains']}), dispatches "
            f"{d['train_dispatches_gated']}"
            f"/{d['train_dispatches_every_window']}, hybrid RMSE "
            f"{d['hybrid_rmse_gated']:.4f} vs "
            f"{d['hybrid_rmse_every_window']:.4f} "
            f"(ratio {d['hybrid_rmse_ratio']:.3f})")
    br = res["batch_refresh"]
    lines += [
        "",
        "# batch-model refresh from archived drifted windows (abrupt)",
        f"rounds {br['refresh']['rounds']}, dispatches "
        f"{br['refresh']['dispatches']} "
        f"({br['dispatches_per_round']:.2f}/round), refreshed streams "
        f"{sorted(br['refresh']['refreshed'])}",
        f"batch RMSE {br['batch_rmse_refresh']:.4f} vs "
        f"{br['batch_rmse_baseline']:.4f} unrefreshed; hybrid "
        f"{br['hybrid_rmse_refresh']:.4f} vs "
        f"{br['hybrid_rmse_baseline']:.4f}",
        "",
        "# weak scaling, host-device rehearsal (one CPU-pinned subprocess "
        "per cell; wall/stream, steady median)",
    ]
    ws = res["weak_scaling"]
    lines.append(f"{'streams':<10}" + "".join(
        f"{str(d) + ' dev (ms)':>14}" for d in ws["devices"]))
    for S in ws["streams"]:
        row = f"{S:<10}"
        for d in ws["devices"]:
            wps = ws["wall_per_stream_steady_s"][str(d)][str(S)]
            row += f"{wps * 1e3:>14.3f}"
        lines.append(row)
    lines += [
        f"weak-scaling ratio (wall/stream at S={max(ws['streams'])} vs "
        f"S={min(ws['streams'])}): worst "
        f"{ws['weak_scaling_ratio_worst']:.3f} across device counts",
        f"dispatches/window max {ws['dispatches_per_window_max']:.2f}, "
        f"retraces after first window {ws['retraces_after_first_window_total']}",
        f"parity vs unsharded path: {ws['parity_max_abs_diff']:.2e}; "
        f"cross-device probe agreement: "
        f"{ws['cross_device_probe_worst']:.2e}",
    ]
    return "\n".join(lines)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized run: 4 streams, 4 windows, 3 epochs, "
                        "120 records")
    p.add_argument("--streams", type=int, default=None)
    p.add_argument("--windows", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--records", type=int, default=None)
    p.add_argument("--devices", type=int, default=None,
                   help="host devices to expose to XLA (default: the "
                        "machine's core count); the fleet paths shard "
                        "their stream axis across them")
    p.add_argument("--weak-cell", default=None, metavar="SPEC_JSON",
                   help=argparse.SUPPRESS)  # sweep child-process mode
    p.add_argument("--out", default="BENCH_fleet.json")
    args = p.parse_args()

    if args.weak_cell is not None:
        # child of the weak-scaling sweep: the parent pinned the device
        # count in our environment; print the cell JSON and nothing else
        enable_compile_cache()
        print(json.dumps(_weak_cell(json.loads(args.weak_cell))))
        return

    # must land before the first (lazy) jax import anywhere below: expose
    # the cores as XLA devices so the fleet's stream axis has a mesh
    ensure_host_devices(args.devices)
    enable_compile_cache()

    if args.smoke:
        defaults = dict(n_streams=4, n_windows=4, epochs=3,
                        records_per_window=120,
                        weak_streams=[8, 64, 1024], weak_devices=[1, 2])
    else:
        defaults = dict(n_streams=8, n_windows=8, epochs=10,
                        records_per_window=250)
    if args.streams is not None:
        defaults["n_streams"] = args.streams
    if args.windows is not None:
        defaults["n_windows"] = args.windows
    if args.epochs is not None:
        defaults["epochs"] = args.epochs
    if args.records is not None:
        defaults["records_per_window"] = args.records

    res = run(**defaults)
    print(report(res))
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
