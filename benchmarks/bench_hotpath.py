"""Speed-layer hot-path benchmark: per-window training wall-clock, compiled
vs legacy, tracked as ``BENCH_hotpath.json`` from this PR onward.

The paper's latency claim (Table 3, Sec. 6.3) needs speed-layer retraining to
fit inside every 30 s window.  The legacy path re-traces and re-compiles the
train step every window and dispatches one device call per minibatch; the
compiled path (``repro.training.compiled.CompiledForecaster``) compiles one
epoch-scan executable per shape bucket and dispatches once per window.  This
benchmark drives both over the same drifting windowed stream (paper LSTM
config: H=40, lag 5, 5 features, speed layer bs 64) and records:

* per-window speed-train wall-clock, for each path;
* steady-state (windows >= 2) mean wall and windows/sec;
* first-window vs steady-state ratio (the amortized compile);
* retrace counts (measured trace-time counter on the compiled path; the
  legacy path re-jits by construction, one trace per window);
* ``speedup_steady_state`` = legacy steady mean / compiled steady mean.

Since PR 3 the same file also tracks the two kernel-backlog closures
(extended, not forked, per ROADMAP):

* ``fused_vjp`` — the compiled hot path with ``use_pallas=True``, i.e. the
  cached train step running the fused-sequence Pallas kernel end to end
  (fused forward + fused backward via ``jax.custom_vjp``), window-driven
  exactly like ``compiled``; ``speedup_fused_vs_scan_autodiff`` compares
  their steady states (acceptance: fused is no slower);
* ``backward_pass`` — per-train-step ``value_and_grad`` wall for the
  scan-autodiff baseline vs the fused VJP, plus forward-only walls, at the
  paper's speed-layer batch shape;
* ``int8_inference`` — per-window predict wall on float vs int8-synced
  params (the ``quantized_sync`` edge path through the ``int8_matmul``
  kernel) and the float-vs-int8 ``model_nbytes`` the per-window sync
  transfers.

    PYTHONPATH=src python -m benchmarks.bench_hotpath            # paper-ish
    PYTHONPATH=src python -m benchmarks.bench_hotpath --smoke    # CI: seconds
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List


def _stream_windows(n_windows: int, records_per_window: int):
    """The paper's drifting wind-turbine stream, windowed and supervised —
    same construction as ``launch.edge_cloud.build_real_pipeline``."""
    import numpy as np

    from repro.core import WindowPlan, WindowedStream
    from repro.streams.normalize import MinMaxScaler
    from repro.streams.sources import gradual_drift, wind_turbine_series

    series = wind_turbine_series(
        1600 + records_per_window * n_windows + 5, seed=0)
    hist, stream_raw = series[:1600], series[1600:]
    stream_raw = gradual_drift(stream_raw, alphas=np.full(5, 1.5e-3), seed=1)
    scaler = MinMaxScaler.fit(hist)
    stream = WindowedStream(scaler.transform(stream_raw),
                            WindowPlan(n_windows, records_per_window, lag=5))
    return [stream.supervised(w) for w in range(n_windows)]


def _drive(fc, windows, key) -> List[float]:
    """One fc.train per window (cold params each window — the paper's speed
    layer), returning per-window wall seconds."""
    from repro.core.stages import split_chain

    keys = split_chain(key, len(windows))
    walls = []
    for data, k in zip(windows, keys):
        t0 = time.perf_counter()
        fc.train(data, None, k)
        walls.append(time.perf_counter() - t0)
    return walls


def _summary(walls: List[float], retraces: List[int]) -> Dict:
    steady = walls[1:] if len(walls) > 1 else walls
    mean_steady = sum(steady) / len(steady)
    median_steady = sorted(steady)[len(steady) // 2]
    return {
        "per_window_wall_s": walls,
        "retraces_per_window": retraces,
        "first_window_wall_s": walls[0],
        "steady_state_wall_s": mean_steady,
        # at the compiled path's ms scale a single scheduler hiccup skews the
        # mean; cross-path comparisons use the median
        "steady_state_median_s": median_steady,
        "first_vs_steady_ratio": walls[0] / max(mean_steady, 1e-12),
        "windows_per_sec_steady": 1.0 / max(mean_steady, 1e-12),
        "retraces_after_first_window": sum(retraces[1:]),
    }


def _bench_backward_pass(cfg, cfg_fused, batch_size: int, iters: int) -> Dict:
    """Per-train-step ``value_and_grad`` wall: autodiff through the jnp scan
    (the pre-PR-3 training path) vs the fused Pallas VJP — the tentpole's
    backward-pass closure, measured at the paper's speed-layer batch shape."""
    import jax

    from repro.models import lstm as lstm_mod

    c = cfg.lstm
    p = lstm_mod.init_params(cfg, jax.random.PRNGKey(0))
    batch = {
        "x": jax.random.normal(jax.random.PRNGKey(1),
                               (batch_size, c.lag, c.n_features)),
        "y": jax.random.normal(jax.random.PRNGKey(2),
                               (batch_size, c.out_dim)),
    }

    def timed(fn):
        r = fn(p, batch)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(p, batch)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    out = {}
    for label, c_ in (("scan_autodiff", cfg), ("fused_vjp", cfg_fused)):
        vg = jax.jit(jax.value_and_grad(
            lambda p, b, c_=c_: lstm_mod.loss_fn(c_, p, b)[0]))
        fwd = jax.jit(lambda p, b, c_=c_: lstm_mod.loss_fn(c_, p, b)[0])
        out[f"{label}_step_s"] = timed(vg)
        out[f"{label}_forward_s"] = timed(fwd)
    out["iters"] = iters
    out["batch_shape"] = [batch_size, c.lag, c.n_features]
    out["fused_vs_scan_step_speedup"] = (
        out["scan_autodiff_step_s"] / max(out["fused_vjp_step_s"], 1e-12))
    return out


def _bench_int8_inference(fc, windows, key, iters: int) -> Dict:
    """Edge-inference closure: predict wall on float params vs the
    int8-synced model (``quantize_tree`` -> ``QTensor`` leaves -> the fused
    ``int8_matmul`` kernel), plus the per-window sync transfer sizes."""
    import jax

    from repro.serving.quantize import quantize_tree, tree_nbytes

    params, _ = fc.train(windows[0], None, key)
    qparams = quantize_tree(params, min_size=64)
    x = windows[-1]["x"]

    def timed(p):
        r = fc.predict(p, x)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fc.predict(p, x)
        del r
        return (time.perf_counter() - t0) / iters

    nb_f, nb_q = tree_nbytes(params), tree_nbytes(qparams)
    t_float = timed(params)
    t_int8 = timed(qparams)
    return {
        "predict_float_s": t_float,
        "predict_int8_s": t_int8,
        # CI gates this at ~1: on interpret backends (CPU CI) the serving
        # path dequantizes a synced QTensor tree once per install and
        # serves the cached float tree, so quantized predict keeps the 4x
        # sync-transfer win without paying the interpreted per-step qmatmul
        "predict_ratio_int8_vs_float": t_int8 / max(t_float, 1e-12),
        "iters": iters,
        "batch": int(x.shape[0]),
        "model_nbytes_float": nb_f,
        "model_nbytes_int8": nb_q,
        "sync_bytes_ratio": nb_f / max(nb_q, 1),
    }


def run(n_windows: int = 8, records_per_window: int = 250,
        epochs: int = 10, batch_size: int = 64,
        micro_iters: int = 50) -> Dict:
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core import lstm_forecaster
    from repro.core.stages import split_chain

    cfg = get_config("lstm-paper")
    cfg_fused = dataclasses.replace(cfg, use_pallas=True)
    windows = _stream_windows(n_windows, records_per_window)
    key = jax.random.PRNGKey(1)

    # -- compiled hot path (scan-autodiff) vs fused-VJP hot path -------------
    # the two paths are driven *interleaved*, window by window, so transient
    # host noise (this is a shared container) biases neither side
    fc = lstm_forecaster(cfg, epochs=epochs, batch_size=batch_size)
    ff = lstm_forecaster(cfg_fused, epochs=epochs, batch_size=batch_size)
    eng, feng = fc.engine, ff.engine
    walls, retraces, seen = [], [], 0
    fwalls, fretraces, fseen = [], [], 0
    for data, k in zip(windows, split_chain(key, n_windows)):
        t0 = time.perf_counter()
        fc.train(data, None, k)
        walls.append(time.perf_counter() - t0)
        retraces.append(eng.retrace_count - seen)
        seen = eng.retrace_count
        t0 = time.perf_counter()
        ff.train(data, None, k)
        fwalls.append(time.perf_counter() - t0)
        fretraces.append(feng.retrace_count - fseen)
        fseen = feng.retrace_count
    compiled = _summary(walls, retraces)
    compiled["shape_buckets"] = eng.cache_size
    fused = _summary(fwalls, fretraces)
    fused["shape_buckets"] = feng.cache_size

    # -- legacy baseline (pre-optimization fit: re-jit every window) ---------
    fl = lstm_forecaster(cfg, epochs=epochs, batch_size=batch_size,
                         compiled=False)
    lwalls = _drive(fl, windows, key)
    # each legacy fit builds a fresh jit, so it retraces every distinct batch
    # shape every window: the full batch plus the ragged tail when n % bs != 0
    # (a sub-batch-size window has only the one ragged shape)
    lretraces = [1 if len(w["x"]) % batch_size == 0 or len(w["x"]) < batch_size
                 else 2 for w in windows]
    legacy = _summary(lwalls, lretraces)

    return {
        "benchmark": "speed_layer_hotpath",
        "config": {
            "model": "lstm-paper",
            "n_windows": n_windows,
            "records_per_window": records_per_window,
            "epochs": epochs,
            "batch_size": batch_size,
        },
        "compiled": compiled,
        "fused_vjp": fused,
        "legacy": legacy,
        "speedup_steady_state": (legacy["steady_state_wall_s"]
                                 / max(compiled["steady_state_wall_s"], 1e-12)),
        "speedup_fused_vs_scan_autodiff": (
            compiled["steady_state_median_s"]
            / max(fused["steady_state_median_s"], 1e-12)),
        "backward_pass": _bench_backward_pass(cfg, cfg_fused, batch_size,
                                              micro_iters),
        "int8_inference": _bench_int8_inference(fc, windows, key,
                                                micro_iters),
    }


def report(res: Dict) -> str:
    c, l, f = res["compiled"], res["legacy"], res["fused_vjp"]
    lines = [
        "# speed-layer hot path: per-window training wall-clock (s)",
        f"{'window':<8}{'compiled':>12}{'fused_vjp':>12}{'legacy':>12}"
        f"{'retraces(c)':>12}",
    ]
    for w, (cw, fw, lw, r) in enumerate(zip(c["per_window_wall_s"],
                                            f["per_window_wall_s"],
                                            l["per_window_wall_s"],
                                            c["retraces_per_window"])):
        lines.append(f"{w:<8}{cw:>12.4f}{fw:>12.4f}{lw:>12.4f}{r:>12}")
    bp, q = res["backward_pass"], res["int8_inference"]
    lines += [
        "",
        f"steady-state wall: compiled {c['steady_state_wall_s']:.4f}s "
        f"({c['windows_per_sec_steady']:.1f} windows/s)  "
        f"fused_vjp {f['steady_state_wall_s']:.4f}s "
        f"({f['windows_per_sec_steady']:.1f} windows/s)  "
        f"legacy {l['steady_state_wall_s']:.4f}s "
        f"({l['windows_per_sec_steady']:.1f} windows/s)",
        f"first-vs-steady ratio: compiled {c['first_vs_steady_ratio']:.1f}x  "
        f"legacy {l['first_vs_steady_ratio']:.1f}x",
        f"retraces after first window: compiled "
        f"{c['retraces_after_first_window']} "
        f"(buckets={c['shape_buckets']})  legacy "
        f"{l['retraces_after_first_window']}",
        f"steady-state speedup vs legacy: {res['speedup_steady_state']:.1f}x",
        f"fused-VJP vs scan-autodiff steady state: "
        f"{res['speedup_fused_vs_scan_autodiff']:.2f}x",
        "",
        "# backward pass (per train step, value_and_grad, "
        f"batch {bp['batch_shape']})",
        f"scan-autodiff step {bp['scan_autodiff_step_s']*1e6:>8.0f}us  "
        f"(fwd {bp['scan_autodiff_forward_s']*1e6:.0f}us)",
        f"fused-VJP     step {bp['fused_vjp_step_s']*1e6:>8.0f}us  "
        f"(fwd {bp['fused_vjp_forward_s']*1e6:.0f}us)   "
        f"step speedup {bp['fused_vs_scan_step_speedup']:.2f}x",
        "",
        f"# int8 edge inference (batch {q['batch']})",
        f"predict: float {q['predict_float_s']*1e3:.2f}ms  "
        f"int8 {q['predict_int8_s']*1e3:.2f}ms  "
        f"(ratio {q['predict_ratio_int8_vs_float']:.2f}x)",
        f"model sync bytes: float {q['model_nbytes_float']}  "
        f"int8 {q['model_nbytes_int8']}  "
        f"({q['sync_bytes_ratio']:.1f}x smaller)",
    ]
    return "\n".join(lines)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized run: 4 windows, 3 epochs, 120 records")
    p.add_argument("--windows", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--records", type=int, default=None)
    p.add_argument("--out", default="BENCH_hotpath.json")
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        defaults = dict(n_windows=4, epochs=3, records_per_window=120,
                        micro_iters=15)
    else:
        defaults = dict(n_windows=8, epochs=10, records_per_window=250)
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
