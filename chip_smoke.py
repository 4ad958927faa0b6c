#!/usr/bin/env python3
"""Chip smoke run: the fleet's train-and-serve path, once, on a TPU.

    python chip_smoke.py             # phases A, B and C on one chip
    python chip_smoke.py --chips 4   # the stream mesh over four chips, only

It drives the edge-cloud-integrated deployment through the calls behind
``python -m repro.launch.edge_cloud --real --streams N`` — the
``build_fleet_pipeline`` + ``FleetBusExecutor`` pair — at the paper model's
full width (``lstm-paper``: LSTM(40) -> Dense(10) -> Dense(1), lag 5, five
features, 10,981 parameters), with data generated from fixed seeds:

* A, float model sync: 8 streams x 4 windows of 250 records, fast epochs,
  the request plane answering forecast queries from device-resident state
  through 4 batch slots.
* B, int8 model sync: A with ``quantized_sync=True``.  Serving runs the
  int8 Pallas kernel, and the serving executable must hold a
  ``tpu_custom_call`` (a compiled kernel, not the interpreter).
* C, the fused kernels: ``lstm_sequence`` and its custom-VJP gradient at
  the paper shape, against the per-step kernel scan and the ``ref.py``
  oracle on the chip, and the oracle on the CPU.

``--chips 4`` runs only the stream mesh: a 1024-stream fleet fit and
predict with the stream axis over four chips, against the same fleet on one
chip in this process.

Each phase prints one JSON line of what it measured: programs built and
their seconds (the program's compile records, ``repro.tracing``; a load
from the persistent cache is timed as its read),
steady seconds timed to ``block_until_ready``, dispatches, retraces,
requests, parity and ``peak_bytes_in_use``.  Each check raises on a miss,
so any failure exits non-zero, as does a run where JAX finds no TPU.  The
last line of a passing run is one JSON object naming the device.  The
compile cache is placed by ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# -- tolerances --------------------------------------------------------------
# The TPU's default precision for an f32 matmul rounds both operands to
# bfloat16 (relative error <= 2**-9 each) and accumulates in f32, so each
# product carries a relative error of at most 2**-8.  The forecaster's gate
# pre-activations sum |x * w| terms to a few units (at most ~4), which moves
# a gate by at most ~2**-8 * 4 = 0.016; sigmoid and tanh have slope <= 1 and
# the forget gate keeps the recurrence contracting, so the hidden state and
# the [0, 1]-scaled forecast move by no more.  Same params on two backends:
PROBE_ATOL = 2e-2
# Fleet mean RMSE of a whole run on the chip vs the same seed on the CPU.
# Every optimizer step sees the rounding above, so the trained weights part
# ways, but the error level a fit reaches is set by the data, not by the
# last bits of its weights: a 5% relative band.
RMSE_RTOL = 5e-2
# Fused kernels vs the per-step kernel scan and the jnp oracle: the bound
# above on unit-scale hidden states (x ~ N(0, 1), weights ~ 0.2 N(0, 1));
# gradients relative to their largest entry.
KERNEL_ATOL = 2e-2
GRAD_RTOL = 2e-2
# One fleet sharded over four chips vs the same fleet on one, per stream.
# Streams never interact, but XLA compiles the fit for 256 streams per chip
# in one layout and for 1024 in the other, and may sum a stream's f32
# gradients in another order; training carries those last-bit differences
# into the weights (1.4e-5 at most on a v5e).  A weight that close to a
# bfloat16 rounding boundary then rounds to its neighbour under the default
# precision, so a forecast may move by up to the probe bound above.  A
# stream mixed up with another, or a shard read from the wrong chip, moves
# it by the data's own scale.
MESH_ATOL = PROBE_ATOL

STREAMS, WINDOWS, RECORDS, QPS, SLOTS = 8, 4, 250, 1.0, 4
MESH_STREAMS, MESH_WINDOWS = 1024, 3
PAPER_SHAPE = (64, 5, 5, 40)  # B, T, F, H
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start)?\(")


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_custom_call(hlo: str, what: str) -> None:
    """A compiled Mosaic kernel appears in the HLO as a ``tpu_custom_call``;
    an interpreted one would have lowered to plain HLO ops."""
    check("tpu_custom_call" in hlo, f"{what}: no tpu_custom_call in its HLO")


def compiled_since(t0: float) -> dict:
    """Programs built since ``t0`` (``time.perf_counter()``) and their
    seconds, from the program's ``compile:<function>`` records; a load from
    the persistent cache counts, timed as its read."""
    from repro import tracing

    recs = tracing.records(since=t0)
    check(recs is not None, "the span ring dropped compile records")
    recs = [r for r in recs if r.name.startswith("compile:")]
    return {"compile_s": sum(r.dur for r in recs), "compiles": len(recs)}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def tree_max_abs(a, b) -> float:
    import jax

    return max(max_abs(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                             jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# Phases A and B: the fleet through the bus executor
# ---------------------------------------------------------------------------


def run_fleet(device, quantized: bool):
    """One edge-cloud-integrated fleet run, every array on ``device``."""
    import jax

    from repro.launch.edge_cloud import build_fleet_pipeline
    from repro.runtime import (FleetBusExecutor, edge_cloud_integrated,
                               paper_topology)

    with jax.default_device(device):
        stages, bp, streams, cost = build_fleet_pipeline(
            STREAMS, WINDOWS, fast=True, records_per_window=RECORDS,
            devices=[device])
        ex = FleetBusExecutor(stages, edge_cloud_integrated(),
                              paper_topology(), cost, quantized_sync=quantized,
                              qps=QPS, serve_slots=SLOTS)
        res = ex.run(streams, bp, jax.random.PRNGKey(1))
    return stages, streams, res


def reference_predict(params, x):
    """The forecaster's plain jnp forward; int8 trees are dequantized first
    (``int8_matmul_ref`` semantics), so no kernel is involved."""
    from repro.configs import get_config
    from repro.models import lstm
    from repro.serving.quantize import dequantize_tree

    return lstm.predict(get_config("lstm-paper"), dequantize_tree(params), x)


def fleet_phase(name: str, quantized: bool, chip, cpu) -> dict:
    import jax
    import numpy as np

    from repro.runtime import fleet_key_chains
    from repro.training.compiled import bucket_streams

    t0 = time.perf_counter()
    stages, streams, res = run_fleet(chip, quantized)
    cold_run_s = time.perf_counter() - t0
    cold = compiled_since(t0)
    fc = stages.speed_training.forecaster
    ids = list(streams)
    fit_retraces = fc.retrace_count - len(fc.trace_counts())

    srv = res.serving
    check(srv is not None and srv["n_answered"] > 0,
          f"{name}: the request plane answered no query")
    answers = [a for q in res.queries for a in q.answer]
    check(len(answers) > 0 and bool(np.all(np.isfinite(answers))),
          f"{name}: non-finite or missing query answers")
    check(res.train_dispatches == res.n_windows,
          f"{name}: {res.train_dispatches} fit dispatches for "
          f"{res.n_windows} windows")
    check(srv["dispatches_per_tick"] == 1.0,
          f"{name}: {srv['dispatches_per_tick']} dispatches per serving tick")

    # the chip-trained serving params on a fixed probe batch: the serving
    # executable on the chip vs the plain forward on the CPU
    params_seq = [res.final_params[sid] for sid in ids]
    check(all(p is not None for p in params_seq),
          f"{name}: a stream ended the run without a speed model")
    rng = np.random.default_rng(2024)
    probe = rng.uniform(0.0, 1.0, (STREAMS, 16, 5, 5)).astype(np.float32)
    with jax.default_device(chip):
        chip_pred = fc.predict_fleet(params_seq, list(probe))
    ref_fn = jax.jit(reference_predict)
    with jax.default_device(cpu):
        cpu_pred = [np.asarray(ref_fn(p, x))
                    for p, x in zip(params_seq, probe)]
    probe_diff = max(max_abs(a, b) for a, b in zip(chip_pred, cpu_pred))
    check(all(np.all(np.isfinite(p)) for p in chip_pred),
          f"{name}: non-finite chip predictions")
    check(probe_diff <= PROBE_ATOL,
          f"{name}: chip vs CPU probe predictions differ by {probe_diff:.3g}"
          f" > {PROBE_ATOL}")

    sb = bucket_streams(STREAMS)
    x0 = np.zeros((sb, SLOTS, 5, 5), np.float32)
    stacked = jax.tree_util.tree_map(lambda *ls: np.stack(ls),
                                     *(params_seq + params_seq[:1]
                                       * (sb - STREAMS)))
    with jax.default_device(chip):
        serving_hlo = fc.predict_fleet_fn(sb).lower(
            stacked, x0).compile().as_text()
    if quantized:
        require_custom_call(serving_hlo, f"{name}: int8 serving executable")

    # steady state: every executable is warm; time whole-fleet fits and
    # serving ticks, counting any trace or compile they cause
    keys = fleet_key_chains(jax.random.PRNGKey(7), ids, WINDOWS)
    datas = [[streams[sid].supervised(w) for sid in ids]
             for w in range(WINDOWS)]
    ticks = [list(rng.uniform(0.0, 1.0, (STREAMS, SLOTS, 5, 5))
                  .astype(np.float32)) for _ in range(8)]
    with jax.default_device(chip):
        fc.predict_fleet(params_seq, ticks[0])  # stacks the serving tree
        traces0 = (fc.retrace_count
                   + sum(fc.predict_trace_counts().values()))
        steady_mark = time.perf_counter()
        fit_s = [fc.train_fleet(d, [keys[sid][w] for sid in ids])[1]
                 for w, d in enumerate(datas)]
        tick_s = [timed(fc.predict_fleet, params_seq, xs)[1] for xs in ticks]
        steady = compiled_since(steady_mark)
        traces = (fc.retrace_count
                  + sum(fc.predict_trace_counts().values()) - traces0)
    check(traces == 0 and steady["compiles"] == 0,
          f"{name}: {traces} traces and {steady['compiles']} compiles in "
          "steady windows")

    # the same seed end to end on the CPU backend
    _, _, res_cpu = run_fleet(cpu, quantized)
    rmse_chip, rmse_cpu = res.mean_rmse(), res_cpu.mean_rmse()
    rmse_rel = {k: abs(rmse_chip[k] - rmse_cpu[k]) / rmse_cpu[k]
                for k in rmse_chip}
    for k, rel in rmse_rel.items():
        check(np.isfinite(rmse_chip[k]) and rel <= RMSE_RTOL,
              f"{name}: {k} RMSE {rmse_chip[k]:.5f} on the chip vs "
              f"{rmse_cpu[k]:.5f} on the CPU ({rel:.3f} > {RMSE_RTOL})")

    return {
        "phase": name,
        "streams": STREAMS, "windows": res.n_windows,
        "records_per_window": RECORDS, "int8_sync": quantized,
        "cold_run_s": cold_run_s, **cold,
        "steady_fit_window_s": statistics.median(fit_s),
        "steady_tick_s": statistics.median(tick_s),
        "fit_dispatches_per_window": res.train_dispatches / res.n_windows,
        "serving_dispatches_per_tick": srv["dispatches_per_tick"],
        "fit_retraces_after_first_window": fit_retraces,
        "traces_in_steady": traces,
        "compiles_in_steady": steady["compiles"],
        "requests": srv["n_requests"],
        "answered": srv["n_answered"],
        "starved": srv["n_starved"],
        "serving_has_tpu_custom_call": "tpu_custom_call" in serving_hlo,
        "probe_max_abs_diff_vs_cpu": probe_diff,
        "rmse_chip": rmse_chip, "rmse_cpu": rmse_cpu,
        "rmse_rel_diff": rmse_rel,
        "peak_bytes_in_use": peak_bytes(chip),
    }


# ---------------------------------------------------------------------------
# Phase C: the fused LSTM kernels at the paper shape
# ---------------------------------------------------------------------------


def kernel_phase(chip, cpu) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.lstm_cell.ops import lstm_sequence, lstm_sequence_scan
    from repro.kernels.lstm_cell.ref import lstm_sequence_ref

    B, T, F, H = PAPER_SHAPE
    rng = np.random.default_rng(0)
    args = (rng.normal(size=(B, T, F)).astype(np.float32),
            (0.2 * rng.normal(size=(F, 4 * H))).astype(np.float32),
            (0.2 * rng.normal(size=(H, 4 * H))).astype(np.float32),
            (0.2 * rng.normal(size=(4 * H,))).astype(np.float32))
    ct = rng.normal(size=(B, H)).astype(np.float32)  # cotangent of h

    def grads(seq):
        return jax.jit(jax.grad(
            lambda x, wx, wh, b, ct: jnp.sum(seq(x, wx, wh, b) * ct),
            argnums=(0, 1, 2, 3)))

    grad_fused, grad_ref = grads(lstm_sequence), grads(lstm_sequence_ref)
    ref_fwd = jax.jit(lstm_sequence_ref)
    mark = time.perf_counter()
    with jax.default_device(chip):
        dev_args = jax.device_put(args, chip)
        dev_ct = jax.device_put(ct, chip)
        h_fused = lstm_sequence(*dev_args)
        h_scan = lstm_sequence_scan(*dev_args)
        h_ref = ref_fwd(*dev_args)
        g_fused = grad_fused(*dev_args, dev_ct)
        g_ref = grad_ref(*dev_args, dev_ct)
        jax.block_until_ready((h_fused, h_scan, h_ref, g_fused, g_ref))
        compile_ = compiled_since(mark)
        fwd_s = statistics.median(timed(lstm_sequence, *dev_args)[1]
                                  for _ in range(20))
        grad_s = statistics.median(timed(grad_fused, *dev_args, dev_ct)[1]
                                   for _ in range(20))
        fwd_hlo = lstm_sequence.lower(*dev_args).compile().as_text()
        grad_hlo = grad_fused.lower(*dev_args, dev_ct).compile().as_text()
    require_custom_call(fwd_hlo, "lstm_sequence forward")
    require_custom_call(grad_hlo, "lstm_sequence custom-VJP gradient")
    with jax.default_device(cpu):
        h_cpu = ref_fwd(*args)
        g_cpu = grad_ref(*args, ct)

    fwd_diff = {"vs_kernel_scan": max_abs(h_fused, h_scan),
                "vs_ref_chip": max_abs(h_fused, h_ref),
                "vs_ref_cpu": max_abs(h_fused, h_cpu)}
    for k, d in fwd_diff.items():
        check(d <= KERNEL_ATOL, f"lstm_sequence {k}: {d:.3g} > {KERNEL_ATOL}")
    grad_rel = {}
    for ref_name, ref in (("ref_chip", g_ref), ("ref_cpu", g_cpu)):
        for gname, gf, gr in zip(("dx", "dwx", "dwh", "db"), g_fused, ref):
            rel = max_abs(gf, gr) / max(float(np.max(np.abs(gr))), 1e-30)
            grad_rel[f"{gname}_vs_{ref_name}"] = rel
            check(rel <= GRAD_RTOL,
                  f"lstm_sequence {gname} vs {ref_name}: {rel:.3g} relative "
                  f"> {GRAD_RTOL}")
    return {
        "phase": "C", "shape_B_T_F_H": list(PAPER_SHAPE), **compile_,
        "steady_fwd_s": fwd_s, "steady_grad_s": grad_s,
        "fwd_max_abs_diff": fwd_diff, "grad_rel_diff": grad_rel,
        "peak_bytes_in_use": peak_bytes(chip),
    }


# ---------------------------------------------------------------------------
# --chips 4: the stream mesh
# ---------------------------------------------------------------------------


def mesh_phase(devices) -> dict:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import lstm_fleet_forecaster
    from repro.runtime import fleet_key_chains
    from repro.streams.sources import fleet_windowed_streams
    from repro.training.compiled import bucket_examples, bucket_streams

    cfg = get_config("lstm-paper")
    streams, _ = fleet_windowed_streams(MESH_STREAMS, MESH_WINDOWS + 1,
                                        RECORDS, "gradual",
                                        alphas=np.full(5, 1.5e-3))
    ids = list(streams)
    keys = fleet_key_chains(jax.random.PRNGKey(1), ids, MESH_WINDOWS)
    datas = [[streams[sid].supervised(w) for sid in ids]
             for w in range(MESH_WINDOWS)]
    probe = [streams[sid].supervised(MESH_WINDOWS)["x"] for sid in ids]

    out = {"phase": "mesh", "streams": MESH_STREAMS,
           "windows": MESH_WINDOWS, "records_per_window": RECORDS}
    params, preds, fleets = {}, {}, {}
    for label, devs in (("chips4", devices), ("chip1", devices[:1])):
        ff = lstm_fleet_forecaster(cfg, epochs=10, batch_size=64,
                                   devices=devs)
        mark = time.perf_counter()
        walls = []
        with jax.default_device(devs[0]):
            for w, d in enumerate(datas):
                p, wall = ff.train_fleet(d, [keys[sid][w] for sid in ids])
                walls.append(wall)
            pred = ff.predict_fleet(p, probe)
            pred_s = statistics.median(
                timed(ff.predict_fleet, p, probe)[1] for _ in range(5))
        stacked = p[0].owner.stacked
        leaf = jax.tree_util.tree_leaves(stacked)[0]
        shard_rows = sorted({s.data.shape[0] for s in leaf.addressable_shards})
        check(leaf.sharding.device_set == set(devs)
              and shard_rows == [bucket_streams(MESH_STREAMS) // len(devs)],
              f"{label}: stacked params on {len(leaf.sharding.device_set)} "
              f"device(s), shard rows {shard_rows}")
        params[label], preds[label], fleets[label] = (
            jax.tree_util.tree_map(np.asarray, jax.device_get(stacked)),
            pred, ff)
        out[label] = {
            "devices": len(devs), "first_window_s": walls[0],
            "steady_fit_window_s": statistics.median(walls[1:]),
            "steady_predict_s": pred_s,
            "fit_dispatches_per_window": ff.train_dispatches / len(datas),
            "fit_retraces_after_first_window": (ff.retrace_count
                                                - len(ff.trace_counts())),
            "shard_rows": shard_rows, **compiled_since(mark),
            "peak_bytes_in_use": [peak_bytes(d) for d in devs],
        }

    # which collectives the sharded fit holds: streams are independent, so
    # none is expected
    ff4 = fleets["chips4"]
    sb = bucket_streams(MESH_STREAMS)
    nb = bucket_examples(len(datas[0][0]["x"]), ff4.batch_size)
    key_sds = jax.ShapeDtypeStruct((sb, 2), np.uint32)
    carry = jax.eval_shape(jax.vmap(lambda k: ff4.opt.init(ff4.model.init(k))),
                           key_sds)
    f32 = np.float32
    fit_hlo = ff4.fleet_fit_fn(sb, nb).lower(
        carry, key_sds, key_sds,
        jax.ShapeDtypeStruct((sb, nb, 5, 5), f32),
        jax.ShapeDtypeStruct((sb, nb, 1), f32),
        jax.ShapeDtypeStruct((sb, nb), f32)).compile().as_text()
    found = sorted(m.group(1) for m in COLLECTIVE.finditer(fit_hlo))
    stream_diffs = [max_abs(a, b) for a, b in zip(preds["chips4"],
                                                  preds["chip1"])]
    out.update({
        "param_max_abs_diff_4_vs_1": tree_max_abs(params["chips4"],
                                                  params["chip1"]),
        "pred_max_abs_diff_4_vs_1": max(stream_diffs),
        "streams_bitwise_equal": sum(d == 0.0 for d in stream_diffs),
        "fit_collectives": len(found),
        "fit_collective_kinds": sorted(set(found)),
    })
    check(out["pred_max_abs_diff_4_vs_1"] <= MESH_ATOL,
          f"4-chip fleet vs 1-chip fleet: {json.dumps(out)}")
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: phases A, B and C on one chip; 4: only the "
                        "stream mesh over four chips vs one")
    args = p.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default backend is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # imported before any program builds, so each leaves its record
    from repro import tracing  # noqa: F401
    chip = devices[0]
    print(json.dumps({"compile_cache": cache, "devices": len(devices),
                      "device_kind": chip.device_kind}), flush=True)
    if args.chips == 4:
        used = devices[:4]
        print(json.dumps(mesh_phase(used)), flush=True)
    else:
        used = [chip]
        cpu = jax.devices("cpu")[0]
        for name, quantized in (("A", False), ("B", True)):
            print(json.dumps(fleet_phase(name, quantized, chip, cpu)),
                  flush=True)
        print(json.dumps(kernel_phase(chip, cpu)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": chip.platform, "kind": chip.device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
