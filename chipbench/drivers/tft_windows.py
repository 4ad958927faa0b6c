"""The Temporal Fusion Transformer's windows cell: the fleet pipeline with
TFT as every stream's forecaster, window after window, through
``FleetBusExecutor.run``, as the windows driver runs the paper's LSTM.

Each window every stream's 250 records become 59 encoder-decoder samples
(168 past steps, 24 future steps of known inputs, the stream's id, 24
targets); every stream's TFT is retrained cold for 100 steps in one
``train_fleet`` dispatch, synced, and serves the next window's day-ahead
forecasts (the 0.5 quantile over 24 steps) beside the batch model,
combined by the dynamic weights.

The output check is the windows driver's (``chipbench.check``) against
``chipbench/reference_tft.py``: the first three step losses, the end-of-fit
loss, the synced trees, and the batch, speed and hybrid forecasts (all 24
steps), as medians and 90th percentiles over streams and checked windows,
and the windows missing; and the median gap of the step losses just after
the first update (``early_steps_gap``).
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import flops_tft, generator, reference, reference_tft
from chipbench.check import compare_windows
from chipbench.drivers import windows as wd

STATED = ("hidden", "n_heads", "lookback", "horizon", "n_observed", "n_known",
          "dropout")
# the fit steps of ``fit_early_steps_gap``: after one update, before a
# change of rounding has compounded
EARLY_STEPS = (1, 2)


def program_tft(cfg: Dict):
    """The program's configuration of ``cfg["model"]``, once it has the
    widths, the id rows (one per stream), the clip and the parameter count
    that ``cfg`` states."""
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    mcfg = get_config(cfg["model"])
    stated = {k: cfg[k] for k in STATED}
    stated.update(n_ids=cfg["streams"], quantiles=tuple(cfg["quantiles"]))
    for k, v in stated.items():
        if getattr(mcfg.tft, k) != v:
            raise ValueError(f"program's {cfg['model']} has {k}="
                             f"{getattr(mcfg.tft, k)}, not {v}")
    if mcfg.max_grad_norm != cfg["max_grad_norm"]:
        raise ValueError(f"program's {cfg['model']} clips at "
                         f"{mcfg.max_grad_norm}, not {cfg['max_grad_norm']}")
    shapes = jax.eval_shape(get_model(mcfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    if n != cfg["parameters"]:
        raise ValueError(f"program's {cfg['model']} has {n} parameters, "
                         f"not {cfg['parameters']}")
    return mcfg


class Cell(wd.Cell):
    """The program built for the TFT windows cell, warmed up; ``timed``,
    ``windows_for``, ``check_windows`` and ``as_program`` are the windows
    driver's.  With ``program`` false only the data and the keys are
    made, for readings of the reference alone."""

    def __init__(self, ctx, program: bool = True):
        import jax

        from repro.core import (FleetStages, fleet_forecaster_for,
                                forecaster_for, pretrain_batch_model)
        from repro.core.windows import WindowPlan, WindowedStream
        from repro.runtime import ALL_DEPLOYMENTS, CostModel, paper_topology
        from chipbench.common import Spanned

        cfg, tr, spans = ctx.cfg, ctx.traffic, ctx.spans
        self.ctx, self.cfg = ctx, cfg
        mcfg = program_tft(cfg)
        if (cfg["sync"], cfg["weighting"]) != ("float32", "dynamic"):
            raise ValueError("the TFT windows driver checks float sync with "
                             "dynamic weights only")
        S, rpw, lag = cfg["streams"], cfg["records_per_window"], cfg["lag"]
        self.S, self.rpw, self.lag = S, rpw, lag
        self.period = float(tr["seasonal_period_records"])
        self.max_windows = min(int(tr["max_windows"]),
                               int(tr["max_records"]) // (S * rpw))
        hist_n = int(cfg["history_records"])

        def plan(n_windows, records, sid, first):
            return WindowPlan(n_windows, records, lag,
                              horizon=cfg["horizon"], period=self.period,
                              stream_id=sid, first_record=first)

        with spans.span("data"):
            self.hist, self.live, _ = generator.fleet(
                ctx.seed, S, self.max_windows, tr, cfg)
            self.ids = [f"t{i:04d}" for i in range(S)]
            self.streams = {
                sid: WindowedStream(self.live[i], plan(
                    self.max_windows, rpw, i, hist_n))
                for i, sid in enumerate(self.ids)}
            # M^b learns from stream 0's history
            self.hist0 = WindowedStream(self.hist[0], plan(
                1, hist_n, 0, 0)).supervised(0)
        self.run_key, self.batch_key = wd._keys(ctx.seed)
        if not program:
            return
        with spans.span("pretrain"):
            fc_batch = forecaster_for(mcfg, epochs=cfg["batch_epochs"],
                                      batch_size=cfg["batch_size"],
                                      lr=cfg["lr"])
            self.bp, _ = pretrain_batch_model(fc_batch, self.hist0,
                                              self.batch_key)
            jax.block_until_ready(self.bp)
        self.fc = fleet_forecaster_for(
            mcfg, epochs=cfg["speed_epochs"], batch_size=cfg["speed_batch"],
            lr=cfg["lr"], devices=ctx.devs)
        st = FleetStages.build(self.fc, mode="dynamic")
        for name in ("speed_training", "batch_inference", "speed_inference"):
            setattr(st, name, Spanned(getattr(st, name), name, spans))
        for name in ("model_sync", "weight_solve", "hybrid_combine"):
            setattr(st.single, name,
                    Spanned(getattr(st.single, name), name, spans))
        self.stages = st
        self.ex = wd._executor_class()(
            st, ALL_DEPLOYMENTS[cfg["deployment"]](), paper_topology(),
            CostModel(ingest_s=rpw / 7.0 * 0.45),
            window_period_s=float(cfg["window_period_s"]))
        warm = int(tr["warmup_windows"])
        with spans.span("warmup"):
            self.ex.run(self.streams, self.bp, self.run_key, n_windows=warm)
        self.est_window_s = (self.ex.t1 - self.ex.t0) / warm

    def window_data(self, i: int, w: int) -> Dict[str, np.ndarray]:
        return reference_tft.window(self.live[i], w, self.cfg,
                                    self.ctx.traffic, i)

    def reference(self, checked: List[int], dtype, rows=None,
                  variant: str = "sound") -> Dict:
        """The plain reference's outputs for the checked windows, for the
        streams in ``rows``, under ``variant`` (``reference_tft.VARIANTS``)."""
        import jax.numpy as jnp

        cfg = self.cfg
        rows = list(range(self.S)) if rows is None else list(rows)
        fits = sorted({v for w in checked for v in (w - 1, w)})
        chains = reference.key_chains(self.run_key, self.S, max(fits) + 1)
        items = reference_tft.cfg_items(cfg)
        hist0 = reference_tft.samples(
            self.hist[0], cfg["lookback"], cfg["horizon"], self.period, 0, 0)
        nb = reference.bucket(len(hist0["x"]), cfg["batch_size"])
        bx, by, bm = reference_tft.pad(hist0, nb)
        fit = lambda keys, x, y, m, epochs, batch: reference_tft.fleet_fit(
            keys, jnp.asarray(x), jnp.asarray(y), jnp.asarray(m),
            cfg_items=items, epochs=epochs, batch=batch, dtype=dtype,
            variant=variant)
        predict = lambda p, x: np.asarray(reference_tft.fleet_predict(
            p, jnp.asarray(x), cfg_items=items, variant=variant), np.float32)
        bps, _ = fit(jnp.asarray(self.batch_key)[None], bx[None], by[None],
                     bm[None], cfg["batch_epochs"], cfg["batch_size"])
        out = {"fit": {}, "params": {}, "data": {}}
        for v in fits:
            datas = [self.window_data(i, v) for i in rows]
            nbv = reference.bucket(len(datas[0]["x"]), cfg["speed_batch"])
            x, y, m = (np.stack(a) for a in zip(
                *[reference_tft.pad(d, nbv) for d in datas]))
            p, losses = fit(jnp.asarray(chains[rows, v]), x, y, m,
                            cfg["speed_epochs"], cfg["speed_batch"])
            out["fit"][v] = np.asarray(losses, np.float32)
            out["params"][v] = p
            out["data"][v] = (x, y, [len(d["x"]) for d in datas])
        bp_rows = {k: {kk: jnp.broadcast_to(vv, (len(rows),) + vv.shape[1:])
                       for kk, vv in sub.items()} for k, sub in bps.items()}
        infer = {}
        for w in checked:
            x, _, ns = out["data"][w]
            xp, yp, nsp = out["data"][w - 1]
            ps, pb = predict(out["params"][w - 1], x), predict(bp_rows, x)
            eps = predict(out["params"][w - 1], xp)
            epb = predict(bp_rows, xp)
            for j, i in enumerate(rows):
                n, npv = ns[j], nsp[j]
                ws = reference.dwa(eps[j, :npv], epb[j, :npv], yp[j, :npv])
                infer[("speed", i, w)] = ps[j, :n]
                infer[("batch", i, w)] = pb[j, :n]
                infer[("hybrid", i, w)] = (
                    ws * ps[j, :n].astype(np.float64)
                    + (1 - ws) * pb[j, :n].astype(np.float64))
        trees = {}
        for v in fits:
            host = {k: {kk: np.asarray(vv, np.float32)
                        for kk, vv in sub.items()}
                    for k, sub in out["params"][v].items()}
            for j, i in enumerate(rows):
                trees[(i, v)] = {k: {kk: vv[j] for kk, vv in sub.items()}
                                 for k, sub in host.items()}
        return {"fit": {v: out["fit"][v] for v in fits}, "synced": trees,
                "infer": infer, "rows": rows}


def early_steps_gap(prog: Dict, ref: Dict) -> float:
    """The larger over ``EARLY_STEPS`` of the median, over streams and
    checked fits, of a step loss's relative gap to the reference's.  At
    these steps other rounding has not compounded yet (PERF.md section 4),
    so the median parts it from a wrong update, a lost half batch or a
    precision step down, where the worst stream of ``fit_first_steps_gap``
    can only just do so."""
    gaps = {s: [] for s in EARLY_STEPS}
    for v, rl in ref["fit"].items():
        pl = prog["fit"].get(v)
        for j, i in enumerate(ref["rows"]):
            for s in EARLY_STEPS:
                g = (np.inf if pl is None else
                     abs(float(pl[i, s]) - float(rl[j, s]))
                     / max(abs(float(rl[j, s])), 1e-12))
                gaps[s].append(g if np.isfinite(g) else np.inf)
    return max(float(np.median(g)) for g in gaps.values())


def compare(prog: Dict, ref: Dict, checked: List[int]) -> Dict[str, float]:
    """The windows driver's numbers (``compare_windows``) and
    ``fit_early_steps_gap``."""
    out = compare_windows(prog, ref, checked)
    out["fit_early_steps_gap"] = early_steps_gap(prog, ref)
    return out


def measure(ctx, cell: Cell, n: int) -> Dict:
    """One timed run of ``n`` windows with its output check; what ``run``
    reports, and the program's outputs for the readings."""
    checked = cell.check_windows(n)
    ctx.window_starts()
    cell.ex.on_window = ctx.mark_window
    r0 = time.perf_counter()
    t = cell.timed(n, checked)
    r1 = time.perf_counter()
    ctx.window_ends(t["t0"], t["t1"])
    window_s = (t["t1"] - t["t0"]) / n
    print(f"windows: {n} timed at {window_s:.6f} s each "
          f"(warm-up estimate {cell.est_window_s:.6f} s); checked "
          f"{checked}", file=sys.stderr)
    examples = len(cell.window_data(0, 1)["x"])
    window_flops = flops_tft.window_flops(ctx.cfg, cell.S, examples, 4)
    prog = wd.program_outputs(cell, t)
    dev = ctx.device_record()
    return {"prog": prog, "checked": checked, "window_s": window_s,
            "device": dev,
            "readings": {"n_windows": n, "window_s": window_s,
                         "flops_per_window": window_flops,
                         "run_span": (r0, r1), "streams": cell.S}}


def run(ctx) -> Dict:
    cell = Cell(ctx)
    n = (int(ctx.traffic["trace_windows"]) if ctx.trace
         else cell.windows_for(ctx.seconds))
    m = measure(ctx, cell, n)
    del cell.ex, cell.stages, cell.fc
    rows = ctx.check_rows(cell.S)
    ref = cell.reference(m["checked"], ctx.dtype(ctx.cfg["precision"]), rows)
    checks = compare(m["prog"], ref, m["checked"])
    return {
        "metrics": {"window_s": m["window_s"]},
        "attempted": cell.S * (n - 1),
        "failed": int(m["prog"]["missing"]),
        "checks": checks,
        "device": m["device"],
        "readings": m["readings"],
    }
