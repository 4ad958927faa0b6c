"""Windows cells: the edge-cloud-integrated fleet pipeline, window after
window, through ``FleetBusExecutor.run``.

Each window puts every stream's records on the bus, retrains every stream's
speed model in one ``train_fleet`` dispatch, syncs the models to the edge,
runs batch and speed inference for the fleet, solves the dynamic weights
(paper Algorithm 1) and combines the forecasts.  The executor's clock is
virtual, so windows follow each other as fast as the work allows: the
window measures the work, not the paper's 30 s period.

The output check follows the fits of a few windows drawn from the seed
(always window 1, the first that serves) and compares, against the plain
reference run from the same seed and data: the fit's step losses, the
synced trees, the batch and speed forecasts, and the hybrid forecast that
the dynamic weights combine.
"""
from __future__ import annotations

import functools
import math
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import flops, generator, reference
from chipbench.check import compare_windows


def _keys(seed: int):
    import jax.numpy as jnp

    st = np.random.SeedSequence(seed).generate_state(4)
    return jnp.asarray(st[:2]), jnp.asarray(st[2:])


def _host_tree(p):
    from repro.training.compiled import materialize_params

    import jax

    return jax.tree_util.tree_map(np.asarray, materialize_params(p))


class Cell:
    """The program built for one windows cell, warmed up, with its
    recorders; ``timed`` runs the window."""

    def __init__(self, ctx):
        import jax

        from repro.core import (FleetStages, lstm_fleet_forecaster,
                                lstm_forecaster, pretrain_batch_model)
        from repro.core.windows import WindowPlan, WindowedStream
        from repro.runtime import ALL_DEPLOYMENTS, CostModel, paper_topology
        from chipbench.common import Spanned, program_model

        cfg, tr, spans = ctx.cfg, ctx.traffic, ctx.spans
        self.ctx, self.cfg = ctx, cfg
        mcfg = program_model(cfg)
        # the reference follows float sync, dynamic weights, every stream
        # retrained every window: a configuration stating otherwise needs a
        # reference that does too
        if (cfg["sync"], cfg["weighting"]) != ("float32", "dynamic"):
            raise ValueError("the windows driver checks float sync with "
                             "dynamic weights only")
        S, rpw, lag = cfg["streams"], cfg["records_per_window"], cfg["lag"]
        self.S, self.rpw, self.lag = S, rpw, lag
        self.max_windows = min(int(tr["max_windows"]),
                               int(tr["max_records"]) // (S * rpw))
        with spans.span("data"):
            self.hist, self.live, _ = generator.fleet(
                ctx.seed, S, self.max_windows, tr, cfg)
            self.ids = [f"t{i:04d}" for i in range(S)]
            self.streams = {
                sid: WindowedStream(self.live[i], WindowPlan(
                    self.max_windows, rpw, lag))
                for i, sid in enumerate(self.ids)}
        self.run_key, self.batch_key = _keys(ctx.seed)
        self.hist0 = generator.supervised(self.hist[0], lag)
        with spans.span("pretrain"):
            fc_batch = lstm_forecaster(mcfg, epochs=cfg["batch_epochs"],
                                       batch_size=cfg["batch_size"])
            self.bp, _ = pretrain_batch_model(fc_batch, self.hist0,
                                              self.batch_key)
            jax.block_until_ready(self.bp)
        self.fc = lstm_fleet_forecaster(
            mcfg, epochs=cfg["speed_epochs"], batch_size=cfg["speed_batch"],
            devices=ctx.devs)
        st = FleetStages.build(self.fc, mode="dynamic")
        for name in ("speed_training", "batch_inference", "speed_inference"):
            setattr(st, name, Spanned(getattr(st, name), name, spans))
        for name in ("model_sync", "weight_solve", "hybrid_combine"):
            setattr(st.single, name,
                    Spanned(getattr(st.single, name), name, spans))
        self.stages = st
        self.ex = _executor_class()(
            st, ALL_DEPLOYMENTS[cfg["deployment"]](), paper_topology(),
            CostModel(ingest_s=rpw / 7.0 * 0.45),
            window_period_s=float(cfg["window_period_s"]))
        # every shape the window uses compiles here, and the executor's own
        # first-window work runs once
        warm = int(tr["warmup_windows"])
        with spans.span("warmup"):
            self.ex.run(self.streams, self.bp, self.run_key, n_windows=warm)
        self.est_window_s = (self.ex.t1 - self.ex.t0) / warm

    def windows_for(self, seconds: float) -> int:
        n = max(3, math.ceil(seconds / self.est_window_s))
        if n > self.max_windows:
            print(f"windows: {n} wanted, the traffic holds "
                  f"{self.max_windows}", file=sys.stderr)
            n = self.max_windows
        return n

    def check_windows(self, n: int) -> List[int]:
        """Window 1 and further windows drawn from the seed."""
        k = int(self.ctx.traffic["check_windows"])
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.ctx.seed, 0xC4EC]))
        rest = list(range(2, n))
        pick = rng.choice(rest, min(k - 1, len(rest)), replace=False)
        return sorted([1] + [int(w) for w in pick])

    def timed(self, n: int, checked: List[int]) -> Dict:
        """One timed run of ``n`` windows; returns the program's outputs of
        the checked windows (host arrays) and the window's timing."""
        ex, st = self.ex, self.stages
        fits = sorted({v for w in checked for v in (w - 1, w)})
        rec = {"fit": {}, "synced": {}, "infer": {}, "model_window": {}}
        fc = self.fc

        def on_train(kw, out):
            if ex.cur_train in fits:
                rec["fit"][ex.cur_train] = {
                    "losses": np.stack(fc.last_losses),
                    "ids": list(kw["fleet_data"])}

        def on_sync(kw, out):
            sid, w = ex.cur_sync
            if w in fits and out["ok"]:
                rec["synced"][(sid, w)] = out["speed_params"]

        def on_infer(kind):
            def record(kw, out):
                w = ex.cur_infer
                if w not in checked:
                    return
                for sid, o in out["fleet"].items():
                    rec["infer"][(kind, sid, w)] = np.asarray(o["pred"])
                    if kind == "speed":
                        rec["model_window"][(sid, w)] = (
                            -1 if o["fallback"]
                            else ex._fleet.state(sid).window)
            return record

        def on_hybrid(kw, out):
            sid, w = ex.cur_part
            if w in checked:
                rec["infer"][("hybrid", sid, w)] = np.asarray(out["pred"])

        st.speed_training.record = on_train
        st.single.model_sync.record = on_sync
        st.batch_inference.record = on_infer("batch")
        st.speed_inference.record = on_infer("speed")
        st.single.hybrid_combine.record = on_hybrid
        try:
            res = ex.run(self.streams, self.bp, self.run_key, n_windows=n)
        finally:
            for s in (st.speed_training, st.single.model_sync,
                      st.batch_inference, st.speed_inference,
                      st.single.hybrid_combine):
                s.record = None
        served = sum(len(r.records) for r in res.results.values())
        rec["missing"] = self.S * (n - 1) - served
        rec["synced"] = {k: _host_tree(v) for k, v in rec["synced"].items()}
        return {"rec": rec, "t0": ex.t0, "t1": ex.t1, "n": n,
                "fits": fits}

    def reference(self, checked: List[int], dtype, rows=None,
                  fit=reference.fleet_fit) -> Dict:
        """The plain reference's outputs for the checked windows, for the
        streams in ``rows`` (``fit`` is the fleet fit to run: the
        reference's own, or a planted fault's)."""
        import jax.numpy as jnp

        cfg, rpw, lag = self.cfg, self.rpw, self.lag
        rows = list(range(self.S)) if rows is None else list(rows)
        fits = sorted({v for w in checked for v in (w - 1, w)})
        chains = reference.key_chains(self.run_key, self.S, max(fits) + 1)
        items = reference.cfg_items(cfg)
        nb = reference.bucket(len(self.hist0["x"]), cfg["batch_size"])
        bx, by, bm = reference.pad(self.hist0, nb)
        bps, _ = fit(
            jnp.asarray(self.batch_key)[None], jnp.asarray(bx)[None],
            jnp.asarray(by)[None], jnp.asarray(bm)[None], cfg_items=items,
            epochs=cfg["batch_epochs"], batch=cfg["batch_size"], dtype=dtype)
        out = {"fit": {}, "params": {}, "data": {}}
        for v in fits:
            datas = [generator.window(self.live[i], v, rpw, lag)
                     for i in rows]
            nbv = reference.bucket(len(datas[0]["x"]), cfg["speed_batch"])
            x, y, m = (np.stack(a) for a in zip(
                *[reference.pad(d, nbv) for d in datas]))
            p, losses = fit(
                jnp.asarray(chains[rows, v]), jnp.asarray(x),
                jnp.asarray(y), jnp.asarray(m), cfg_items=items,
                epochs=cfg["speed_epochs"], batch=cfg["speed_batch"],
                dtype=dtype)
            out["fit"][v] = np.asarray(losses, np.float32)
            out["params"][v] = p
            out["data"][v] = (x, y, m, [len(d["x"]) for d in datas])
        bp_rows = {k: {kk: jnp.broadcast_to(vv, (len(rows),) + vv.shape[1:])
                       for kk, vv in sub.items()} for k, sub in bps.items()}
        infer = {}
        for w in checked:
            x, y, m, ns = out["data"][w]
            xp, yp, mp, nsp = out["data"][w - 1]
            ps = np.asarray(reference.fleet_predict(
                out["params"][w - 1], jnp.asarray(x)), np.float32)
            pb = np.asarray(reference.fleet_predict(
                bp_rows, jnp.asarray(x)), np.float32)
            eps = np.asarray(reference.fleet_predict(
                out["params"][w - 1], jnp.asarray(xp)), np.float32)
            epb = np.asarray(reference.fleet_predict(
                bp_rows, jnp.asarray(xp)), np.float32)
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

    def as_program(self, ref: Dict, checked: List[int]) -> Dict:
        """Reference outputs laid out as the program's recordings, so a
        reference (the control, a planted fault) can stand in for it."""
        rows = ref["rows"]
        fit = {}
        for v, losses in ref["fit"].items():
            full = np.full((self.S, losses.shape[1]), np.nan, np.float32)
            full[rows] = losses
            fit[v] = full
        return {"fit": fit, "synced": ref["synced"], "infer": ref["infer"],
                "model_window": {(i, w): w - 1 for w in checked for i in rows},
                "missing": 0}


@functools.lru_cache(maxsize=None)
def _executor_class():
    from repro.runtime import FleetBusExecutor

    class Executor(FleetBusExecutor):
        """The program's executor with the benchmark's hooks: the clock
        starts when the executor's own warm-up ends and stops when its
        event loop drains, and each stage call knows which stream and
        window it serves.  Nothing in the program's behaviour changes."""

        cur_train = cur_infer = cur_sync = cur_part = None
        t0 = t1 = 0.0
        on_window = None

        def _reset(self, ids):
            import jax

            super()._reset(ids)
            self.cur_train = self.cur_infer = None
            run = self.kernel.run

            def timed_run(until=None):
                ann = jax.profiler.TraceAnnotation("cb:window")
                ann.__enter__()
                if self.on_window is not None:
                    self.on_window()
                self.t0 = time.perf_counter()
                try:
                    return run(until)
                finally:
                    self.t1 = time.perf_counter()
                    ann.__exit__(None, None, None)

            self.kernel.run = timed_run

        def _dispatch_train(self, w, pend):
            self.cur_train = w
            return super()._dispatch_train(w, pend)

        def _dispatch_infer(self, kind, w, pend):
            self.cur_infer = w
            return super()._dispatch_infer(kind, w, pend)

        def _on_model_sync(self, msg):
            self.cur_sync = (msg.payload["stream"], msg.payload["window"])
            return super()._on_model_sync(msg)

        def _on_part(self, msg):
            self.cur_part = (msg.payload["stream"], msg.payload["window"])
            return super()._on_part(msg)

    return Executor


def program_outputs(cell: Cell, t: Dict) -> Dict:
    """The program's recordings keyed like the reference's (stream index
    for stream id)."""
    rec = t["rec"]
    idx = {sid: i for i, sid in enumerate(cell.ids)}
    fit = {}
    for v, f in rec["fit"].items():
        order = [idx[s] for s in f["ids"]]
        losses = np.full((cell.S, f["losses"].shape[1]), np.nan, np.float32)
        losses[order] = f["losses"]
        fit[v] = losses
    return {
        "fit": fit,
        "synced": {(idx[s], w): tree for (s, w), tree in rec["synced"].items()},
        "infer": {(k, idx[s], w): v for (k, s, w), v in rec["infer"].items()},
        "model_window": {(idx[s], w): mw
                         for (s, w), mw in rec["model_window"].items()},
        "missing": rec["missing"],
    }


def run(ctx) -> Dict:
    cfg = ctx.cfg
    cell = Cell(ctx)
    n = (int(ctx.traffic["trace_windows"]) if ctx.trace
         else cell.windows_for(ctx.seconds))
    checked = cell.check_windows(n)
    ctx.window_starts()
    cell.ex.on_window = ctx.mark_window
    t = cell.timed(n, checked)
    ctx.window_ends(t["t0"], t["t1"])
    window_s = (t["t1"] - t["t0"]) / n
    print(f"windows: {n} timed at {window_s:.6f} s each "
          f"(warm-up estimate {cell.est_window_s:.6f} s); checked "
          f"{checked}", file=sys.stderr)
    examples = len(generator.window(cell.live[0], 1, cell.rpw, cell.lag)["x"])
    window_flops = flops.window_flops(cfg, cell.S, examples, 4)
    prog = program_outputs(cell, t)
    dev = ctx.device_record()
    del cell.ex, cell.stages, cell.fc, t
    rows = ctx.check_rows(cell.S)
    ref = cell.reference(checked, ctx.dtype(cfg["precision"]), rows)
    checks = compare_windows(prog, ref, checked)
    return {
        "metrics": {"window_s": window_s},
        "attempted": cell.S * (n - 1),
        "failed": int(prog["missing"]),
        "checks": checks,
        "device": dev,
        "readings": {"n_windows": n, "window_s": window_s,
                     "flops_per_window": window_flops},
    }
