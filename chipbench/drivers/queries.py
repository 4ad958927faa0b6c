"""Queries cells: forecast queries against the fleet's served models,
through the program's ``QueryPlane`` and one ``predict_fleet`` dispatch per
tick.

Set-up trains every stream's speed model on the first window (one
``train_fleet`` dispatch) and gives each stream that window's context, as
the edge holds them between two windows.  The window then offers queries
open loop at the traffic's fixed rate: the loop submits every query that is
due, admits, serves one tick, and retires the finished queries.  A query's
latency runs from the time it was due, so a slow tick delays every query
behind it.  Queries due in the window are waited for up to ``drain_s``
past its close; one that never comes back counts as unanswered.

The output check samples answered queries from the seed and recomputes
them with the plain reference's models, trained from the same seed.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict

import numpy as np

from chipbench import flops, generator, reference
from chipbench.check import compare_answers

KINDS = ("point", "horizon", "whatif")


class Cell:
    def __init__(self, ctx):
        import jax

        from repro.core import lstm_fleet_forecaster
        from repro.core.stages import ServingStage
        from chipbench.common import Spanned, program_model

        cfg, spans = ctx.cfg, ctx.spans
        self.ctx, self.cfg = ctx, cfg
        mcfg = program_model(cfg)
        S, rpw, lag = cfg["streams"], cfg["records_per_window"], cfg["lag"]
        self.S, self.rpw, self.lag = S, rpw, lag
        with spans.span("data"):
            _, self.live, _ = generator.fleet(ctx.seed, S, 1,
                                                      ctx.traffic, cfg)
            self.ids = [f"t{i:04d}" for i in range(S)]
            self.datas = [generator.window(self.live[i], 0, rpw, lag)
                          for i in range(S)]
        st = np.random.SeedSequence(ctx.seed).generate_state(2)
        self.run_key = jax.numpy.asarray(st)
        self.keys = reference.key_chains(self.run_key, S, 1)[:, 0]
        self.fc = lstm_fleet_forecaster(
            mcfg, epochs=cfg["speed_epochs"], batch_size=cfg["speed_batch"],
            devices=ctx.devs)
        with spans.span("pretrain"):
            self.params, _ = self.fc.train_fleet(
                self.datas, [self.keys[i] for i in range(S)])
        self.serving = Spanned(ServingStage(self.fc), "serving", spans)
        self.slots = int(ctx.traffic["slots"])
        with spans.span("warmup"):
            ref = self.datas[0]["x"][-1]
            k = 1
            while k <= self.slots:
                xs = [np.repeat(ref[None], k, axis=0)] + [
                    np.zeros((0,) + ref.shape, ref.dtype)] * (S - 1)
                self.serving(params_seq=self.params, xs=xs)
                k *= 2
            # the loop's own code paths, on arrivals of their own
            self.loop(generator.arrivals(ctx.seed ^ 0x3A3A, ctx.traffic, S,
                                         float(ctx.traffic["warmup_s"])),
                      float(ctx.traffic["warmup_s"]), sample=set())

    def plane(self):
        from repro.serving.query_plane import QueryPlane

        qp = QueryPlane(self.ids, self.slots)
        for sid, d in zip(self.ids, self.datas):
            qp.observe_window(sid, d["x"], 0)
        return qp

    def loop(self, arr: Dict, seconds: float, sample, on_start=None):
        """Serve ``arr`` open loop; returns latencies (NaN = unanswered),
        answers of the sampled uids, ticks and rows served."""
        from repro.serving.query_plane import ForecastQuery

        spans, serving = self.ctx.spans, self.serving
        qp = self.plane()
        due, stream, kind = arr["due"], arr["stream"], arr["kind"]
        horizon, scale, offset = arr["horizon"], arr["scale"], arr["offset"]
        n = len(due)
        lat = np.full(n, np.nan)
        answers = {}
        drain = float(self.ctx.traffic["drain_s"])
        ticks = rows = 0
        i = 0
        model_windows = {sid: 0 for sid in self.ids}
        if on_start is not None:
            on_start()
        lag = np.zeros(n)
        window = spans.span("window")
        window.__enter__()
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                qp.submit(ForecastQuery(
                    uid=i, stream=self.ids[stream[i]], kind=KINDS[kind[i]],
                    horizon=int(horizon[i]),
                    perturb_scale=float(scale[i]),
                    perturb_offset=float(offset[i]), arrived_at=due[i]))
                lag[i] = now - due[i]
                i += 1
            qp.admit(now)
            batch = qp.build_batch()
            if batch is not None:
                with spans.span("tick"):
                    by_stream, xs = batch
                    out = serving(params_seq=self.params, xs=xs)
                    qp.apply(by_stream, out["preds"], model_windows)
                    done = time.perf_counter() - t0
                    for q in qp.retire(done):
                        lat[q.uid] = done - due[q.uid]
                        if q.uid in sample:
                            answers[q.uid] = list(q.answer)
                ticks += 1
                rows += sum(len(x) for x in xs)
            elif i >= n:
                break
            else:
                wait = due[i] - (time.perf_counter() - t0)
                if wait > 2e-4:
                    time.sleep(wait - 1e-4)
            if now > seconds + drain:
                break
        window.__exit__(None, None, None)
        return {"lat": lat, "answers": answers, "ticks": ticks, "rows": rows,
                "submit_lag": lag, "t0": t0, "t1": t0 + seconds}

    def reference(self, arr: Dict, uids, dtype,
                  fit=reference.fleet_fit) -> Dict:
        """The sampled queries' answers from the reference's own models,
        every horizon step in one batched call."""
        import jax.numpy as jnp

        cfg = self.cfg
        nb = reference.bucket(len(self.datas[0]["x"]), cfg["speed_batch"])
        x, y, m = (np.stack(a) for a in zip(
            *[reference.pad(d, nb) for d in self.datas]))
        params, _ = fit(
            jnp.asarray(self.keys), jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(m), cfg_items=reference.cfg_items(cfg),
            epochs=cfg["speed_epochs"], batch=cfg["speed_batch"], dtype=dtype)
        uids = sorted(uids)
        s = arr["stream"][uids]
        kinds, hz = arr["kind"][uids], arr["horizon"][uids]
        ctx = np.stack([self.datas[j]["x"][-1] for j in s]).astype(np.float32)
        sc = arr["scale"][uids][:, None, None]
        off = arr["offset"][uids][:, None, None]
        ctx = np.where((kinds == 2)[:, None, None], ctx * sc + off, ctx)
        # each stream's sampled queries as one batch, as a tick serves them
        slot = np.zeros(len(uids), np.int64)
        count = np.zeros(self.S, np.int64)
        for j, st in enumerate(s):
            slot[j], count[st] = count[st], count[st] + 1
        rows = 1 << max(0, int(count.max() - 1).bit_length())
        out = {u: [] for u in uids}
        for step in range(int(hz.max())):
            x = np.zeros((self.S, rows) + ctx.shape[1:], np.float32)
            x[s, slot] = ctx
            pred = np.asarray(reference.fleet_predict(params, jnp.asarray(x)),
                              np.float32)[s, slot, 0]
            for j, u in enumerate(uids):
                if step < hz[j]:
                    out[u].append(float(pred[j]))
            nxt = ctx[:, -1].copy()
            nxt[:, 0] = pred
            ctx = np.concatenate([ctx[:, 1:], nxt[:, None]], axis=1)
        return {"answers": out,
                "stream": {u: int(arr["stream"][u]) for u in uids}}


def run(ctx) -> Dict:
    cfg, tr = ctx.cfg, ctx.traffic
    cell = Cell(ctx)
    seconds = float(tr["trace_seconds"]) if ctx.trace else ctx.seconds
    arr = generator.arrivals(ctx.seed, tr, cell.S, seconds)
    n = len(arr["due"])
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 0x5A4D]))
    sample = set(int(u) for u in rng.choice(n, min(n, int(tr["check_queries"])),
                                            replace=False))
    gc.collect()
    gc.freeze()
    ctx.spans.keep.add("tick")
    ctx.window_starts()
    res = cell.loop(arr, seconds, sample, on_start=ctx.mark_window)
    ctx.window_ends(res["t0"], res["t1"])
    gc.unfreeze()
    lat = res["lat"]
    answered = np.isfinite(lat)
    drain = float(tr["drain_s"])
    # an unanswered query waited until the check gave up on it
    full = np.where(answered, lat, seconds + drain - arr["due"])
    p95 = float(np.quantile(full, 0.95))
    in_window = answered & (arr["due"] + full <= seconds)
    qps = float(in_window.sum()) / seconds
    print(f"queries: {n} offered at {n / seconds:.1f}/s, "
          f"{int(answered.sum())} answered, {int(in_window.sum())} in the "
          f"window; latency p50 {np.median(full):.6f} s p95 {p95:.6f} s; "
          f"submitted late by p95 {np.quantile(res['submit_lag'], 0.95):.6f}"
          f" s; {res['ticks']} ticks, {res['rows']} rows", file=sys.stderr)
    dev = ctx.device_record()
    prog = {"answers": res["answers"], "unanswered": int((~answered).sum())}
    del cell.fc, cell.params, cell.serving
    ref = cell.reference(arr, sorted(res["answers"]) or sorted(sample),
                         ctx.dtype(cfg["precision"]))
    checks = compare_answers(prog, ref)
    ticks = ctx.spans.walls.get("tick", [])
    return {
        "metrics": {"query_p95_s": p95, "queries_per_s": qps},
        "attempted": n, "failed": prog["unanswered"],
        "checks": checks, "device": dev,
        "readings": {"tick_s": float(np.median(ticks)) if ticks else None,
                     "seconds": seconds,
                     "flops_served": res["rows"] * flops.forward_per_example(
                         cfg)},
    }
