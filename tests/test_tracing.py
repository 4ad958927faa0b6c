"""The span recorder (``repro.tracing``): self time, the bounded ring and
its refusal of a window it no longer holds whole, compile records, and the
spans the executor, the stages and the query plane leave."""
import collections
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core import (
    FleetStages,
    lstm_fleet_forecaster,
    lstm_forecaster,
    pretrain_batch_model,
)
from repro.core.stages import HybridCombine
from repro.runtime import (
    FleetBusExecutor,
    edge_cloud_integrated,
    paper_topology,
)
from repro.serving.query_plane import ForecastQuery, QueryPlane
from repro.streams.sources import fleet_windowed_streams


def _named(recs, name):
    return [r for r in recs if r.name == name]


def test_nested_spans_give_self_time():
    t0 = time.perf_counter()
    with tracing.span("t.outer"):
        time.sleep(0.01)
        with tracing.span("t.inner"):
            time.sleep(0.02)
            with tracing.span("t.leaf"):
                time.sleep(0.01)
        with tracing.span("t.inner"):
            pass
    recs = tracing.records(since=t0)
    (outer,), inners, (leaf,) = (_named(recs, n) for n in
                                 ("t.outer", "t.inner", "t.leaf"))
    assert len(inners) == 2
    assert leaf.self_s == leaf.dur >= 0.01
    assert inners[0].self_s == inners[0].dur - leaf.dur
    assert inners[0].self_s >= 0.02
    assert outer.self_s == pytest.approx(
        outer.dur - inners[0].dur - inners[1].dur, abs=1e-12)
    assert outer.self_s >= 0.01
    # records close in order: children before their parent, all inside it
    assert [r.name for r in recs[-4:]] == ["t.leaf", "t.inner", "t.inner",
                                           "t.outer"]
    assert all(outer.start <= r.start and r.end <= outer.end
               for r in recs[-4:])
    tot = tracing.totals(outer.start, outer.end)
    assert tot["t.inner"].count == 2
    assert tot["t.inner"].seconds == inners[0].dur + inners[1].dur


def test_a_span_shows_in_a_profiler_trace_only_while_one_runs(tmp_path):
    import glob

    with tracing.span("t.untraced"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("t.traced"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes for line in plane.lines for e in line.events}
    assert "t.traced" in names and "t.untraced" not in names
    # the ring records both, traced or not
    assert [r.name for r in tracing.records()[-2:]] == ["t.untraced",
                                                         "t.traced"]


def test_a_stage_wall_is_its_span():
    t0 = time.perf_counter()
    out = HybridCombine()(pred_speed=np.ones(4), pred_batch=np.zeros(4),
                          w_speed=0.25, w_batch=0.75)
    (rec,) = _named(tracing.records(since=t0), "stage.hybrid_combine")
    assert out.wall_s == rec.dur
    np.testing.assert_allclose(out["pred"], 0.25)


def test_ring_drops_oldest_and_refuses_an_evicted_window(monkeypatch):
    monkeypatch.setattr(tracing, "_ring", collections.deque(maxlen=4))
    for i in range(3):
        with tracing.span(f"r{i}"):
            pass
    start = tracing.records()[0].start
    assert [r.name for r in tracing.records(since=start)] == ["r0", "r1",
                                                              "r2"]
    for i in range(3, 6):
        with tracing.span(f"r{i}"):
            pass
    held = tracing.records()
    assert [r.name for r in held] == ["r2", "r3", "r4", "r5"]
    # r0 and r1 are gone: a window from r0's start is no longer whole
    assert tracing.records(since=start) is None
    assert tracing.totals(since=start) is None
    # one that starts after the oldest held record ended still is
    tot = tracing.totals(since=held[1].start)
    assert sorted(tot) == ["r3", "r4", "r5"]
    assert all(t.count == 1 for t in tot.values())


def test_a_new_shape_yields_a_compile_record():
    def doubled_sum(x):
        return (2 * x).sum()

    f = jax.jit(doubled_sum)
    f(np.ones(3, np.float32)).block_until_ready()
    t0 = time.perf_counter()
    f(np.ones(3, np.float32)).block_until_ready()
    assert not [r for r in tracing.records(since=t0)
                if r.name.startswith("compile:")]
    f(np.ones(7, np.float32)).block_until_ready()
    t1 = time.perf_counter()
    recs = [r for r in tracing.records(since=t0, until=t1)
            if r.name.startswith("compile:")]
    assert len(recs) == 1 and "doubled_sum" in recs[0].name
    assert recs[0].dur > 0 and recs[0].self_s == recs[0].dur


@pytest.fixture(scope="module")
def small_fleet():
    cfg = get_config("lstm-paper")
    streams, hist0 = fleet_windowed_streams(
        5, 4, 150, "gradual", seed=0, hist_len=1200,
        alphas=np.full(5, 1.5e-3))
    bp, _ = pretrain_batch_model(lstm_forecaster(cfg, epochs=1,
                                                 batch_size=256),
                                 hist0, jax.random.PRNGKey(0))
    return cfg, streams, bp


def _bus_run(small_fleet, n_streams):
    cfg, streams, bp = small_fleet
    ff = lstm_fleet_forecaster(cfg, epochs=2, batch_size=64)
    ex = FleetBusExecutor(FleetStages.build(ff), edge_cloud_integrated(),
                          paper_topology())
    sub = dict(list(streams.items())[:n_streams])
    ex.run(sub, bp, jax.random.PRNGKey(1), n_windows=4)
    loop = _named(tracing.records(), "loop")[-1]
    return loop, tracing.totals(loop.start, loop.end)


def test_bus_run_records_its_loop_and_the_work_inside(small_fleet):
    loop, tot = _bus_run(small_fleet, 5)
    assert tot["loop"].count == 1 and tot["loop"].seconds == loop.dur
    # windows 1-3 serve: one hybrid step per stream and window
    assert tot["executor.on_part"].count == 5 * 3
    for name in ("stage.weight_solve", "stage.hybrid_combine"):
        assert tot[name].count == 5 * 3
    for name in ("stage.model_sync", "stage.data_sync"):
        assert tot[name].count == 5 * 4
    assert tot["executor.dispatch_train"].count == 4
    assert tot["stage.speed_training"].count == 4
    assert tot["fleet.fit.stage"].count == 4
    assert tot["executor.publish_models"].count == 4
    assert tot["executor.dispatch_infer"].count == 2 * 3
    # train eval, batch and speed inference: two predicts a window each
    assert tot["fleet.predict.stage"].count == tot[
        "fleet.predict.wait"].count == 2 * 4 + 2 * 3
    # the spans inside account for the loop's time but its own dispatch
    inside = sum(t.self_s for k, t in tot.items()
                 if not k.startswith("compile:"))
    assert inside == pytest.approx(loop.dur, rel=1e-9)
    assert 0 < loop.self_s < loop.dur


def test_records_per_stream_and_window_stay_within_five(small_fleet):
    """What each further stream adds to a window's records: the budget that
    keeps the recorder's cost small at a thousand streams."""
    counts = {}
    for s in (2, 5):
        _, tot = _bus_run(small_fleet, s)
        counts[s] = sum(t.count for k, t in tot.items()
                        if not k.startswith("compile:"))
    assert (counts[5] - counts[2]) / (3 * 4) <= 5


def test_a_query_plane_tick_records_its_four_spans():
    qp = QueryPlane(["a", "b"], n_slots=4)
    qp.observe_window("a", np.ones((3, 5, 5), np.float32), 0)
    qp.submit(ForecastQuery(uid=0, stream="a"))
    t0 = time.perf_counter()
    qp.admit(0.0)
    by_stream, xs = qp.build_batch()
    qp.apply(by_stream, [np.full((len(x), 1), 0.5, np.float32) for x in xs],
             {"a": 0})
    done = qp.retire(0.1)
    assert [q.answer for q in done] == [[0.5]]
    recs = tracing.records(since=t0)
    assert [r.name for r in recs] == ["plane.admit", "plane.build_batch",
                                      "plane.apply", "plane.retire"]
