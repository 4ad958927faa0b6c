"""Request-plane tests: slot recycling under staggered arrivals, FIFO
no-starvation, batched-vs-unbatched answer parity, deterministic open-loop
traces, and a trace replayed end-to-end through ``FleetBusExecutor`` (every
request answered on its stream's response topic, one vmapped dispatch per
serving tick, stale-bounded serving models)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    lstm_fleet_forecaster,
    lstm_forecaster,
    pretrain_batch_model,
)
from repro.core.stages import FleetStages, ServingStage
from repro.runtime import (
    FleetBusExecutor,
    edge_cloud_integrated,
    fleet_key_chains,
    paper_topology,
)
from repro.runtime.modules import T_RESPONSE, stream_topic
from repro.serving.batching import BatchScheduler, Request
from repro.serving.query_plane import (
    ForecastQuery,
    QueryPlane,
    answer_query_unbatched,
    latency_stats,
    open_loop_trace,
)
from repro.streams.sources import fleet_windowed_streams

N_STREAMS = 3
RPW = 150
EPOCHS = 4


@pytest.fixture(scope="module")
def cfg():
    return get_config("lstm-paper")


@pytest.fixture(scope="module")
def fleet_setup(cfg):
    streams, hist0 = fleet_windowed_streams(
        N_STREAMS, 3, RPW, "gradual", seed=0, hist_len=1200,
        alphas=np.full(5, 1.5e-3))
    fc_batch = lstm_forecaster(cfg, epochs=4, batch_size=256)
    bp, _ = pretrain_batch_model(fc_batch, hist0, jax.random.PRNGKey(0))
    return streams, bp


# ---------------------------------------------------------------------------
# scheduler: slot recycling + clock stamping
# ---------------------------------------------------------------------------


def _req(uid, n_new=1):
    return Request(uid=uid, prompt=np.arange(3, dtype=np.int32),
                   max_new_tokens=n_new)


def test_scheduler_slot_recycling_staggered_arrivals():
    """Slots freed by short requests refill from the queue in FIFO order
    without waiting for the long co-batched request to drain; the runtime
    clock stamps both admission and finish."""
    s = BatchScheduler(2)
    long_req = _req(0, n_new=5)
    s.submit(long_req)
    s.submit(_req(1, n_new=1))
    assert s.admit(now=0.0) == [0, 1]
    assert long_req.admitted_at == 0.0

    s.submit(_req(2, n_new=1))  # staggered arrival: queue is full
    assert s.admit(now=1.0) == []  # no free slot yet

    # request 1 finishes -> its slot recycles, request 2 admits next tick
    s.slots[1].request.generated.append(7)
    done = s.retire_finished(now=2.0)
    assert [r.uid for r in done] == [1] and done[0].finished_at == 2.0
    assert s.admit(now=3.0) == [1]
    assert s.slots[1].request.uid == 2
    assert s.slots[1].request.admitted_at == 3.0
    # the long request never left its slot
    assert s.slots[0].request is long_req
    assert not s.idle


def test_scheduler_retire_requires_clock():
    """``retire_finished`` no longer silently stamps 0.0 — the clock is a
    required argument."""
    with pytest.raises(TypeError):
        BatchScheduler(1).retire_finished()


def test_queryplane_fifo_no_starvation():
    """A queue much longer than the slot count drains completely in FIFO
    admission order — multi-tick horizon queries occupy slots but never
    push later queries out of order or starve them."""
    ids = ["a", "b"]
    plane = QueryPlane(ids, n_slots=2)
    ctx = np.ones((5, 5), np.float32)
    for sid in ids:
        plane.observe_window(sid, ctx[None].repeat(3, 0).reshape(3, 5, 5), 0)
    qs = [ForecastQuery(uid=i, stream=ids[i % 2],
                        kind="horizon" if i % 3 == 0 else "point",
                        horizon=3 if i % 3 == 0 else 1)
          for i in range(9)]
    for q in qs:
        plane.submit(q)

    preds_const = lambda xs: [np.full((len(x), 1), 0.5) for x in xs]
    tick = 0
    while plane.busy:
        plane.admit(float(tick))
        batch = plane.build_batch()
        assert batch is not None
        by_stream, xs = batch
        plane.apply(by_stream, preds_const(xs), {sid: 0 for sid in ids})
        plane.retire(float(tick))
        tick += 1
        assert tick < 50, "queue starved"

    assert all(q.done and q.finished_at is not None for q in qs)
    # strict FIFO: admission times never decrease in submission order
    admits = [q.admitted_at for q in qs]
    assert admits == sorted(admits)


def test_queryplane_blocks_until_stream_has_context():
    """A queue-head query for a stream with no window yet holds admission
    (strict FIFO, no reordering) and admits as soon as the context lands."""
    plane = QueryPlane(["a", "b"], n_slots=2)
    x = np.ones((3, 5, 5), np.float32)
    plane.observe_window("b", x, 0)
    plane.submit(ForecastQuery(uid=0, stream="a"))
    plane.submit(ForecastQuery(uid=1, stream="b"))
    assert plane.admit(0.0) == []  # head blocks, "b" must wait behind it
    plane.observe_window("a", x, 0)
    assert plane.admit(1.0) == [0, 1]


def test_whatif_perturbs_context_once():
    plane = QueryPlane(["a"], n_slots=1)
    x = np.full((3, 5, 5), 2.0, np.float32)
    plane.observe_window("a", x, 0)
    q = ForecastQuery(uid=0, stream="a", kind="whatif",
                      perturb_scale=2.0, perturb_offset=1.0)
    plane.submit(q)
    plane.admit(0.0)
    np.testing.assert_allclose(q.ctx, 2.0 * 2.0 + 1.0)


# ---------------------------------------------------------------------------
# batched vs unbatched answers
# ---------------------------------------------------------------------------


def test_batched_vs_unbatched_answer_parity(fleet_setup, cfg):
    """Every query kind answered by the batched serving tick matches the
    unbatched per-query reference to vmap tolerance, including multi-step
    horizon feedback and same-stream queries sharing a tick."""
    streams, _ = fleet_setup
    ids = list(streams)
    ff = lstm_fleet_forecaster(cfg, epochs=EPOCHS, batch_size=64)
    keys = fleet_key_chains(jax.random.PRNGKey(3), ids, 1)
    params, _ = ff.train_fleet(
        [streams[sid].supervised(0) for sid in ids],
        [keys[sid][0] for sid in ids])
    base_ctx = {sid: np.asarray(streams[sid].supervised(0)["x"])[-1]
                for sid in ids}

    qs = [
        ForecastQuery(uid=0, stream=ids[0]),
        ForecastQuery(uid=1, stream=ids[0], kind="horizon", horizon=3),
        ForecastQuery(uid=2, stream=ids[1], kind="whatif",
                      perturb_scale=1.1, perturb_offset=0.05),
        ForecastQuery(uid=3, stream=ids[2], kind="horizon", horizon=2),
        ForecastQuery(uid=4, stream=ids[1]),
    ]
    plane = QueryPlane(ids, n_slots=5)
    for sid in ids:
        plane.observe_window(sid, streams[sid].supervised(0)["x"], 0)
    for q in qs:
        plane.submit(q)

    stage = ServingStage(ff)
    tick = 0
    while plane.busy:
        plane.admit(float(tick))
        by_stream, xs = plane.build_batch()
        out = stage(params_seq=params, xs=xs)
        plane.apply(by_stream, out["preds"], {sid: 0 for sid in ids})
        plane.retire(float(tick))
        tick += 1

    assert stage.dispatches == stage.ticks  # one vmapped dispatch per tick
    for q in qs:
        ref = answer_query_unbatched(
            ff.single.predict, params[ids.index(q.stream)], q,
            base_ctx[q.stream])
        assert len(q.answer) == q.horizon
        assert max(abs(a - b) for a, b in zip(q.answer, ref)) <= 1e-6


# ---------------------------------------------------------------------------
# open-loop trace + full bus replay
# ---------------------------------------------------------------------------


def test_open_loop_trace_deterministic():
    a = open_loop_trace(["s0", "s1"], qps=10.0, n_requests=40, seed=7)
    b = open_loop_trace(["s0", "s1"], qps=10.0, n_requests=40, seed=7)
    c = open_loop_trace(["s0", "s1"], qps=10.0, n_requests=40, seed=8)
    assert [(q.stream, q.kind, q.horizon, q.perturb_scale, q.perturb_offset,
             q.arrived_at) for q in a] == \
           [(q.stream, q.kind, q.horizon, q.perturb_scale, q.perturb_offset,
             q.arrived_at) for q in b]
    assert [(q.kind, q.perturb_scale) for q in a] != \
           [(q.kind, q.perturb_scale) for q in c]
    # exact open-loop spacing, round-robin streams
    assert a[1].arrived_at - a[0].arrived_at == pytest.approx(0.1)
    assert [q.stream for q in a[:4]] == ["s0", "s1", "s0", "s1"]


def test_latency_stats_empty_is_infinite():
    s = latency_stats([])
    assert s["p99_s"] == float("inf") and s["p50_s"] == float("inf")


def test_fleet_bus_serving_replays_trace_e2e(fleet_setup, cfg):
    """A deterministic arrival trace replayed through the full fleet bus:
    every request is answered on its own stream's response topic, serving
    costs one vmapped dispatch per tick, and every answer's serving model
    trails its context by at most one training window."""
    streams, bp = fleet_setup
    ids = list(streams)
    ff = lstm_fleet_forecaster(cfg, epochs=EPOCHS, batch_size=64)
    trace = open_loop_trace(ids, qps=12.0, n_requests=30, start=5.0, seed=3)
    ex = FleetBusExecutor(
        FleetStages.build(ff, mode="dynamic"), edge_cloud_integrated(),
        paper_topology(), window_period_s=5.0, query_trace=trace,
        serve_slots=4)
    res = ex.run(streams, bp, jax.random.PRNGKey(1), n_windows=3)

    s = res.serving
    assert s is not None
    assert s["n_requests"] == 30
    assert s["n_starved"] == 0 and s["n_answered"] == 30
    assert s["dispatches_per_tick"] == 1.0
    assert s["sustained_qps"] >= s["offered_qps"]
    assert np.isfinite(s["p99_s"]) and s["p99_s"] > 0

    # per-stream response topics, one response per request
    resp_topics = [m.topic for m in res.message_log
                   if m.topic.startswith(T_RESPONSE)]
    assert len(resp_topics) == 30
    for q in res.queries:
        assert stream_topic(T_RESPONSE, q.stream) in resp_topics
        assert q.done and q.finished_at is not None
        assert q.admitted_at >= q.arrived_at
        # staleness bound: the serving model is at most one training
        # window behind the context it answered against
        assert 0 <= q.context_window - q.model_window <= 1


def test_staleness_watchdog_serves_fallback_under_delayed_sync(fleet_setup,
                                                               cfg):
    """Regression for the staleness watchdog: when every model-sync publish
    is delayed well past the staleness bound, answers whose speed model has
    fallen more than ``staleness_bound`` windows behind their context must
    be served from the batch fallback (and stamped ``served_fallback``),
    while every answer still served from a speed model keeps honouring the
    bound."""
    from repro.core.scenarios import CHAOS_STAGE_COSTS
    from repro.runtime import FaultPlane, MessageFault

    streams, bp = fleet_setup
    ids = list(streams)
    ff = lstm_fleet_forecaster(cfg, epochs=EPOCHS, batch_size=64)
    period = 5.0
    # window 0's sync (published ~0.3s) lands clean; every later sync is
    # delayed 3 windows, so the serving model is pinned at window 0
    plane = FaultPlane(0, message_faults=[
        MessageFault("model/latest/*", "delay", p=1.0, delay_s=3 * period,
                     start=0.8 * period)])
    # arrivals span windows 1..2+, after the delayed syncs start biting
    trace = open_loop_trace(ids, qps=3.0, n_requests=30, start=2 * period,
                            seed=3)
    ex = FleetBusExecutor(
        FleetStages.build(ff, mode="dynamic"), edge_cloud_integrated(),
        paper_topology(), window_period_s=period, query_trace=trace,
        serve_slots=4, fault_plane=plane,
        stage_costs=dict(CHAOS_STAGE_COSTS), staleness_bound=1)
    res = ex.run(streams, bp, jax.random.PRNGKey(1), n_windows=3)

    s = res.serving
    assert s is not None and s["n_answered"] == 30
    assert plane.stats["msg_delay"] > 0
    # the watchdog flipped stale answers to the fallback ...
    assert s["fallback_frac"] > 0.0
    assert any(q.served_fallback for q in res.queries)
    # ... and whatever was still served from a speed model obeys the bound
    for q in res.queries:
        if not q.served_fallback and q.model_window >= 0:
            assert 0 <= q.context_window - q.model_window <= 1
    assert s["max_staleness"] <= 1


def test_queryplane_refuses_a_multi_step_forecaster():
    """The plane rolls a one-step forecaster's context a tick at a time; a
    forecaster that answers a whole horizon at once (TFT's 24 steps) is
    refused with a clear error, by the plane and by an executor asked to
    serve through it, before anything compiles."""
    import dataclasses

    from repro.core import fleet_forecaster_for
    from repro.core.windows import WindowPlan, WindowedStream

    with pytest.raises(ValueError, match="one-step forecaster"):
        QueryPlane(["a"], n_slots=2, horizon=24)
    assert QueryPlane(["a"], n_slots=2, horizon=1).ids == ["a"]

    pub = get_config("tft-electricity")
    tcfg = pub.replace(tft=dataclasses.replace(
        pub.tft, hidden=8, n_heads=2, lookback=6, horizon=3, n_ids=2))
    fc = fleet_forecaster_for(tcfg, epochs=1, batch_size=4,
                              devices=jax.devices()[:1])
    assert fc.horizon == 3
    series = np.zeros((40, 5), np.float32)
    streams = {f"s{i}": WindowedStream(series, WindowPlan(
        2, 20, 6, horizon=3, period=10.0, stream_id=i)) for i in range(2)}
    ex = FleetBusExecutor(FleetStages.build(fc, mode="dynamic"),
                          edge_cloud_integrated(), paper_topology(), qps=5.0)
    with pytest.raises(ValueError, match="answers 3 steps at once"):
        ex.run(streams, None, jax.random.PRNGKey(0), n_windows=2)
