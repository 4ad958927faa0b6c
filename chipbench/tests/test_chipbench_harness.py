"""The harness's own arithmetic and bookkeeping, on the CPU."""
import json
import os
import re
import shutil
import time

import numpy as np
import pytest

from _small import ROOT, SEED

from chipbench import flops, generator, run, trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_benchmark_names_and_units_use_the_allowed_characters():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
        names.append(w["name"])
    assert len(set(names)) == len(names)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(names)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert LINE.match(m["layer"]) and m["moves"] in e2e
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in e2e
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_what_it_must(workload):
    e2e = run.metrics_of(BENCH, workload, "end_to_end")
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2
    layer = run.metrics_of(BENCH, workload, "per_layer", names)
    assert layer and all(m["moves"] in names for m in layer)
    files = run.cell_files(workload)
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "drivers",
                                       files["traffic"]["driver"] + ".py"))
    for m in layer:
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py"))


def test_harness_finds_config_traffic_and_metric_from_files(tmp_path):
    """A new cell and a new per-layer metric are files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(os.path.join(ROOT, "chipbench/configs/"
                                            "paper-turbines-64.json")))
    cfg.update(name="paper-turbines-8", streams=8)
    (root / "chipbench/configs/paper-turbines-8.json").write_text(
        json.dumps(cfg))
    (root / "chipbench/traffic/slow-queries.json").write_text(json.dumps(
        dict(json.load(open(os.path.join(ROOT, "chipbench/traffic/"
                                               "queries.json"))),
             rate_per_s=100)))
    (root / "chipbench/limits/t8-slow.json").write_text(
        json.dumps({"answer_gap": 1.0}))
    (root / "chipbench/metrics/rows_per_tick.query.py").write_text(
        "def read(rd):\n    return rd['rows'] / rd['ticks']\n")
    bench["configs"].append({"name": "paper-turbines-8", "source": "x",
                             "file": "chipbench/configs/paper-turbines-8.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "t8-slow", "config": "paper-turbines-8",
                               "traffic": "slow-queries", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "rows_per_tick.query", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "query plane", "moves": "query_p95_s",
                               "workloads": ["t8-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    f = run.cell_files("t8-slow", root=str(root))
    assert f["cfg"]["streams"] == 8 and f["traffic"]["rate_per_s"] == 100
    assert f["limits"] == {"answer_gap": 1.0}
    layer = run.metrics_of(bench, "t8-slow", "per_layer",
                           ["query_p95_s", "setup_s"])
    assert [m["name"] for m in layer] == ["compile_s", "rows_per_tick.query"]
    assert run.read_metric("rows_per_tick.query", {"rows": 12, "ticks": 4},
                           str(root)) == 3


def test_lstm_flops_match_a_hand_count():
    cfg = {"hidden": 40, "n_features": 5, "dense": 10, "lag": 5}
    # one lag step: x (1x5) @ W (5x160) and h (1x40) @ U (40x160),
    # two FLOPs per multiply-add
    step = 2 * 5 * 160 + 2 * 40 * 160
    assert step == 14_400
    heads = 2 * 40 * 10 + 2 * 10 * 1
    assert flops.forward_per_example(cfg) == 5 * step + heads == 72_820
    assert flops.train_per_example_epoch(cfg) == 218_460
    cfg.update(speed_epochs=100)
    assert flops.window_flops(cfg, 64, 250, 4) == 64 * (
        100 * 250 * 218_460 + 4 * 250 * 72_820)


class _Stage:
    """Serving that answers zeros, stalling once for ``stall_s``."""

    def __init__(self, stall_s: float, at_tick: int):
        self.stall_s, self.at, self.n = stall_s, at_tick, 0

    def __call__(self, params_seq, xs):
        self.n += 1
        if self.n == self.at:
            time.sleep(self.stall_s)
        return {"preds": [np.zeros((len(x), 1), np.float32) for x in xs]}


def _loop(stall_s: float):
    from chipbench.common import Spans
    from chipbench.drivers import queries

    class Ctx:
        spans = Spans()
        traffic = {"drain_s": 2.0}

    c = queries.Cell.__new__(queries.Cell)
    c.ctx, c.ids, c.slots, c.params = Ctx, ["a", "b"], 8, [None, None]
    c.datas = [{"x": np.zeros((3, 5, 5), np.float32)}] * 2
    c.serving = _Stage(stall_s, at_tick=5)
    traffic = {"rate_per_s": 400, "kind_mix": [1, 0, 0], "shape_seed": 3}
    arr = generator.arrivals(SEED, traffic, 2, 0.5)
    return arr, c.loop(arr, 0.5, sample=set())


def test_open_loop_latency_runs_from_the_due_time():
    arr, calm = _loop(0.0)
    assert np.isfinite(calm["lat"]).all()
    # answered no earlier than due, and the generator never ran early
    assert (calm["lat"] >= 0).all() and (calm["submit_lag"] >= 0).all()
    arr, stalled = _loop(0.1)
    # a 0.1 s stall early in the window delays every query behind it: the
    # queries due during the stall wait it out from their due time
    assert np.quantile(stalled["lat"], 0.95) > 0.05 > np.quantile(
        calm["lat"], 0.95)
    assert stalled["lat"].max() >= 0.09


def test_arrivals_keep_the_work_fixed_across_seeds():
    t = {"rate_per_s": 1000, "kind_mix": [1, 1, 1], "shape_seed": 1}
    a = generator.arrivals(1, t, 64, 2.0)
    b = generator.arrivals(2**31 + 5, t, 64, 2.0)
    assert len(a["due"]) == len(b["due"]) == 2000
    assert np.allclose(
        np.sort(np.diff(np.r_[0, a["due"]])),
        np.sort(np.diff(np.r_[0, b["due"]])), rtol=1e-6)
    assert np.array_equal(np.bincount(a["kind"]), np.bincount(b["kind"]))
    assert np.array_equal(np.sort(np.bincount(a["stream"], minlength=64)),
                          np.sort(np.bincount(b["stream"], minlength=64)))
    assert a["due"][-1] < 2.0


def test_fleet_data_is_seeded_and_scaled():
    cfg = {"history_records": 200, "lag": 5, "records_per_window": 50}
    t = {"drift_mix": ["gradual", "seasonal", "abrupt", "none"]}
    h1, l1, k1 = generator.fleet(7, 8, 3, t, cfg)
    h2, l2, k2 = generator.fleet(7, 8, 3, t, cfg)
    assert np.array_equal(l1, l2) and k1 == k2
    assert sorted(k1) == sorted(["gradual", "seasonal", "abrupt", "none"] * 2)
    assert h1.min() == 0.0 and h1.max() == 1.0
    assert l1.shape == (8, 3 * 50 + 5, 5)
    w = generator.window(l1[0], 1, 50, 5)
    assert w["x"].shape == (50, 5, 5)
    assert np.array_equal(w["x"][0, -1], l1[0, 49])


def _ev(name, start_us, dur_us):
    return [name, start_us * 1000, dur_us * 1000]


def test_trace_reduction_by_hand():
    tr = {"devices": {"/device:TPU:0": {
        "ops": [_ev("fusion.1", 10, 20), _ev("fusion.2", 25, 10),
                _ev("copy", 60, 10)],
        "modules": [_ev("jit_fleet_fit(3)", 10, 25),
                    _ev("jit_fleet_predict(4)", 60, 10)]}},
        "host": [_ev("cb:window", 0, 100), _ev("cb:speed_training", 5, 40),
                 _ev("cb:model_sync", 40, 15)]}
    r = trace.reduce(tr)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(35e-6)  # [10,35] and [60,70]
    assert r["modules_s"] == pytest.approx(
        {"jit_fleet_fit": 25e-6, "jit_fleet_predict": 10e-6})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(20e-6)]
    gaps = dict(r["idle_gaps"])
    # [0,10] under the training span; [35,60] (mid 47.5) under model_sync;
    # [70,100] under nothing but the window
    assert gaps["cb:speed_training"] == pytest.approx(10e-6)
    assert gaps["cb:model_sync"] == pytest.approx(25e-6)
    assert gaps["cb:window"] == pytest.approx(30e-6)
    assert trace.reduce({"devices": {}, "host": tr["host"]}) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "t64_windows_trace.json")
    tr = json.load(open(path))
    r = trace.reduce(tr)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["modules_s"]["jit_fleet_fit"] > 0
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


@pytest.mark.parametrize("name", ["paper-turbines-64",
                                  "paper-turbines-1024"])
def test_configs_state_the_programs_widths_and_parameter_count(name):
    from chipbench.common import program_model

    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      name + ".json")))
    assert program_model(cfg).lstm.hidden == cfg["hidden"]
    with pytest.raises(ValueError, match="parameters"):
        program_model(dict(cfg, parameters=10981))
    with pytest.raises(ValueError, match="n_features"):
        program_model(dict(cfg, n_features=25))
