"""The readers of the program's span records (``metrics/*`` with source
``program_span``), each fed a recorded ring and the run's readings."""
import collections
import sys

import pytest

from _small import ROOT  # noqa: F401  (puts the program on the path)

from chipbench import run
from chipbench.common import Spans

# (name, start, dur, self_s), in the order the spans closed
WINDOWS_RING = [
    ("stage.model_sync", 0.5, 0.1, 0.1),       # warm-up, before the window
    ("loop", 1.0, 0.5, 0.1),                   # an earlier run's loop
    ("compile:jit_fleet_predict", 10.5, 0.2, 0.2),
    ("fleet.fit.stage", 11.1, 0.5, 0.5),
    ("stage.speed_training", 11.0, 2.0, 1.5),
    ("executor.dispatch_train", 10.9, 2.2, 0.2),
    ("executor.publish_models", 13.5, 1.0, 1.0),
    ("stage.weight_solve", 15.0, 0.05, 0.05),
    ("executor.on_part", 15.0, 0.25, 0.2),
    ("executor.on_part", 16.0, 0.35, 0.35),
    ("fleet.fit.stage", 17.0, 0.3, 0.3),
    ("loop", 10.0, 10.0, 3.0),                 # the timed run
    ("compile:jit_reference", 21.0, 1.0, 1.0),  # the check, after it
]
# two windows in the loop
WINDOWS = {"publish_s.window": 0.5, "part_s.window": 0.3,
           "loop_self_s.window": 1.5, "fit_stage_s.window": 0.4,
           "compiles.window": 1}

QUERIES_RING = [
    ("stage.serving", 1.0, 0.1, 0.1),          # the warm-up loop's tick
    ("plane.admit", 9.90, 0.01, 0.01),         # tick 1, before its predict
    ("plane.build_batch", 9.92, 0.02, 0.02),
    ("fleet.predict.stage", 10.00, 0.02, 0.02),
    ("fleet.predict.wait", 10.05, 0.04, 0.04),
    ("stage.serving", 10.00, 0.10, 0.04),
    ("plane.apply", 10.10, 0.03, 0.03),
    ("plane.retire", 10.13, 0.01, 0.01),
    ("plane.admit", 10.14, 0.01, 0.01),        # tick 2
    ("plane.build_batch", 10.15, 0.02, 0.02),
    ("compile:jit_fleet_predict", 10.20, 0.05, 0.05),
    ("fleet.predict.stage", 10.20, 0.06, 0.06),
    ("fleet.predict.wait", 10.27, 0.02, 0.02),
    ("stage.serving", 10.20, 0.10, 0.02),
    ("plane.apply", 10.30, 0.03, 0.03),        # after the last predict
]
# two ticks timed; what lies between the first predict's start and the
# last one's end: the predicts, and tick 1's apply and retire and tick 2's
# admit and build
QUERIES = {"plane_s.query": (0.03 + 0.01 + 0.01 + 0.02) / 2,
           "predict_stage_s.query": (0.02 + 0.06) / 2,
           "predict_wait_s.query": (0.04 + 0.02) / 2,
           "compiles.query": 1}


def _ring(monkeypatch, recs, full=False):
    """The program's ring holding ``recs``; ``full``: at its bound, so it
    may have dropped older records."""
    from repro import tracing

    monkeypatch.setattr(tracing, "_ring", collections.deque(
        recs, maxlen=len(recs) if full else len(recs) + 8))


def _queries_rd():
    spans = Spans()
    spans.walls["tick"] = [0.11, 0.12]
    return {"spans": spans}


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_windows_reader_reads_the_last_loop(monkeypatch, name):
    _ring(monkeypatch, WINDOWS_RING)
    assert run.read_metric(name, {"n_windows": 2}) == pytest.approx(
        WINDOWS[name], abs=1e-12)
    # a full ring whose oldest record ended inside the timed loop may have
    # dropped part of it
    _ring(monkeypatch, WINDOWS_RING[2:], full=True)
    assert run.read_metric(name, {"n_windows": 2}) is None


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_queries_reader_reads_the_timed_ticks(monkeypatch, name):
    _ring(monkeypatch, QUERIES_RING)
    assert run.read_metric(name, _queries_rd()) == pytest.approx(
        QUERIES[name], abs=1e-12)
    _ring(monkeypatch, QUERIES_RING[3:], full=True)
    assert run.read_metric(name, _queries_rd()) is None


@pytest.mark.parametrize("name", sorted(WINDOWS) + sorted(QUERIES))
def test_reader_of_a_program_without_the_recorder_reads_nothing(
        monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    rd = dict(_queries_rd(), n_windows=2)
    assert run.read_metric(name, rd) is None
