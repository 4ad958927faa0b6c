"""The queries cell's output check on the CPU at a small size: a sound run
is correct, the bfloat16 control fails the answer limit, and an altered or
unserved answer makes ``correct`` false (the windows cell too, for an
altered forecast)."""
import jax.numpy as jnp
import numpy as np
import pytest

from _small import cell, run_small


def test_sound_queries_run_is_correct():
    res = run_small("t64-queries")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"query_p95_s", "queries_per_s", "setup_s"}


@pytest.mark.parametrize("workload", ["t64-windows", "t64-queries"])
def test_altered_answer_is_caught(monkeypatch, workload):
    from repro.training.compiled import FleetForecaster

    orig = FleetForecaster.predict_fleet
    monkeypatch.setattr(
        FleetForecaster, "predict_fleet",
        lambda self, p, xs: [a + 0.05 for a in orig(self, p, xs)])
    res = run_small(workload)
    assert res["correct"] is False, res["checks"]


def test_queries_left_out_of_the_batch_are_caught(monkeypatch):
    from repro.serving.query_plane import QueryPlane

    orig = QueryPlane.build_batch

    def half(self):
        out = orig(self)
        if out is None:
            return None
        by_stream, xs = out
        # the second half of the fleet's streams never gets its rows served
        cut = len(self.ids) // 2
        for j, sid in enumerate(self.ids[cut:], cut):
            by_stream[sid] = []
            xs[j] = xs[j][:0]
        return by_stream, xs

    monkeypatch.setattr(QueryPlane, "build_batch", half)
    res = run_small("t64-queries")
    assert res["correct"] is False
    assert res["checks"]["queries_unanswered"]["value"] > 0


def test_bfloat16_control_fails_the_answer_limit():
    from chipbench import generator
    from chipbench.check import compare_answers

    drv, c, ctx, f = cell("t64-queries")
    arr = generator.arrivals(ctx.seed, f["traffic"], c.S, 0.5)
    uids = list(range(len(arr["due"])))
    ref = c.reference(arr, uids, jnp.float32)
    ctl = c.reference(arr, uids, jnp.bfloat16)
    got = compare_answers({"answers": ctl["answers"], "unanswered": 0}, ref)
    assert got["answer_gap"] > f["limits"]["answer_gap"], got
    assert np.isfinite(got["answer_gap"])
