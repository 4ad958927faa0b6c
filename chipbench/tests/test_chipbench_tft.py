"""The TFT windows cell through the harness on the CPU at a small size: a
sound run is correct and reports what the cell must; the bfloat16 control
and a fault planted in the reference's fit, standing in for the program,
fail a limit, and a sound variant with other rounding does not; the FLOP
count matches a hand count, and the configuration states the program's
widths."""
import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from _small import ROOT, SEED

from chipbench import flops_tft, run
from repro.configs import get_config as PUBLISHED_CONFIG

SMALL = dict(hidden=16, n_heads=2, lookback=20, lag=20, horizon=4,
             streams=3, records_per_window=40, history_records=120,
             speed_epochs=2, speed_batch=8, batch_epochs=1, batch_size=8)


def _files():
    f = run.cell_files("tft8-windows")
    f["cfg"].update(SMALL)
    f["traffic"].update(max_windows=5, warmup_windows=2, trace_windows=3,
                        check_windows=2)
    f["peaks"] = {"cpu": {"bf16_flops_per_s": 1e12}}
    return f


def test_tft_flops_match_a_hand_count():
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "tft-electricity-8.json")))
    # PERF.md's count at the published widths, term by term: static
    # encoders, past and future selection, LSTMs, gated skips, enrichment
    # and position-wise GRNs, attention, head
    H, T = 160, 192
    hand = (103_360 + 5 * 204_800
            + 168 * (486_400 + 8 * 204_800) + 51_200
            + 24 * (209_600 + 3 * 204_800) + 51_200
            + T * 16 * H * H
            + 3 * T * 4 * H * H
            + 2 * T * 8 * H * H + 51_200
            + T * 2 * (2 * 4 * H * 40 + 2 * H * 40) + 4 * 4 * T * T * 40
            + 24 * 2 * H * 3)
    assert flops_tft.forward_per_example(cfg) == hand == 642_484_160
    assert flops_tft.window_flops(cfg, 8, 59, 4) == 8 * (
        100 * 59 * 3 * hand + 4 * 59 * hand)


def test_tft_config_states_the_programs_widths_and_parameter_count(
        monkeypatch):
    import repro.configs as rc
    from chipbench.drivers import tft_windows

    # the published config, whatever a module fixture has patched in
    monkeypatch.setattr(rc, "get_config", PUBLISHED_CONFIG)

    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "tft-electricity-8.json")))
    assert tft_windows.program_tft(cfg).tft.hidden == cfg["hidden"]
    with pytest.raises(ValueError, match="parameters"):
        tft_windows.program_tft(dict(cfg, parameters=2_500_000))
    with pytest.raises(ValueError, match="n_ids"):
        tft_windows.program_tft(dict(cfg, streams=4))
    with pytest.raises(ValueError, match="clips"):
        tft_windows.program_tft(dict(cfg, max_grad_norm=1.0))


def _small_program():
    """The program's tft-electricity at the small widths; writes its
    parameter count into ``SMALL``."""
    import jax
    import numpy as np

    import repro.configs as rc
    from repro.models import get_model

    pub = rc.get_config("tft-electricity")
    cfg = pub.replace(tft=dataclasses.replace(
        pub.tft, hidden=16, n_heads=2, lookback=20, horizon=4, n_ids=3))
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    SMALL["parameters"] = sum(int(np.prod(a.shape))
                              for a in jax.tree_util.tree_leaves(shapes))
    return cfg


@pytest.fixture(scope="module")
def small_program():
    import repro.configs as rc

    cfg = _small_program()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(rc, "get_config", lambda name: cfg)
        yield cfg


@pytest.fixture(scope="module")
def small_cell(small_program):
    """The driver's cell built as a run builds it, and its checked
    windows' float32 reference."""
    import argparse
    import time

    import jax

    from chipbench.common import CompileMeter, Spans
    from chipbench.drivers import tft_windows

    f = _files()
    args = argparse.Namespace(workload="tft8-windows", seed=SEED,
                              seconds=0.5, trace=0)
    ctx = run.Ctx(args, f, jax.devices()[:1], CompileMeter(), Spans(), 0.0,
                  time.perf_counter())
    c = tft_windows.Cell(ctx)
    checked = c.check_windows(4)
    return c, checked, c.reference(checked, jnp.float32), f["limits"]


def _run(trace=0):
    return run.run_cell(["--workload", "tft8-windows", "--seed", str(SEED),
                         "--seconds", "0.5", "--trace", str(trace)],
                        require_chip=False, files=_files(), cache=False)


def test_sound_tft_run_is_correct_and_reports_its_metrics(small_program):
    res = _run(trace=1)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    got = set(res["metrics"])
    # the device-trace metrics find no chip here and read nothing
    assert {"encdec_s.window", "fit_stage_s.window", "publish_s.window",
            "compiles.window", "mfu.window", "compile_s"} <= got, got
    assert res["metrics"]["encdec_s.window"]["value"] > 0
    assert res["metrics"]["compiles.window"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("stand_in", ["bfloat16", "state_unchanged",
                                      "half_batch"])
def test_control_and_faults_fail_a_tft_limit(small_cell, stand_in):
    from chipbench.drivers.tft_windows import compare

    c, checked, ref, limits = small_cell
    if stand_in == "bfloat16":
        other = c.reference(checked, jnp.bfloat16)
    else:
        other = c.reference(checked, jnp.float32, variant=stand_in)
    got = compare(c.as_program(other, checked), ref, checked)
    assert any(got[k] > limits[k] for k in got), got
    # each fails the early steps' median gap too, not the worst stream only
    assert got["fit_early_steps_gap"] > limits["fit_early_steps_gap"], got


def test_sound_variant_and_the_reference_itself_pass(small_cell):
    from chipbench.drivers.tft_windows import compare

    c, checked, ref, limits = small_cell
    same = compare(c.as_program(ref, checked), ref, checked)
    assert all(v == 0.0 for v in same.values()), same
    assert set(same) == set(limits)
    fused = c.reference(checked, jnp.float32, variant="fused_gates")
    got = compare(c.as_program(fused, checked), ref, checked)
    assert all(got[k] <= limits[k] for k in got), got
