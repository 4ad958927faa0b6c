"""The windows cells' output check on the CPU at a small size: a sound
run is correct, the bfloat16 control fails a limit, and each fault planted
in the fit makes ``correct`` false."""
import jax.numpy as jnp
import pytest

from _small import cell, run_small


def test_sound_windows_run_is_correct():
    res = run_small("t64-windows")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"window_s", "setup_s"}
    assert list(res)[-1] == "checks"


def _unchanged(orig):
    def make(model, opt):
        step = orig(model, opt)

        def broken(params, opt_state, batch):
            _, opt_state, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics

        return broken

    return make


def _half_batch(orig):
    def make(model, opt):
        step = orig(model, opt)

        def broken(params, opt_state, batch):
            m = batch["mask"]
            keep = (jnp.arange(m.shape[0]) < m.shape[0] // 2).astype(m.dtype)
            return step(params, opt_state, dict(batch, mask=m * keep))

        return broken

    return make


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_windows_fault_in_the_fit_is_caught(monkeypatch, fault):
    from repro.training import compiled

    plant = {"state_unchanged": _unchanged, "half_batch": _half_batch}[fault]
    monkeypatch.setattr(compiled, "make_train_step",
                        plant(compiled.make_train_step))
    res = run_small("t64-windows")
    assert res["correct"] is False, res["checks"]


def test_bfloat16_control_fails_a_windows_limit():
    from chipbench.check import compare_windows

    drv, c, ctx, f = cell("t64-windows")
    checked = c.check_windows(4)
    ref = c.reference(checked, jnp.float32)
    ctl = c.reference(checked, jnp.bfloat16)
    got = compare_windows(c.as_program(ctl, checked), ref, checked)
    assert any(got[k] > f["limits"][k] for k in got), got
    same = compare_windows(c.as_program(ref, checked), ref, checked)
    assert all(v == 0.0 for v in same.values()), same
