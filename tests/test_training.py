"""Optimizer, train loop and checkpoint tests."""
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.training import adamw, checkpoint, fit, make_train_step, sgd, warmup_cosine
from repro.training.optimizer import global_norm


def test_adamw_matches_numpy_reference():
    """One AdamW step against a hand-rolled numpy implementation."""
    lr, b1, b2, eps = 0.1, 0.9, 0.999, 1e-8
    p0 = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.5, -0.1, 0.2])}
    opt = adamw(lr, b1, b2, eps, weight_decay=0.0, clip_norm=None)
    st = opt.init(p0)
    p1, st1, _ = opt.update(g, st, p0)

    gn = np.asarray(g["w"])
    m = (1 - b1) * gn
    v = (1 - b2) * gn * gn
    mhat = m / (1 - b1)
    vhat = v / (1 - b2)
    expect = np.asarray(p0["w"]) - lr * mhat / (np.sqrt(vhat) + eps)
    np.testing.assert_allclose(np.asarray(p1["w"]), expect, rtol=1e-6)


def test_adamw_weight_decay_and_clip():
    opt = adamw(0.1, weight_decay=0.1, clip_norm=1.0)
    p0 = {"w": jnp.asarray([10.0])}
    g = {"w": jnp.asarray([100.0])}  # will be clipped to norm 1
    st = opt.init(p0)
    p1, _, metrics = opt.update(g, st, p0)
    assert float(metrics["grad_norm"]) == pytest.approx(100.0)
    # clipped g=1.0 -> mhat/sqrt(vhat) = 1; decay adds 0.1*10
    expect = 10.0 - 0.1 * (1.0 + 0.1 * 10.0)
    np.testing.assert_allclose(np.asarray(p1["w"]), [expect], rtol=1e-4)


def test_warmup_cosine_schedule():
    s = warmup_cosine(1.0, warmup=10, total=110, final_frac=0.1)
    assert float(s(jnp.asarray(0))) == 0.0
    assert float(s(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(110))) == pytest.approx(0.1, abs=1e-6)
    assert float(s(jnp.asarray(5))) == pytest.approx(0.5)


def test_global_norm():
    t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)


def test_fit_reduces_lstm_loss():
    cfg = get_config("lstm-paper")
    model = get_model(cfg)
    rng = np.random.default_rng(0)
    # learnable signal: y = mean of last lag of target channel
    x = rng.normal(0, 1, (256, 5, 5)).astype(np.float32)
    y = x[:, :, 0].mean(axis=1, keepdims=True).astype(np.float32)
    res = fit(model, {"x": x, "y": y}, epochs=30, batch_size=64, lr=1e-2)
    first = res.history[0]["loss"] if res.history else None
    loss, _ = model.loss_fn(res.params, {"x": jnp.asarray(x), "y": jnp.asarray(y)})
    assert float(loss) < 0.05, f"LSTM failed to fit: {float(loss)}"
    assert res.steps == 30 * (256 // 64)


def test_sgd_descends_quadratic():
    opt = sgd(0.05, momentum=0.5)
    p = {"w": jnp.asarray([5.0])}
    st = opt.init(p)
    for _ in range(100):
        g = {"w": 2 * p["w"]}
        p, st, _ = opt.update(g, st, p)
    assert abs(float(p["w"][0])) < 0.05


def test_checkpoint_roundtrip():
    tree = {
        "layers": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "head": {"b": jnp.asarray([1.5], jnp.bfloat16)},
    }
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        h = checkpoint.save(path, tree, step=7, meta={"arch": "test"})
        assert h.nbytes > 0 and h.path.endswith(".npz")
        back = checkpoint.load(h.path)
        np.testing.assert_array_equal(
            np.asarray(back["layers"]["w"]), np.asarray(tree["layers"]["w"])
        )
        assert back["head"]["b"].dtype == jnp.bfloat16


def test_train_step_is_jittable_and_deterministic():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt = adamw(1e-3)
    step = jax.jit(make_train_step(model, opt))
    batch = {
        "tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size),
        "targets": jax.random.randint(key, (2, 16), 0, cfg.vocab_size),
    }
    p1, s1, m1 = step(params, opt.init(params), batch)
    p2, s2, m2 = step(params, opt.init(params), batch)
    assert float(m1["loss"]) == float(m2["loss"])


def _lstm_paper_batch(lag, seed=0):
    cfg = get_config("lstm-paper")
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (64, lag, cfg.lstm.n_features)).astype(np.float32)
    y = x[:, :, :1].mean(axis=1).astype(np.float32)
    return cfg, {"x": jnp.asarray(x), "y": jnp.asarray(y)}


@pytest.mark.parametrize("lag, rolled", [(5, False), (32, True)])
def test_lstm_grad_unrolls_short_lags(lag, rolled):
    """The gradient of a short-lag fit is straight-line code: no device
    loop and no per-step rewrite of a stacked residual buffer.  A lag past
    ``UNROLL_MAX_LAG`` keeps the rolled scan."""
    from repro.models import lstm

    assert (lag > lstm.UNROLL_MAX_LAG) == rolled
    cfg, batch = _lstm_paper_batch(lag)
    params = lstm.init_params(cfg, jax.random.PRNGKey(0))
    grad = jax.grad(lambda p: lstm.loss_fn(cfg, p, batch)[0])
    hlo = jax.jit(grad).lower(params).compile().as_text()
    has_loop = re.search(r"\bwhile\(", hlo) is not None
    assert has_loop == rolled
    if not rolled:
        assert "dynamic-update-slice" not in hlo


def test_lstm_unrolled_matches_rolled_scan(monkeypatch):
    """For lag 5 the unrolled recurrence computes what the rolled scan over
    the same ``cell_step`` computes: forward outputs and every parameter
    gradient agree to float32 rounding.  A gradient leaf is compared by its
    norm: a near-zero entry left by cancellation differs from its rolled
    twin by a rounding of the leaf's scale, not of its own."""
    from repro.models import lstm

    cfg, batch = _lstm_paper_batch(5, seed=1)
    params = lstm.init_params(cfg, jax.random.PRNGKey(1))

    def run():
        pred = jax.jit(lambda p: lstm.forward(cfg, p, batch["x"]))(params)
        grads = jax.jit(jax.grad(
            lambda p: lstm.loss_fn(cfg, p, batch)[0]))(params)
        return pred, grads

    pred, grads = run()
    monkeypatch.setattr(lstm, "UNROLL_MAX_LAG", 0)
    pred_r, grads_r = run()
    np.testing.assert_allclose(np.asarray(pred), np.asarray(pred_r),
                               rtol=1e-6)
    for g, g_r in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(grads_r)):
        g, g_r = np.asarray(g), np.asarray(g_r)
        assert np.linalg.norm(g - g_r) <= 1e-6 * np.linalg.norm(g_r)
