"""The Temporal Fusion Transformer (``models/tft.py``) against its plain
reference (``models/tft_reference.py``) at a small size on the CPU, with
seeded random weights; its samples, its dropout keys, its fleet fit and its
parameter count at the published widths."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.windows import WindowPlan, WindowedStream, known_inputs
from repro.models import get_model, tft
from repro.models import tft_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = get_config("tft-electricity")
CFG = PUBLISHED.replace(tft=dataclasses.replace(
    PUBLISHED.tft, hidden=16, n_heads=2, lookback=20, horizon=4, n_ids=3))
C = CFG.tft
DIMS = (C.n_observed, C.n_known, C.lookback)
B = 5

# Forward outputs are O(1) and pass about twenty float32 layers; the two
# implementations sum in other orders (batched einsums against per-sample,
# per-variable and per-head products), which moves them by a few ulps a
# layer: 1e-5 holds that with room, and bfloat16's 8-bit mantissa misses it
# by orders of magnitude.
FWD_TOL = 1e-5
# gradients sum over rows and positions too, and the smallest leaves are
# near 1e-4 in size: relative to each leaf's largest entry
GRAD_TOL = 1e-4


def _params(seed=0):
    """The program's init with every leaf moved by seeded noise, so biases,
    LayerNorm gains and the embedding all matter."""
    p = get_model(CFG).init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(p)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, ks)])


def _batch(seed=0, n=B):
    rng = np.random.default_rng(seed)
    series = rng.random((3, 80, C.n_observed)).astype(np.float32)
    xs, ys = [], []
    for i in range(3):
        s = WindowedStream(series[i], WindowPlan(
            2, 40, C.lookback, horizon=C.horizon, period=37.0, stream_id=i,
            first_record=100))
        d = s.supervised(1)
        xs.append(d["x"])
        ys.append(d["y"])
    x, y = np.concatenate(xs), np.concatenate(ys)
    pick = rng.choice(len(x), n, replace=False)
    return x[pick], y[pick]


@jax.jit
def _ref_forward(p, x, rng=None):
    return ref.forecasts(p, x, DIMS, rng, C.dropout)


_ref_grad = jax.jit(jax.value_and_grad(ref.loss), static_argnums=(4, 5, 7))
_forward = jax.jit(lambda p, x, rng=None: tft.forward(CFG, p, x, rng))
_loss = jax.jit(lambda p, batch: get_model(CFG).loss_fn(p, batch)[0])


def test_forward_matches_the_reference():
    p = _params()
    x, _ = _batch()
    got = _forward(p, x)
    want = _ref_forward(p, x)
    assert got.shape == (B, C.horizon, len(C.quantiles))
    np.testing.assert_allclose(got, want, rtol=FWD_TOL, atol=FWD_TOL)
    med = jax.jit(lambda p, x: tft.predict(CFG, p, x))(p, x)
    np.testing.assert_allclose(med, want[..., 1], rtol=FWD_TOL, atol=FWD_TOL)


def test_dropout_forward_follows_the_stated_keys():
    p = _params()
    x, _ = _batch()
    key = jax.random.PRNGKey(7)
    got = _forward(p, x, key)
    want = _ref_forward(p, x, key)
    np.testing.assert_allclose(got, want, rtol=FWD_TOL, atol=FWD_TOL)
    # dropout is on: another key moves the forecasts
    other = _forward(p, x, jax.random.PRNGKey(8))
    assert float(jnp.max(jnp.abs(other - got))) > 1e-3


def test_loss_and_gradients_match_the_reference():
    p = _params()
    x, y = _batch()
    mask = np.ones((B,), np.float32)
    mask[-1] = 0.0
    key = jax.random.PRNGKey(3)
    l, g = jax.jit(jax.value_and_grad(_loss))(
        p, {"x": x, "y": y, "mask": mask, "rng": key})
    lr, gr = _ref_grad(p, x, y, mask, C.quantiles, DIMS, key, C.dropout)
    np.testing.assert_allclose(l, lr, rtol=FWD_TOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= GRAD_TOL * scale, path


def test_the_mask_gives_the_unpadded_loss():
    p = _params()
    x, y = _batch()
    plain = _loss(p, {"x": x[:3], "y": y[:3]})
    xp = np.concatenate([x[:3], np.zeros_like(x[:2])])
    yp = np.concatenate([y[:3], np.full_like(y[:2], 9.0)])
    mask = np.array([1, 1, 1, 0, 0], np.float32)
    masked = _loss(p, {"x": xp, "y": yp, "mask": mask})
    np.testing.assert_allclose(masked, plain, rtol=1e-6)


def test_the_epoch_scan_hands_each_step_its_dropout_key(monkeypatch):
    """Step i's key is the i-th of ``split(fold_in(perm_key, 0xD0),
    steps)``, and the permutations are those of a model without dropout;
    a model without dropout gets no key."""
    from repro.models.model import Model
    from repro.training import compiled

    def make_train_step(model, opt):
        def step(params, opt_state, batch):
            # each step's rows and key, read back through the history
            return params, opt_state, {"loss": (batch["x"][:, 0],
                                                batch.get("rng"))}

        return step

    monkeypatch.setattr(compiled, "make_train_step", make_train_step)

    def run(dropout):
        model = Model(cfg=CFG, init=None, loss_fn=None, prefill=None,
                      decode_step=None, init_cache=None, dropout=dropout)
        fit = compiled._make_epoch_scan(model, None, 3, 4, 8)
        x = jnp.arange(8, dtype=jnp.float32)[:, None]
        _, _, (rows, keys) = fit({}, None, x, x, jnp.ones(8),
                                 jax.random.PRNGKey(5))
        return np.asarray(rows), keys

    rows, keys = run(0.0)
    rows_d, keys_d = run(C.dropout)
    assert keys is None
    np.testing.assert_array_equal(rows, rows_d)
    np.testing.assert_array_equal(
        keys_d, compiled.dropout_keys(jax.random.PRNGKey(5), 6))
    np.testing.assert_array_equal(
        compiled.dropout_keys(jax.random.PRNGKey(5), 6),
        jax.random.split(jax.random.fold_in(jax.random.PRNGKey(5), 0xD0), 6))


def test_fleet_fit_first_steps_match_a_per_stream_reference_fit():
    from repro.core import fleet_forecaster_for

    fc = fleet_forecaster_for(CFG, epochs=1, batch_size=8,
                              devices=jax.devices()[:1])
    rng = np.random.default_rng(4)
    # 39 records give 16 samples, two full minibatches: no padding
    series = rng.random((3, 39, C.n_observed)).astype(np.float32)
    datas = [WindowedStream(series[i], WindowPlan(
        1, 39, C.lookback, horizon=C.horizon, period=37.0,
        stream_id=i)).supervised(0) for i in range(3)]
    keys = [jax.random.fold_in(jax.random.PRNGKey(9), i) for i in range(3)]
    fc.train_fleet(datas, keys)
    init = jax.jit(get_model(CFG).init)
    for i, d in enumerate(datas):
        ik, pk = jax.random.split(keys[i])
        want, _ = ref.fit_losses(
            init(ik), pk, d["x"], d["y"], np.ones(16, np.float32),
            epochs=1, batch=8, lr=1e-3, clip=CFG.max_grad_norm,
            quantiles=C.quantiles, dims=DIMS, rate=C.dropout)
        np.testing.assert_allclose(fc.last_losses[i], want, rtol=FWD_TOL)


@pytest.mark.parametrize("path", ["lone_bucket", "batch_model"])
def test_a_lone_stream_fit_matches_a_reference_fit(path):
    """A stream fitted on its own, as the batch model is pretrained and as
    ``train_fleet`` fits a stream whose window buckets apart from the
    rest's, takes the same steps as the reference: 47 records give 24
    samples, padded to a bucket of 32, beside two streams of 16."""
    from repro.core import fleet_forecaster_for, forecaster_for
    from repro.training.compiled import bucket_examples, pad_to_bucket

    rng = np.random.default_rng(6)
    lens = (39, 39, 47) if path == "lone_bucket" else (47,)
    datas = [WindowedStream(rng.random((n, C.n_observed)).astype(
        np.float32), WindowPlan(1, n, C.lookback, horizon=C.horizon,
                                period=37.0, stream_id=i)).supervised(0)
             for i, n in enumerate(lens)]
    keys = [jax.random.fold_in(jax.random.PRNGKey(3), i)
            for i in range(len(lens))]
    if path == "lone_bucket":
        fc = fleet_forecaster_for(CFG, epochs=1, batch_size=8,
                                  devices=jax.devices()[:1])
        params, _ = fc.train_fleet(datas, keys)
        got = fc.last_losses[-1]
    else:
        fc = forecaster_for(CFG, epochs=1, batch_size=8)
        params = [fc.train(datas[0], None, keys[0])[0]]
        got = fc.engine.last_losses
    d = pad_to_bucket(datas[-1], bucket_examples(24, 8))
    ik, pk = jax.random.split(keys[-1])
    want, p_ref = ref.fit_losses(
        jax.jit(get_model(CFG).init)(ik), pk, d["x"], d["y"], d["mask"],
        epochs=1, batch=8, lr=1e-3, clip=CFG.max_grad_norm,
        quantiles=C.quantiles, dims=DIMS, rate=C.dropout)
    np.testing.assert_allclose(np.asarray(got).reshape(-1), want,
                               rtol=FWD_TOL)
    from repro.training.compiled import materialize_params

    p = materialize_params(params[-1])
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p_ref)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=0, atol=GRAD_TOL)


def test_a_bfloat16_forward_fails_the_tolerance():
    p = _params()
    x, _ = _batch()
    want = _ref_forward(p, x)
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    got = _forward(p16, jnp.asarray(x, jnp.bfloat16))
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap > 100 * FWD_TOL, gap


def test_window_samples_at_the_published_lengths():
    k, tau = PUBLISHED.tft.lookback, PUBLISHED.tft.horizon
    series = np.random.default_rng(0).random((1000, 5)).astype(np.float32)
    s = WindowedStream(series, WindowPlan(4, 250, k, horizon=tau,
                                          period=12500.0, stream_id=6,
                                          first_record=1600))
    d = s.supervised(2)
    assert d["x"].shape == (59, k + tau, PUBLISHED.tft.n_channels)
    assert d["y"].shape == (59, tau)
    rec = s.window_records(2)
    np.testing.assert_array_equal(d["x"][3, :k, :5], rec[3:3 + k])
    assert not d["x"][:, k:, :5].any()
    np.testing.assert_array_equal(d["y"][3], rec[3 + k:3 + k + tau, 0])
    np.testing.assert_allclose(
        d["x"][3, :, 5:8], known_inputs(np.arange(2103, 2103 + k + tau),
                                        12500.0))
    assert (d["x"][..., 8] == 6).all()


def test_published_parameter_count_from_eval_shape():
    shapes = jax.eval_shape(get_model(PUBLISHED).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "tft-electricity-8.json")))
    assert n == cfg["parameters"] == 2_972_703
    assert PUBLISHED.tft.hidden == cfg["hidden"] == 160
