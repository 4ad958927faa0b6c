"""Faults planted in the plain reference where it stands in for the
program: what the output check has to catch.  ``readings.py`` reads each
fault's numbers on the chip to set the limits' upper ends; the benchmark's
own runs never import this file.

* ``state_unchanged``: every optimizer step returns the parameters it was
  given (the losses are those of the initial model on each minibatch).
* ``half_batch``: each minibatch's second half is left out of the loss, the
  mean taken over the rest.
* ``answer_altered``: every forecast the fleet produces is off by
  ``ALTER`` (on the [0, 1] scale of the data).
* ``exchange_left_out`` (planted in the program itself, on several
  chips): the last ``lost`` chips' shares of each stacked result never
  reach the host, which reads zeros for those streams.

It also holds a sound computation with other rounding, which a check must
let pass: ``fused_gates``, the reference with each LSTM step's two gate
products taken as one product over ``[x_t, h]`` (the same sums in another
order, at the configuration's precision).
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import reference

ALTER = 0.01
FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def _forward_fused(p, x):
    lp = p["lstm"]
    w = jnp.concatenate([lp["kernel"], lp["recurrent"]], axis=0)
    H = lp["recurrent"].shape[0]
    x = x.astype(w.dtype)
    h0 = jnp.zeros((x.shape[0], H), x.dtype)

    def step(carry, x_t):
        h, c = carry
        z = jnp.concatenate([x_t, h], axis=-1) @ w + lp["bias"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    (h, _), _ = jax.lax.scan(step, (h0, h0), x.transpose(1, 0, 2))
    d = jax.nn.relu(h @ p["dense"]["dense_w"] + p["dense"]["dense_b"])
    return d @ p["head"]["head_w"] + p["head"]["head_b"]


def _loss_fused(p, x, y, mask):
    err = _forward_fused(p, x) - y.astype(p["head"]["head_b"].dtype)
    m = mask.astype(err.dtype)[:, None]
    return jnp.sum(err * err * m) / jnp.maximum(jnp.sum(m), 1.0)


def _fit(init_key, perm_key, x, y, mask, *, cfg, epochs, batch, dtype, fault):
    nb = x.shape[0]
    p = reference.init(init_key, cfg, dtype)
    zeros = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), p)
    perms = jax.vmap(lambda k: jax.random.permutation(k, nb))(
        jax.random.split(perm_key, epochs))
    idx = perms.reshape(-1, batch)
    half = (jnp.arange(batch) < batch // 2).astype(mask.dtype)

    def body(carry, ib):
        p, mu, nu, step = carry
        m = mask[ib] * half if fault == "half_batch" else mask[ib]
        lf = _loss_fused if fault == "fused_gates" else reference.loss
        l, g = jax.value_and_grad(lf)(p, x[ib], y[ib], m)
        q, mu, nu, step = reference.adam_step(p, mu, nu, step, g,
                                              float(cfg["lr"]))
        if fault == "state_unchanged":
            q = p
        return (q, mu, nu, step), l

    (p, _, _, _), losses = jax.lax.scan(
        body, (p, zeros, zeros, jnp.zeros((), jnp.int32)), idx)
    return p, losses


@partial(jax.jit, static_argnames=("cfg_items", "epochs", "batch", "dtype",
                                   "fault"))
def fleet_fit(keys, x, y, mask, *, cfg_items, epochs, batch, dtype, fault):
    cfg = dict(cfg_items)

    def one(k, x, y, m):
        ik, pk = jax.random.split(k)
        return _fit(ik, pk, x, y, m, cfg=cfg, epochs=epochs, batch=batch,
                    dtype=dtype, fault=fault)

    return jax.vmap(one)(keys, x, y, mask)


def fit_for(fault: str):
    """The fleet fit that stands in for the program under ``fault`` (or
    the sound variant ``fused_gates``)."""
    if fault in ("state_unchanged", "half_batch", "fused_gates"):
        return partial(fleet_fit, fault=fault)
    return reference.fleet_fit


def alter(outputs: dict) -> dict:
    """``answer_altered`` applied to the reference's outputs."""
    infer = {k: v + ALTER for k, v in outputs.get("infer", {}).items()}
    answers = {u: [a + ALTER for a in v]
               for u, v in outputs.get("answers", {}).items()}
    return dict(outputs, infer=infer, answers=answers)


@contextmanager
def exchange_left_out(lost: int, chips: int = 4):
    """Within the block, the program loses the last ``lost`` of ``chips``
    chips' shares of every stacked result on its way to the host (the
    synced trees and the fleet's forecasts): those streams read zeros."""
    import numpy as np

    from repro.training import compiled

    def cut(a):
        a = np.array(a)
        a[a.shape[0] * (chips - lost) // chips:] = 0
        return a

    orig_host = compiled._FleetStack.host
    orig_predict = compiled.FleetForecaster.predict_fleet

    def host(self):
        return jax.tree_util.tree_map(cut, orig_host(self))

    def predict(self, params, xs):
        out = orig_predict(self, params, xs)
        keep = len(out) * (chips - lost) // chips
        return [p if j < keep else np.zeros_like(p)
                for j, p in enumerate(out)]

    compiled._FleetStack.host = host
    compiled.FleetForecaster.predict_fleet = predict
    try:
        yield
    finally:
        compiled._FleetStack.host = orig_host
        compiled.FleetForecaster.predict_fleet = orig_predict
