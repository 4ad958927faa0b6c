"""A plain reference of the Temporal Fusion Transformer (``models/tft.py``):
forward pass, quantile loss, gradients and a fit, in ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``.  No vmap, no padding, no
packing: one sample's arrays at a time (its id, its observed reals over the
look-back, its known reals over every step; ``lax.map`` takes a batch's
samples one after another), every variable's and every head's map applied
on its own, the recurrences stepped one at a time.

It follows the equations in ``models/tft.py``'s docstring, which are the
paper's (arXiv:1912.09363, Sec. 4), with its departures: LayerNorm epsilon
1e-3 (Keras's default, which the paper's code uses), and the weights a
caller gives it (the tests draw them from the program's seeded init).

Dropout, in training, draws site ``s``'s keep mask over the whole batch
from ``fold_in(rng, s)`` (the batch's shape at that site) and takes the
sample's row of it, so a looped sample sees what it sees in a batch.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.tft import (D_ATTN_OUT, D_CTX_C, D_CTX_E, D_CTX_H,
                              D_CTX_S, D_ENRICH, D_FUTURE_SEL, D_FUTURE_VAR,
                              D_GATE_ATTN, D_GATE_LSTM, D_HEADS, D_PAST_SEL,
                              D_PAST_VAR, D_POS_GRN, D_STATIC_SEL,
                              D_STATIC_VAR, LN_EPS)

HIGHEST = "highest"


class Masks:
    """Sample ``b``'s dropout masks of a batch of ``B`` under key ``rng``
    at ``rate``; all ones without a key."""

    def __init__(self, rng, rate: float, b: int = 0, B: int = 1):
        self.rng, self.rate, self.b, self.B = rng, rate, b, B

    def __call__(self, x, site: int):
        if self.rng is None or self.rate <= 0:
            return x
        keep = jax.random.bernoulli(jax.random.fold_in(self.rng, site),
                                    1.0 - self.rate,
                                    (self.B,) + x.shape)[self.b]
        return jnp.where(keep, x / (1.0 - self.rate), 0.0)


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def glu(g, x):
    return jax.nn.sigmoid(x @ g["w4"] + g["b4"]) * (x @ g["w5"] + g["b5"])


def gated_skip(g, x, skip):
    """``LN(skip + GLU(x))`` (dropout, if any, already on x)."""
    return layer_norm(skip + glu(g, x), g["ln_g"], g["ln_b"])


def grn(g, a, masks, site, c=None):
    """``GRN(a, c) = LN(skip(a) + GLU(W1 ELU(W2 a + W3 c + b2) + b1))``."""
    h = a @ g["w2"] + g["b2"]
    if c is not None:
        h = h + c @ g["w3"]
    h = jax.nn.elu(h) @ g["w1"] + g["b1"]
    skip = a @ g["skip_w"] + g["skip_b"] if "skip_w" in g else a
    return gated_skip(g, masks(h, site), skip)


def _one(g, j):
    """Variable ``j``'s GRN out of a stacked group."""
    return {k: v[j] for k, v in g.items()}


def vsn(sel, var, xi, masks, sites, c=None):
    """xi (..., V, H): ``v = softmax(GRN(flatten(xi), c))``, then
    ``sum_j v_j GRN_j(xi_j)``, each variable's GRN on its own."""
    V = xi.shape[-2]
    v = jax.nn.softmax(grn(sel, xi.reshape(xi.shape[:-2] + (-1,)), masks,
                           sites[0], c), axis=-1)
    # every variable's GRN, with its slice of the stacked site's mask
    h = jnp.stack([grn_var(var, j, xi[..., j, :]) for j in range(V)],
                  axis=-2)
    h = masks(h, sites[1])
    out = 0.0
    for j in range(V):
        gj = _one(var, j)
        out = out + v[..., j, None] * gated_skip(gj, h[..., j, :],
                                                 xi[..., j, :])
    return out


def grn_var(var, j, a):
    """The part of variable ``j``'s GRN before its dropout and gate."""
    gj = _one(var, j)
    return jax.nn.elu(a @ gj["w2"] + gj["b2"]) @ gj["w1"] + gj["b1"]


def lstm(p, xs, h, c):
    """One LSTM layer stepped over xs (T, H) from (h, c); gates in the
    order input, forget, cell, output.  Returns every h and the last
    state."""
    def step(carry, x_t):
        h, c = carry
        z = x_t @ p["kernel"] + h @ p["recurrent"] + p["bias"]
        i, f, g, o = jnp.split(z, 4)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    state, hs = jax.lax.scan(step, (h, c), xs)
    return hs, state


def attention(p, theta, masks):
    """Interpretable multi-head attention over theta (T, H), causal: each
    head its own Q and K, one V shared, dropout on each head's output,
    heads averaged, then ``W_o`` and dropout."""
    T = theta.shape[0]
    n, _, d = p["wq"].shape
    v = theta @ p["wv"]
    causal = np.tril(np.ones((T, T), bool))
    heads = []
    for i in range(n):
        s = (theta @ p["wq"][i]) @ (theta @ p["wk"][i]).T / np.sqrt(d)
        a = jax.nn.softmax(jnp.where(causal, s, -1e9), axis=-1)
        heads.append(a @ v)
    heads = masks(jnp.stack(heads), D_HEADS)
    avg = sum(heads[i] for i in range(n)) / n
    return masks(avg @ p["wo"], D_ATTN_OUT)


def forward(p: Dict, sid: int, obs, known, masks: Optional[Masks] = None):
    """One sample: its id, observed reals (k, n_observed), known reals
    (k + tau, n_known) -> quantile forecasts (tau, n_quantiles)."""
    with jax.default_matmul_precision(HIGHEST):
        masks = masks or Masks(None, 0.0)
        e = p["embed"]
        k = obs.shape[0]
        zeta = vsn(p["static_sel"], p["static_var"], e["ids"][sid][None],
                   masks, (D_STATIC_SEL, D_STATIC_VAR))
        c_s = grn(p["ctx_s"], zeta, masks, D_CTX_S)
        c_e = grn(p["ctx_e"], zeta, masks, D_CTX_E)
        c_h = grn(p["ctx_h"], zeta, masks, D_CTX_H)
        c_c = grn(p["ctx_c"], zeta, masks, D_CTX_C)
        lin = lambda x, w, b: x[..., None] * w + b     # each real its own map
        past_in = jnp.concatenate(
            [lin(obs, e["obs_w"], e["obs_b"]),
             lin(known[:k], e["known_w"], e["known_b"])], axis=1)
        past = vsn(p["past_sel"], p["past_var"], past_in, masks,
                   (D_PAST_SEL, D_PAST_VAR), c_s)
        future = vsn(p["future_sel"], p["future_var"],
                     lin(known[k:], e["known_w"], e["known_b"]), masks,
                     (D_FUTURE_SEL, D_FUTURE_VAR), c_s)
        enc, state = lstm(p["lstm_enc"], past, c_h, c_c)
        dec, _ = lstm(p["lstm_dec"], future, *state)
        phi = jnp.concatenate([enc, dec])
        xi = jnp.concatenate([past, future])
        phi_t = gated_skip(p["gate_lstm"], masks(phi, D_GATE_LSTM), xi)
        theta = grn(p["enrich"], phi_t, masks, D_ENRICH, c_e)
        attn = attention(p["attn"], theta, masks)
        delta = gated_skip(p["gate_attn"], masks(attn, D_GATE_ATTN), theta)
        psi = gated_skip(p["gate_out"],
                         grn(p["pos_grn"], delta, masks, D_POS_GRN), phi_t)
        return psi[k:] @ p["head"]["w"] + p["head"]["b"]


def unpack(x, n_observed: int, n_known: int, lookback: int):
    """A packed sample (k + tau, C) -> (id, observed, known)."""
    return (x[0, n_observed + n_known].astype(jnp.int32),
            x[:lookback, :n_observed], x[:, n_observed:n_observed + n_known])


def forecasts(p: Dict, x, dims, rng=None, rate: float = 0.0):
    """Every sample of x (B, k + tau, C), one after another (``dims`` is
    (n_observed, n_known, lookback)) -> (B, tau, n_quantiles)."""
    B = x.shape[0]
    return jax.lax.map(
        lambda b: forward(p, *unpack(x[b], *dims), Masks(rng, rate, b, B)),
        jnp.arange(B))


def loss(p: Dict, x, y, mask, quantiles: Sequence[float], dims,
         rng=None, rate: float = 0.0):
    """``sum_q mean_{rows, tau} max(q u, (q - 1) u)`` over the rows
    ``mask`` keeps."""
    with jax.default_matmul_precision(HIGHEST):
        q = jnp.asarray(quantiles, jnp.float32)
        u = y[..., None] - forecasts(p, x, dims, rng, rate)
        per = jnp.sum(mask[:, None, None] * jnp.maximum(q * u, (q - 1.0) * u),
                      axis=(0, 1))
        return jnp.sum(per / (jnp.maximum(jnp.sum(mask), 1.0) * y.shape[1]))


@partial(jax.jit, static_argnames=("lr", "clip"))
def adam_step(p, mu, nu, step, g, lr: float, clip: float):
    """AdamW (b1 0.9, b2 0.999, eps 1e-8, no decay) on gradients clipped to
    global norm ``clip``."""
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(l ** 2) for l in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    step = step + 1
    c1, c2 = 1 - 0.9 ** step, 1 - 0.999 ** step

    def one(p, m, v, g):
        g = g * scale
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + 1e-8)), m, v

    out = jax.tree_util.tree_map(one, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), step


def fit_losses(p0: Dict, perm_key, x, y, mask, *, epochs: int, batch: int,
               lr: float, clip: float, quantiles, dims, rate: float,
               n_steps: Optional[int] = None):
    """The step losses of a cold fit from ``p0``: each epoch a permutation
    of the padded window (``split(perm_key, epochs)``), step ``i``'s
    dropout key the ``i``-th of ``split(fold_in(perm_key, 0xD0), steps)``;
    the first ``n_steps`` steps only when given."""
    nb = len(x)
    perms = [np.asarray(jax.random.permutation(k, nb))
             for k in jax.random.split(perm_key, epochs)]
    idx = np.concatenate(perms).reshape(-1, batch)
    keys = jax.random.split(jax.random.fold_in(perm_key, 0xD0), len(idx))
    grad = jax.jit(jax.value_and_grad(loss), static_argnums=(4, 5, 7))
    p = p0
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    step = 0
    out = []
    for i, ib in enumerate(idx[:n_steps]):
        l, g = grad(p, x[ib], y[ib], mask[ib], tuple(quantiles), tuple(dims),
                    keys[i], rate)
        p, mu, nu, step = adam_step(p, mu, nu, step, g, lr=lr, clip=clip)
        out.append(float(l))
    return np.asarray(out), p
