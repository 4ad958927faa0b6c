"""The plain reference of the Temporal Fusion Transformer cell: the model
(Lim et al., arXiv:1912.09363, at the widths ``cfg`` states), its samples,
its training and its serving, written from the description in plain
``jax.numpy``.  It imports nothing of the program.

The model: the stream's id embedded and put through a variable selection
network (VSN) and four GRNs (contexts for selection, enrichment and the
LSTM's two states); past and future VSNs over each real input's own linear
map; an LSTM encoder over the look-back from the state contexts and an
LSTM decoder over the horizon from the encoder's state; a gated skip back
to the VSN outputs; static enrichment; causal interpretable multi-head
attention (per-head Q and K, one shared V, heads averaged); a position-wise
GRN and a last gated skip; a linear map to the quantiles on the horizon.
``GRN(a, c) = LN(skip(a) + GLU(W1 ELU(W2 a + W3 c + b2) + b1))``, ``GLU(x)
= sigmoid(W4 x + b4) * (W5 x + b5)``, dropout before each GLU but the last,
on each head's output and after the attention's output map; LayerNorm
epsilon 1e-3.

What it must share with the program to follow the same trajectory is
semantics, not code: the seeded key chains and the init (as
``chipbench/reference.py`` states them, with each stacked group of weights
drawn at once); the minibatching; the dropout keys (step ``i``'s key is the
``i``-th of ``split(fold_in(perm_key, 0xD0), steps)``, site ``s`` draws its
keep mask over the batch from ``fold_in(key, s)``); AdamW clipped at the
configuration's ``max_grad_norm``; the quantile loss, summed over quantiles
and averaged over real rows and horizon steps; the 0.5 quantile as the
forecast.  ``dtype`` is the precision of parameters and activations, as in
``chipbench/reference.py``.

``variant`` selects a computation for the readings that set the limits:
``fused_gates`` (each LSTM step's two gate products as one product over
``[x_t, h]``: the same sums in another order), or the faults
``state_unchanged`` and ``half_batch``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _path_key

DROPOUT_FOLD = 0xD0
LN_EPS = 1e-3
VARIANTS = ("sound", "fused_gates", "state_unchanged", "half_batch")


# -- samples ----------------------------------------------------------------

def samples(series: np.ndarray, k: int, tau: int, period: float, sid: int,
            first: int) -> Dict[str, np.ndarray]:
    """Encoder-decoder samples of ``series`` (T, F), whose first record is
    record ``first`` of stream ``sid``: each ``k + tau`` consecutive
    records give ``x`` rows of the F channels (zero on the tau future
    rows), the known reals ``t / P``, ``sin 2 pi t / P``, ``cos 2 pi t /
    P`` of each record's index t, and the id; ``y`` is channel 0 over the
    tau future records."""
    T, F = series.shape
    n = T - k - tau + 1
    x = np.zeros((n, k + tau, F + 4), np.float32)
    y = np.zeros((n, tau), np.float32)
    t = first + np.arange(T, dtype=np.float64)
    known = np.stack([t / period, np.sin(2 * np.pi * t / period),
                      np.cos(2 * np.pi * t / period)], axis=-1)
    for j in range(n):
        x[j, :k, :F] = series[j:j + k]
        x[j, :, F:F + 3] = known[j:j + k + tau]
        x[j, :, F + 3] = sid
        y[j] = series[j + k:j + k + tau, 0]
    return {"x": x, "y": y}


def window(live: np.ndarray, w: int, cfg: Dict, traffic: Dict, sid: int
           ) -> Dict[str, np.ndarray]:
    """Window ``w`` of stream ``sid``'s live series: its own records."""
    rpw = int(cfg["records_per_window"])
    return samples(live[w * rpw:(w + 1) * rpw], cfg["lookback"],
                   cfg["horizon"], float(traffic["seasonal_period_records"]),
                   sid, int(cfg["history_records"]) + w * rpw)


def pad(data: Dict[str, np.ndarray], nb: int):
    n = len(data["x"])
    x = np.zeros((nb,) + data["x"].shape[1:], np.float32)
    y = np.zeros((nb,) + data["y"].shape[1:], np.float32)
    m = np.zeros((nb,), np.float32)
    x[:n], y[:n], m[:n] = data["x"], data["y"], 1.0
    return x, y, m


# -- the model --------------------------------------------------------------

def init(key, cfg: Dict, dtype) -> Dict:
    H, nh = cfg["hidden"], cfg["n_heads"]
    d, nq = H // nh, len(cfg["quantiles"])
    nobs, nk, nid = cfg["n_observed"], cfg["n_known"], cfg["streams"]
    npast = nobs + nk

    def w(path, shape, std=None):
        z = jax.random.truncated_normal(_path_key(key, path), -2.0, 2.0,
                                        shape, jnp.float32)
        return (z * (std if std is not None else shape[-2] ** -0.5)
                ).astype(dtype)

    zeros = lambda *s: jnp.zeros(s, dtype)
    ones = lambda *s: jnp.ones(s, dtype)

    def grn(path, din, dout, context=False, n=None):
        lead = () if n is None else (n,)
        g = {"w2": w(f"{path}/w2", lead + (din, H)), "b2": zeros(*lead, H),
             "w1": w(f"{path}/w1", lead + (H, H)), "b1": zeros(*lead, H),
             "w4": w(f"{path}/w4", lead + (H, dout)),
             "b4": zeros(*lead, dout),
             "w5": w(f"{path}/w5", lead + (H, dout)),
             "b5": zeros(*lead, dout),
             "ln_g": ones(*lead, dout), "ln_b": zeros(*lead, dout)}
        if context:
            g["w3"] = w(f"{path}/w3", lead + (H, H))
        if din != dout:
            g["skip_w"] = w(f"{path}/skip_w", lead + (din, dout))
            g["skip_b"] = zeros(*lead, dout)
        return g

    def gate(path):
        return {"w4": w(f"{path}/w4", (H, H)), "b4": zeros(H),
                "w5": w(f"{path}/w5", (H, H)), "b5": zeros(H),
                "ln_g": ones(H), "ln_b": zeros(H)}

    def lstm(path):
        bias = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
        return {"kernel": w(f"{path}/kernel", (H, 4 * H)),
                "recurrent": w(f"{path}/recurrent", (H, 4 * H)),
                "bias": bias.astype(dtype)}

    return {
        "embed": {"ids": w("embed/ids", (nid, H), 1.0),
                  "obs_w": w("embed/obs_w", (nobs, H), 1.0),
                  "obs_b": zeros(nobs, H),
                  "known_w": w("embed/known_w", (nk, H), 1.0),
                  "known_b": zeros(nk, H)},
        "static_sel": grn("static_sel", H, 1),
        "static_var": grn("static_var", H, H, n=1),
        "ctx_s": grn("ctx_s", H, H), "ctx_e": grn("ctx_e", H, H),
        "ctx_h": grn("ctx_h", H, H), "ctx_c": grn("ctx_c", H, H),
        "past_sel": grn("past_sel", npast * H, npast, context=True),
        "past_var": grn("past_var", H, H, n=npast),
        "future_sel": grn("future_sel", nk * H, nk, context=True),
        "future_var": grn("future_var", H, H, n=nk),
        "lstm_enc": lstm("lstm_enc"), "lstm_dec": lstm("lstm_dec"),
        "gate_lstm": gate("gate_lstm"),
        "enrich": grn("enrich", H, H, context=True),
        "attn": {"wq": w("attn/wq", (nh, H, d)), "wk": w("attn/wk", (nh, H, d)),
                 "wv": w("attn/wv", (H, d)), "wo": w("attn/wo", (d, H))},
        "gate_attn": gate("gate_attn"), "pos_grn": grn("pos_grn", H, H),
        "gate_out": gate("gate_out"),
        "head": {"w": w("head/w", (H, nq)), "b": zeros(nq)},
    }


class _Drop:
    def __init__(self, key, rate):
        self.key, self.rate = key, rate

    def __call__(self, x, site):
        if self.key is None:
            return x
        keep = jax.random.bernoulli(jax.random.fold_in(self.key, site),
                                    1.0 - self.rate, x.shape)
        return jnp.where(keep, x / (1.0 - self.rate), jnp.zeros_like(x))


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gate(g, x, skip):
    glu = jax.nn.sigmoid(x @ g["w4"] + g["b4"]) * (x @ g["w5"] + g["b5"])
    return _ln(skip + glu, g["ln_g"], g["ln_b"])


def _grn(g, a, drop, site, c=None):
    h = a @ g["w2"] + g["b2"]
    if c is not None:
        h = h + c @ g["w3"]
    h = jax.nn.elu(h) @ g["w1"] + g["b1"]
    skip = a @ g["skip_w"] + g["skip_b"] if "skip_w" in g else a
    return _gate(g, drop(h, site), skip)


def _var_grns(g, a, drop, site):
    mm = lambda x, w: jnp.einsum("...vi,vio->...vo", x, w)
    h = drop(mm(jax.nn.elu(mm(a, g["w2"]) + g["b2"]), g["w1"]) + g["b1"],
             site)
    glu = jax.nn.sigmoid(mm(h, g["w4"]) + g["b4"]) * (mm(h, g["w5"])
                                                      + g["b5"])
    return _ln(a + glu, g["ln_g"], g["ln_b"])


def _vsn(sel, var, xi, drop, sites, c=None):
    flat = xi.reshape(xi.shape[:-2] + (-1,))
    v = jax.nn.softmax(_grn(sel, flat, drop, sites[0], c), axis=-1)
    return jnp.sum(v[..., None] * _var_grns(var, xi, drop, sites[1]),
                   axis=-2)


def _lstm(p, xs, h, c, fused):
    """xs (T, B, H) -> every h (T, B, H) and the last state."""
    w = jnp.concatenate([p["kernel"], p["recurrent"]], axis=0)

    def step(carry, x_t):
        h, c = carry
        if fused:
            z = jnp.concatenate([x_t, h], axis=-1) @ w + p["bias"]
        else:
            z = x_t @ p["kernel"] + h @ p["recurrent"] + p["bias"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h, c), hs = jax.lax.scan(step, (h, c), xs)
    return hs, (h, c)


def forward(p: Dict, x, cfg: Tuple, key=None, fused: bool = False):
    """x (B, k + tau, C) -> quantile forecasts (B, tau, Q); dropout with a
    key.  ``cfg``: (n_observed, n_known, lookback, dropout)."""
    nobs, nk, k, rate = cfg
    drop = _Drop(key, rate)
    e = p["embed"]
    x = x.astype(e["obs_w"].dtype)
    static = e["ids"][x[:, 0, nobs + nk].astype(jnp.int32)][:, None, :]
    zeta = _vsn(p["static_sel"], p["static_var"], static, drop, (0, 1))
    c_s = _grn(p["ctx_s"], zeta, drop, 2)[:, None, :]
    c_e = _grn(p["ctx_e"], zeta, drop, 3)[:, None, :]
    c_h = _grn(p["ctx_h"], zeta, drop, 4)
    c_c = _grn(p["ctx_c"], zeta, drop, 5)
    obs = x[:, :k, :nobs, None] * e["obs_w"] + e["obs_b"]
    known = x[:, :, nobs:nobs + nk, None] * e["known_w"] + e["known_b"]
    past = _vsn(p["past_sel"], p["past_var"],
                jnp.concatenate([obs, known[:, :k]], axis=2), drop, (6, 7),
                c_s)
    future = _vsn(p["future_sel"], p["future_var"], known[:, k:], drop,
                  (8, 9), c_s)
    enc, state = _lstm(p["lstm_enc"], past.transpose(1, 0, 2), c_h, c_c,
                       fused)
    dec, _ = _lstm(p["lstm_dec"], future.transpose(1, 0, 2), *state, fused)
    phi = jnp.concatenate([enc, dec], axis=0).transpose(1, 0, 2)
    phi_t = _gate(p["gate_lstm"], drop(phi, 10),
                  jnp.concatenate([past, future], axis=1))
    theta = _grn(p["enrich"], phi_t, drop, 11, c_e)
    a = p["attn"]
    T, d = theta.shape[1], a["wv"].shape[-1]
    q = jnp.einsum("bth,nhd->bntd", theta, a["wq"])
    kk = jnp.einsum("bth,nhd->bntd", theta, a["wk"])
    s = jnp.einsum("bntd,bnsd->bnts", q, kk) / jnp.sqrt(
        jnp.asarray(d, theta.dtype))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                  jnp.asarray(-1e9, s.dtype))
    heads = drop(jnp.einsum("bnts,bsd->bntd", jax.nn.softmax(s, axis=-1),
                            theta @ a["wv"]), 12)
    attn = drop(jnp.mean(heads, axis=1) @ a["wo"], 13)
    delta = _gate(p["gate_attn"], drop(attn, 14), theta)
    psi = _gate(p["gate_out"], _grn(p["pos_grn"], delta, drop, 15), phi_t)
    return psi[:, k:] @ p["head"]["w"] + p["head"]["b"]


def loss(p, x, y, mask, cfg, quantiles, key=None, fused=False):
    pred = forward(p, x, cfg, key, fused)
    q = jnp.asarray(quantiles, jnp.float32)
    u = (y[..., None] - pred).astype(jnp.float32)
    m = mask.astype(jnp.float32)[:, None, None]
    per = jnp.sum(jnp.maximum(q * u, (q - 1.0) * u) * m, axis=(0, 1))
    return jnp.sum(per / (jnp.maximum(jnp.sum(m), 1.0) * y.shape[1]))


def adam_step(p, mu, nu, step, g, lr, clip):
    """AdamW (b1 0.9, b2 0.999, eps 1e-8, no decay) on gradients clipped to
    global norm ``clip``; float32 moments and update."""
    f32 = jnp.float32
    gnorm = jnp.sqrt(sum(jnp.sum(l.astype(f32) ** 2)
                         for l in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    step = step + 1
    c1 = 1 - 0.9 ** step.astype(f32)
    c2 = 1 - 0.999 ** step.astype(f32)

    def one(p, m, v, g):
        g = g.astype(f32) * scale
        m = 0.9 * m + (1 - 0.9) * g
        v = 0.999 * v + (1 - 0.999) * g * g
        new = p.astype(f32) - lr * ((m / c1) / (jnp.sqrt(v / c2) + 1e-8))
        return new.astype(p.dtype), m, v

    out = jax.tree_util.tree_map(one, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), step


def fit(init_key, perm_key, x, y, mask, *, cfg, epochs, batch, dtype,
        variant):
    """One cold-start fit; returns (params, the loss of every step)."""
    nb = x.shape[0]
    p = init(init_key, cfg, dtype)
    zeros = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), p)
    perms = jax.vmap(lambda k: jax.random.permutation(k, nb))(
        jax.random.split(perm_key, epochs))
    idx = perms.reshape(-1, batch)
    keys = jax.random.split(jax.random.fold_in(perm_key, DROPOUT_FOLD),
                            idx.shape[0])
    dims = (cfg["n_observed"], cfg["n_known"], cfg["lookback"],
            cfg["dropout"])
    half = (jnp.arange(batch) < batch // 2).astype(mask.dtype)
    fused = variant == "fused_gates"

    def body(carry, step):
        ib, key = step
        p, mu, nu, n = carry
        m = mask[ib] * half if variant == "half_batch" else mask[ib]
        l, g = jax.value_and_grad(loss)(p, x[ib], y[ib], m, dims,
                                        tuple(cfg["quantiles"]), key, fused)
        q, mu, nu, n = adam_step(p, mu, nu, n, g, float(cfg["lr"]),
                                 float(cfg["max_grad_norm"]))
        if variant == "state_unchanged":
            q = p
        return (q, mu, nu, n), l

    (p, _, _, _), losses = jax.lax.scan(
        body, (p, zeros, zeros, jnp.zeros((), jnp.int32)), (idx, keys))
    return p, losses


@partial(jax.jit, static_argnames=("cfg_items", "epochs", "batch", "dtype",
                                   "variant"))
def fleet_fit(keys, x, y, mask, *, cfg_items, epochs, batch, dtype,
              variant="sound"):
    """Every stream's fit, vmapped: keys (S, 2) are each stream's window
    key, split into the init and the permutation key."""
    cfg = dict(cfg_items)

    def one(k, x, y, m):
        ik, pk = jax.random.split(k)
        return fit(ik, pk, x, y, m, cfg=cfg, epochs=epochs, batch=batch,
                   dtype=dtype, variant=variant)

    return jax.vmap(one)(keys, x, y, mask)


@partial(jax.jit, static_argnames=("cfg_items", "variant"))
def fleet_predict(params, x, *, cfg_items, variant="sound"):
    """Each stream's 0.5-quantile forecasts, (S, B, tau)."""
    cfg = dict(cfg_items)
    dims = (cfg["n_observed"], cfg["n_known"], cfg["lookback"], 0.0)
    mid = list(cfg["quantiles"]).index(0.5)
    return jax.vmap(lambda p, x: forward(
        p, x, dims, fused=variant == "fused_gates")[..., mid])(params, x)


def cfg_items(cfg: Dict) -> Tuple:
    keys = ("hidden", "n_heads", "n_observed", "n_known", "lookback",
            "streams", "dropout", "lr", "max_grad_norm")
    return tuple((k, cfg[k]) for k in keys) + (
        ("quantiles", tuple(cfg["quantiles"])),)

