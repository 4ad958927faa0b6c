"""Temporal Fusion Transformer (Lim, Arik, Loeff and Pfister, "Temporal
Fusion Transformers for Interpretable Multi-horizon Time Series
Forecasting", arXiv:1912.09363) as a fleet forecaster.

A sample is packed into ``x`` of shape (B, k + tau, C): the observed reals
(column 0 the target) in the first ``n_observed`` columns, zero on the
``tau`` future rows; the known reals in the next ``n_known``; the stream's
id in the last.  ``y`` is (B, tau), the target over the horizon.

Notation: ``LN`` is LayerNorm; ``GLU(x) = sigmoid(W4 x + b4) * (W5 x +
b5)``, with dropout on x first; ``GRN(a, c) = LN(skip(a) + GLU(W1 ELU(W2 a
+ W3 c + b2) + b1))``, ``skip`` the identity or a linear map where the
widths differ, ``W3`` (no bias) only with a context c.  The blocks:

* static covariate encoders: the id's embedding through a variable
  selection network (VSN), then four GRNs give the contexts ``c_s``
  (selection), ``c_e`` (enrichment), ``c_h`` and ``c_c`` (LSTM state);
* past and future VSNs, each step: ``v = softmax(GRN(flatten(xi), c_s))``,
  ``xi~ = sum_j v_j GRN_j(xi_j)``, every real input first through its own
  linear map to the hidden width;
* an LSTM encoder over the k past steps from ``(c_h, c_c)`` and an LSTM
  decoder over the tau future steps from the encoder's final state
  (``lstm.run_cells``, the paper's forecaster's ``cell_step``);
* gated skip ``phi~ = LN(xi~ + GLU(phi))``; static enrichment ``theta =
  GRN(phi~, c_e)``;
* interpretable multi-head attention with a causal mask: per-head Q and K
  maps, one V map shared by the heads, dropout on each head's output, the
  heads averaged, one linear map back to the hidden width, dropout;
  ``delta = LN(theta + GLU(attn))``;
* a position-wise GRN, then ``psi~ = LN(phi~ + GLU(GRN(delta)))`` (no
  dropout in this last GLU); a linear map to the quantiles on the tau
  decoder positions.

Dropout runs only when the batch carries a key (``batch["rng"]``): site
``s`` draws its keep mask over the whole batch from ``fold_in(rng, s)``.
Departures from the paper's code: LayerNorm epsilon 1e-3 (Keras's default,
which that code uses); weights drawn as the rest of the zoo draws them
(truncated normal at fan-in scale, folded from the weight's path).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lstm, nn

Params = Dict[str, Any]

LN_EPS = 1e-3

# dropout sites, in the order the forward pass reaches them
(D_STATIC_SEL, D_STATIC_VAR, D_CTX_S, D_CTX_E, D_CTX_H, D_CTX_C, D_PAST_SEL,
 D_PAST_VAR, D_FUTURE_SEL, D_FUTURE_VAR, D_GATE_LSTM, D_ENRICH, D_HEADS,
 D_ATTN_OUT, D_GATE_ATTN, D_POS_GRN) = range(16)


def _grn_init(key, path: str, din: int, dh: int, dout: int, dt, *,
              context: bool = False, n: Optional[int] = None) -> Params:
    """One GRN's weights; with ``n``, ``n`` GRNs stacked on a leading axis
    (one per input variable)."""
    def w(name, a, b, scale=None):
        if n is None:
            return nn.dense_init(key, f"{path}/{name}", a, b, dt, scale)
        return nn.stacked_dense_init(key, f"{path}/{name}", n, a, b, dt,
                                     scale)

    lead = () if n is None else (n,)
    g = {"w2": w("w2", din, dh), "b2": nn.zeros(lead + (dh,), dt),
         "w1": w("w1", dh, dh), "b1": nn.zeros(lead + (dh,), dt),
         "w4": w("w4", dh, dout), "b4": nn.zeros(lead + (dout,), dt),
         "w5": w("w5", dh, dout), "b5": nn.zeros(lead + (dout,), dt),
         "ln_g": nn.ones(lead + (dout,), dt),
         "ln_b": nn.zeros(lead + (dout,), dt)}
    if context:
        g["w3"] = w("w3", dh, dh)
    if din != dout:
        g["skip_w"] = w("skip_w", din, dout)
        g["skip_b"] = nn.zeros(lead + (dout,), dt)
    return g


def _gate_init(key, path: str, H: int, dt) -> Params:
    """A gated skip connection: GLU and LayerNorm, width H."""
    return {"w4": nn.dense_init(key, f"{path}/w4", H, H, dt),
            "b4": nn.zeros((H,), dt),
            "w5": nn.dense_init(key, f"{path}/w5", H, H, dt),
            "b5": nn.zeros((H,), dt),
            "ln_g": nn.ones((H,), dt), "ln_b": nn.zeros((H,), dt)}


def _lstm_init(key, path: str, H: int, dt) -> Params:
    b = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
    return {"kernel": nn.dense_init(key, f"{path}/kernel", H, 4 * H, dt),
            "recurrent": nn.dense_init(key, f"{path}/recurrent", H, 4 * H,
                                       dt, scale=H ** -0.5),
            "bias": b.astype(dt)}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    c = cfg.tft
    dt = jnp.dtype(cfg.param_dtype)
    H, d, nh = c.hidden, c.head_dim, c.n_heads
    n_past = c.n_observed + c.n_known
    return {
        "embed": {
            "ids": nn.dense_init(key, "embed/ids", c.n_ids, H, dt,
                                 scale=1.0),
            "obs_w": nn.dense_init(key, "embed/obs_w", c.n_observed, H, dt,
                                   scale=1.0),
            "obs_b": nn.zeros((c.n_observed, H), dt),
            "known_w": nn.dense_init(key, "embed/known_w", c.n_known, H, dt,
                                     scale=1.0),
            "known_b": nn.zeros((c.n_known, H), dt),
        },
        "static_sel": _grn_init(key, "static_sel", H, H, 1, dt),
        "static_var": _grn_init(key, "static_var", H, H, H, dt, n=1),
        "ctx_s": _grn_init(key, "ctx_s", H, H, H, dt),
        "ctx_e": _grn_init(key, "ctx_e", H, H, H, dt),
        "ctx_h": _grn_init(key, "ctx_h", H, H, H, dt),
        "ctx_c": _grn_init(key, "ctx_c", H, H, H, dt),
        "past_sel": _grn_init(key, "past_sel", n_past * H, H, n_past, dt,
                              context=True),
        "past_var": _grn_init(key, "past_var", H, H, H, dt, n=n_past),
        "future_sel": _grn_init(key, "future_sel", c.n_known * H, H,
                                c.n_known, dt, context=True),
        "future_var": _grn_init(key, "future_var", H, H, H, dt,
                                n=c.n_known),
        "lstm_enc": _lstm_init(key, "lstm_enc", H, dt),
        "lstm_dec": _lstm_init(key, "lstm_dec", H, dt),
        "gate_lstm": _gate_init(key, "gate_lstm", H, dt),
        "enrich": _grn_init(key, "enrich", H, H, H, dt, context=True),
        "attn": {
            "wq": nn.stacked_dense_init(key, "attn/wq", nh, H, d, dt),
            "wk": nn.stacked_dense_init(key, "attn/wk", nh, H, d, dt),
            "wv": nn.dense_init(key, "attn/wv", H, d, dt),
            "wo": nn.dense_init(key, "attn/wo", d, H, dt),
        },
        "gate_attn": _gate_init(key, "gate_attn", H, dt),
        "pos_grn": _grn_init(key, "pos_grn", H, H, H, dt),
        "gate_out": _gate_init(key, "gate_out", H, dt),
        "head": {"w": nn.dense_init(key, "head/w", H, len(c.quantiles), dt),
                 "b": nn.zeros((len(c.quantiles),), dt)},
    }


class _Dropout:
    """Inverted dropout at ``rate``, site ``s`` keyed ``fold_in(rng, s)``;
    the identity without a key."""

    def __init__(self, rng: Optional[jax.Array], rate: float):
        self.rng, self.rate = rng, rate

    def __call__(self, x: jax.Array, site: int) -> jax.Array:
        if self.rng is None or self.rate <= 0:
            return x
        keep = jax.random.bernoulli(jax.random.fold_in(self.rng, site),
                                    1.0 - self.rate, x.shape)
        return jnp.where(keep, x / (1.0 - self.rate), jnp.zeros_like(x))


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _glu(g, x):
    return jax.nn.sigmoid(x @ g["w4"] + g["b4"]) * (x @ g["w5"] + g["b5"])


def _gate(g, x, skip, drop, site=None):
    """``LN(skip + GLU(x))``, dropout on x at ``site`` (None: none)."""
    if site is not None:
        x = drop(x, site)
    return _ln(skip + _glu(g, x), g["ln_g"], g["ln_b"])


def grn(g: Params, a: jax.Array, drop, site: int,
        ctx: Optional[jax.Array] = None) -> jax.Array:
    """``GRN(a, c)`` over a's last axis; ``ctx`` broadcasts against a."""
    h = a @ g["w2"] + g["b2"]
    if ctx is not None:
        h = h + ctx @ g["w3"]
    h = jax.nn.elu(h) @ g["w1"] + g["b1"]
    skip = a @ g["skip_w"] + g["skip_b"] if "skip_w" in g else a
    return _gate(g, h, skip, drop, site)


def _stacked_grn(g: Params, a: jax.Array, drop, site: int) -> jax.Array:
    """One GRN per variable: a (..., V, H) with weights stacked over V."""
    mm = lambda x, w: jnp.einsum("...vi,vio->...vo", x, w)
    h = jax.nn.elu(mm(a, g["w2"]) + g["b2"])
    h = drop(mm(h, g["w1"]) + g["b1"], site)
    glu = jax.nn.sigmoid(mm(h, g["w4"]) + g["b4"]) * (mm(h, g["w5"])
                                                      + g["b5"])
    return _ln(a + glu, g["ln_g"], g["ln_b"])


def _vsn(sel, var, xi, drop, sites, ctx=None):
    """Variable selection over xi (..., V, H): weights from the GRN of the
    flattened inputs, a GRN per variable, the weighted sum."""
    flat = xi.reshape(xi.shape[:-2] + (-1,))
    v = jax.nn.softmax(grn(sel, flat, drop, sites[0], ctx), axis=-1)
    return jnp.sum(v[..., None] * _stacked_grn(var, xi, drop, sites[1]),
                   axis=-2)


def _attention(p, theta, drop):
    """Interpretable multi-head attention over theta (B, T, H), causal."""
    d = p["wv"].shape[-1]
    T = theta.shape[1]
    q = jnp.einsum("bth,nhd->bntd", theta, p["wq"])
    k = jnp.einsum("bth,nhd->bntd", theta, p["wk"])
    v = theta @ p["wv"]
    s = jnp.einsum("bntd,bnsd->bnts", q, k) / jnp.sqrt(
        jnp.asarray(d, theta.dtype))
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, jnp.asarray(-1e9, s.dtype)),
                       axis=-1)
    heads = drop(jnp.einsum("bnts,bsd->bntd", a, v), D_HEADS)
    return drop(jnp.mean(heads, axis=1) @ p["wo"], D_ATTN_OUT)


def forward(cfg: ModelConfig, p: Params, x: jax.Array,
            rng: Optional[jax.Array] = None) -> jax.Array:
    """x: (B, k + tau, C) -> quantile forecasts (B, tau, n_quantiles);
    dropout only with ``rng``."""
    c = cfg.tft
    k, nobs, nk = c.lookback, c.n_observed, c.n_known
    drop = _Dropout(rng, c.dropout)
    x = x.astype(p["embed"]["obs_w"].dtype)
    e = p["embed"]
    with jax.named_scope("tft/vsn"):
        ids = x[:, 0, nobs + nk].astype(jnp.int32)
        static = e["ids"][ids][:, None, :]                      # (B, 1, H)
        zeta = _vsn(p["static_sel"], p["static_var"], static, drop,
                    (D_STATIC_SEL, D_STATIC_VAR))               # (B, H)
        c_s = grn(p["ctx_s"], zeta, drop, D_CTX_S)[:, None, :]
        c_e = grn(p["ctx_e"], zeta, drop, D_CTX_E)[:, None, :]
        c_h = grn(p["ctx_h"], zeta, drop, D_CTX_H)
        c_c = grn(p["ctx_c"], zeta, drop, D_CTX_C)
        obs = x[:, :k, :nobs, None] * e["obs_w"] + e["obs_b"]
        known = x[:, :, nobs:nobs + nk, None] * e["known_w"] + e["known_b"]
        past = _vsn(p["past_sel"], p["past_var"],
                    jnp.concatenate([obs, known[:, :k]], axis=2), drop,
                    (D_PAST_SEL, D_PAST_VAR), c_s)              # (B, k, H)
        future = _vsn(p["future_sel"], p["future_var"], known[:, k:], drop,
                      (D_FUTURE_SEL, D_FUTURE_VAR), c_s)        # (B, tau, H)
    unroll = lambda n: n if n <= lstm.UNROLL_MAX_LAG else 1
    with jax.named_scope("tft/lstm_enc"):
        state, enc = lstm.run_cells(p["lstm_enc"], past.transpose(1, 0, 2),
                                    c_h, c_c, sequence=True,
                                    unroll=unroll(k))
    with jax.named_scope("tft/lstm_dec"):
        _, dec = lstm.run_cells(p["lstm_dec"], future.transpose(1, 0, 2),
                                *state, sequence=True,
                                unroll=unroll(c.horizon))
    with jax.named_scope("tft/grn_post"):
        phi = jnp.concatenate([enc, dec], axis=0).transpose(1, 0, 2)
        xi = jnp.concatenate([past, future], axis=1)
        phi_t = _gate(p["gate_lstm"], phi, xi, drop, D_GATE_LSTM)
        theta = grn(p["enrich"], phi_t, drop, D_ENRICH, c_e)
    with jax.named_scope("tft/attention"):
        attn = _attention(p["attn"], theta, drop)
    with jax.named_scope("tft/grn_post"):
        delta = _gate(p["gate_attn"], attn, theta, drop, D_GATE_ATTN)
        psi = _gate(p["gate_out"], grn(p["pos_grn"], delta, drop, D_POS_GRN),
                    phi_t, drop)
        return psi[:, k:] @ p["head"]["w"] + p["head"]["b"]


def quantile_loss(cfg: ModelConfig, pred: jax.Array, y: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """``sum_q mean_{rows, tau} max(q u, (q - 1) u)``, ``u = y - pred_q``,
    over the rows ``mask`` keeps."""
    q = jnp.asarray(cfg.tft.quantiles, jnp.float32)
    u = (y[..., None] - pred).astype(jnp.float32)
    ql = jnp.maximum(q * u, (q - 1.0) * u)                     # (B, tau, Q)
    if mask is None:
        return jnp.sum(jnp.mean(ql, axis=(0, 1)))
    m = mask.astype(jnp.float32)[:, None, None]
    denom = jnp.maximum(jnp.sum(m), 1.0) * ql.shape[1]
    return jnp.sum(jnp.sum(ql * m, axis=(0, 1)) / denom)


def loss_fn(cfg: ModelConfig, p: Params, batch: Dict[str, jax.Array]):
    """The quantile loss; batch {"x", "y": (B, tau)} with an optional
    per-example ``mask`` (as ``lstm.loss_fn``) and an optional dropout key
    ``rng`` (training)."""
    pred = forward(cfg, p, batch["x"], batch.get("rng"))
    loss = quantile_loss(cfg, pred, batch["y"], batch.get("mask"))
    return loss, {"quantile_loss": loss}


def predict(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """The 0.5 quantile over the horizon, (B, tau)."""
    return forward(cfg, p, x)[..., cfg.tft.quantiles.index(0.5)]
