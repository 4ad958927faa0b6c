"""The paper's forecaster (Sec. 6.1.2 / Fig. 6): LSTM(40) -> Dense(10, ReLU)
-> Dense(1), lag n=5, 5 input features; 10,981 parameters.

This is the batch-layer and speed-layer model of the faithful reproduction.
``cell_step`` is the math the Pallas ``lstm_cell`` kernel fuses on TPU; the
pure-jnp path here doubles as its oracle.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn

Params = Dict[str, Any]

# Lags up to this unroll fully (no loop, and no per-step rewrite of a stacked
# residual buffer in the gradient); longer ones keep the rolled scan, whose
# compile time does not grow with the lag.
UNROLL_MAX_LAG = 16


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    c = cfg.lstm
    dt = jnp.dtype(cfg.param_dtype)
    H, F = c.hidden, c.n_features
    return {
        "lstm": {
            "kernel": nn.dense_init(key, "lstm/kernel", F, 4 * H, dt),
            "recurrent": nn.dense_init(key, "lstm/recurrent", H, 4 * H, dt,
                                       scale=H**-0.5),
            "bias": _forget_bias(H, dt),
        },
        "dense": {
            "dense_w": nn.dense_init(key, "dense/dense_w", H, c.dense, dt),
            "dense_b": nn.zeros((c.dense,), dt),
        },
        "head": {
            "head_w": nn.dense_init(key, "head/head_w", c.dense, c.out_dim, dt),
            "head_b": nn.zeros((c.out_dim,), dt),
        },
    }


def _forget_bias(H: int, dt) -> jax.Array:
    """Keras-style unit forget-gate bias (gate order i, f, g, o)."""
    b = jnp.zeros((4 * H,), jnp.float32)
    return b.at[H : 2 * H].set(1.0).astype(dt)


def cell_step(p: Params, x_t: jax.Array, h: jax.Array, c: jax.Array):
    """One LSTM cell step.  x_t: (B, F); h, c: (B, H)."""
    H = h.shape[-1]
    z = x_t @ p["kernel"] + h @ p["recurrent"] + p["bias"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new


def run_cells(p: Params, xs: jax.Array, h0: jax.Array, c0: jax.Array, *,
              sequence: bool = False, unroll: int = 1):
    """``cell_step`` over time-major inputs ``xs`` (T, B, F) from the state
    ``(h0, c0)``.  Returns the final ``(h, c)`` and, with ``sequence``, every
    step's hidden state (T, B, H); else None in its place."""

    def step(carry, x_t):
        h, cc = cell_step(p, x_t, *carry)
        return (h, cc), (h if sequence else None)

    return jax.lax.scan(step, (h0, c0), xs, unroll=unroll)


def _has_qtensor(p: Params) -> bool:
    from repro.serving.quantize import QTensor

    return any(isinstance(leaf, QTensor) for leaf in
               jax.tree_util.tree_leaves(
                   p, is_leaf=lambda x: isinstance(x, QTensor)))


def _mm(x: jax.Array, w) -> jax.Array:
    """x @ w, dispatching the fused int8 dequant-matmul kernel when ``w`` is
    a quantized ``QTensor`` leaf (float leaves multiply as usual, so a
    partially-quantized tree — tiny heads kept in float — still works)."""
    from repro.serving.quantize import QTensor

    if isinstance(w, QTensor):
        from repro.kernels.int8_matmul.ops import qmatmul

        return qmatmul(x, w)
    return x @ w


def _forward_int8(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """Edge inference on an int8-synced speed model (the TFLite-on-Pi
    analog): every quantized weight matrix dispatches ``qmatmul`` — the
    whole-sequence input projection in one kernel call, the recurrent
    projection once per step inside the scan — and activations stay float
    (weight-only quantization, what the accuracy test pins)."""
    c = cfg.lstm
    B, T, _ = x.shape
    lp = p["lstm"]
    zx = _mm(x.reshape(B * T, -1), lp["kernel"]).reshape(B, T, 4 * c.hidden)
    h0 = jnp.zeros((B, c.hidden), x.dtype)
    c0 = jnp.zeros((B, c.hidden), x.dtype)
    bias = lp["bias"]

    def step(carry, z_t):
        h, cc = carry
        z = z_t + _mm(h, lp["recurrent"]) + bias
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * cc + i * g
        h_new = o * jnp.tanh(c_new)
        return (h_new, c_new), None

    (h, _), _ = jax.lax.scan(step, (h0, c0), zx.transpose(1, 0, 2))
    d = jax.nn.relu(_mm(h, p["dense"]["dense_w"]) + p["dense"]["dense_b"])
    return _mm(d, p["head"]["head_w"]) + p["head"]["head_b"]


def forward(cfg: ModelConfig, p: Params, x: jax.Array,
            use_pallas: Optional[bool] = None) -> jax.Array:
    """x: (B, lag, F) -> prediction (B, out_dim).

    A params tree containing ``QTensor`` leaves (an int8-synced speed model)
    routes to the quantized inference path regardless of ``use_pallas``."""
    c = cfg.lstm
    B = x.shape[0]
    if _has_qtensor(p):
        return _forward_int8(cfg, p, x)
    use_pallas = cfg.use_pallas if use_pallas is None else use_pallas
    if use_pallas:
        from repro.kernels.lstm_cell import ops as lstm_ops

        h = lstm_ops.lstm_sequence(
            x, p["lstm"]["kernel"], p["lstm"]["recurrent"], p["lstm"]["bias"]
        )
    else:
        h0 = jnp.zeros((B, c.hidden), x.dtype)
        c0 = jnp.zeros((B, c.hidden), x.dtype)
        T = x.shape[1]
        (h, _), _ = run_cells(p["lstm"], x.transpose(1, 0, 2), h0, c0,
                              unroll=T if T <= UNROLL_MAX_LAG else 1)
    d = jax.nn.relu(h @ p["dense"]["dense_w"] + p["dense"]["dense_b"])
    return d @ p["head"]["head_w"] + p["head"]["head_b"]


def loss_fn(cfg: ModelConfig, p: Params, batch: Dict[str, jax.Array]):
    """MSE regression loss.  batch: {"x": (B,lag,F), "y": (B,out)} plus an
    optional per-example validity "mask" (B,) — 1 for real examples, 0 for
    the padding the fixed-shape-bucket trainer adds.  A masked batch yields
    exactly the unpadded mean, so every shape bucket trains the same loss."""
    pred = forward(cfg, p, batch["x"])
    err = (pred - batch["y"]).astype(jnp.float32)
    sq = err * err
    mask = batch.get("mask")
    if mask is None:
        loss = jnp.mean(sq)
    else:
        m = mask.astype(jnp.float32)[:, None]
        denom = jnp.maximum(jnp.sum(m), 1.0) * sq.shape[-1]
        loss = jnp.sum(sq * m) / denom
    return loss, {"mse": loss, "rmse": jnp.sqrt(loss)}


def predict(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    return forward(cfg, p, x)
