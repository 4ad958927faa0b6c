"""Model FLOPs of the Temporal Fusion Transformer, counted from its shapes.

A multiply-add is two FLOPs.  Only the matrix products count, each where
the program computes it: per look-back step the past variable selection
(the weights' GRN over the flattened inputs and one GRN per variable), per
horizon step the future one, per step the LSTM's two products; the static
encoders and each context's map once per sample; per position the gated
skips, the enrichment, the attention's maps, the position-wise GRN; the
attention's scores and weighted sums over every pair of positions (the
causal mask hides half, the product is computed whole); the quantile head
on the horizon.  Elementwise work, the inputs' own maps (a scale and a
shift each) and the optimizer are left out.  Training costs three forward
passes per example.  Padding rows are not counted.
"""
from __future__ import annotations

from typing import Dict


def _grn(din: int, h: int, dout: int) -> int:
    """One GRN's products on one input, without its context's map."""
    skip = din * dout if din != dout else 0
    return 2 * (din * h + h * h + 2 * h * dout + skip)


def forward_per_example(cfg: Dict) -> int:
    H, nh = cfg["hidden"], cfg["n_heads"]
    d, nq = H // nh, len(cfg["quantiles"])
    k, tau = cfg["lookback"], cfg["horizon"]
    T = k + tau
    vp = cfg["n_observed"] + cfg["n_known"]
    vf = cfg["n_known"]
    ctx_once = 2 * H * H
    static = _grn(H, H, 1) + _grn(H, H, H) + 4 * _grn(H, H, H)
    past = k * (_grn(vp * H, H, vp) + vp * _grn(H, H, H)) + ctx_once
    future = tau * (_grn(vf * H, H, vf) + vf * _grn(H, H, H)) + ctx_once
    lstm = T * 2 * (H * 4 * H + H * 4 * H)
    gates = 3 * T * 2 * (2 * H * H)                  # after LSTM, attn, out
    grns = 2 * T * _grn(H, H, H) + ctx_once          # enrichment, pos-wise
    attn = (T * 2 * (2 * nh * H * d + H * d + d * H)
            + 2 * nh * 2 * T * T * d)
    head = tau * 2 * H * nq
    return static + past + future + lstm + gates + grns + attn + head


def train_per_example_epoch(cfg: Dict) -> int:
    return 3 * forward_per_example(cfg)


def window_flops(cfg: Dict, n_streams: int, examples: int,
                 predict_passes: int) -> int:
    """One fleet window: every stream's cold fit (``speed_epochs`` over its
    ``examples``) and ``predict_passes`` forward passes over the window."""
    fit = cfg["speed_epochs"] * examples * train_per_example_epoch(cfg)
    serve = predict_passes * examples * forward_per_example(cfg)
    return n_streams * (fit + serve)
