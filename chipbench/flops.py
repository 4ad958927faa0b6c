"""Model FLOPs of the paper's forecaster, counted from its shapes.

A multiply-add is two FLOPs.  Only the matrix products count: per lag step
the LSTM's input and recurrent projections, then the dense layer and the
head.  Elementwise gates and the optimizer are left out.  Training costs
three forward passes per example (the forward, and the backward's two
products per matrix).  Padding rows and padded streams are not counted.
"""
from __future__ import annotations

from typing import Dict


def forward_per_example(cfg: Dict) -> int:
    H, F, D, T = cfg["hidden"], cfg["n_features"], cfg["dense"], cfg["lag"]
    out = cfg.get("out_dim", 1)
    return T * (2 * F * 4 * H + 2 * H * 4 * H) + 2 * H * D + 2 * D * out


def train_per_example_epoch(cfg: Dict) -> int:
    return 3 * forward_per_example(cfg)


def window_flops(cfg: Dict, n_streams: int, examples: int,
                 predict_passes: int) -> int:
    """One fleet window: every stream's cold fit (``speed_epochs`` over its
    ``examples``) and ``predict_passes`` forward passes over the window
    (the two evaluation predicts that feed the next weight solve, and the
    batch and speed inference)."""
    fit = cfg["speed_epochs"] * examples * train_per_example_epoch(cfg)
    serve = predict_passes * examples * forward_per_example(cfg)
    return n_streams * (fit + serve)
