"""Time-window bookkeeping and supervised dataset construction.

The paper's problem statement (Sec. 5.1): with time lag n=5, predict
y^i from (y^{i-1}, ..., y^{i-n}); the stream is chopped into time windows of
>= 200 records (~30 s), the speed layer trains on window t and predicts
window t+1.

A multi-horizon forecaster (``horizon`` > 1, the Temporal Fusion
Transformer) takes encoder-decoder samples instead: ``lag`` past steps and
``horizon`` future steps of known inputs, the stream's id, and the target
over the horizon (``make_supervised``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Tuple

import numpy as np

from repro.tracing import span

N_KNOWN = 3


def known_inputs(t: np.ndarray, period: float) -> np.ndarray:
    """The known reals of records ``t`` (their indices in the stream):
    ``t / P``, ``sin 2 pi t / P`` and ``cos 2 pi t / P`` -> (len(t), 3)."""
    t = np.asarray(t, np.float64)
    ph = 2 * np.pi * t / period
    return np.stack([t / period, np.sin(ph), np.cos(ph)],
                    axis=-1).astype(np.float32)


def _encdec(series: np.ndarray, lag: int, horizon: int, target_col: int,
            period: float, stream_id: int, first_record: int
            ) -> Dict[str, np.ndarray]:
    """(T, F) -> {"x": (n, lag + horizon, F + 4), "y": (n, horizon)} with
    n = T - lag - horizon + 1: the observed channels (zero on the horizon
    rows), the known reals, then the stream's id in every row."""
    T, F = series.shape
    L = lag + horizon
    n = max(T - L + 1, 0)
    idx = np.arange(L)[None, :] + np.arange(n)[:, None]      # (n, L)
    x = np.zeros((n, L, F + N_KNOWN + 1), np.float32)
    x[:, :lag, :F] = series[idx[:, :lag]]
    x[:, :, F:F + N_KNOWN] = known_inputs(
        np.arange(first_record, first_record + T), period)[idx]
    x[:, :, -1] = stream_id
    y = series[idx[:, lag:], target_col]
    return {"x": x, "y": y.astype(np.float32)}


def make_supervised(series: np.ndarray, lag: int, target_col: int = 0, *,
                    horizon: int = 1, period: float = 0.0,
                    stream_id: int = 0, first_record: int = 0
                    ) -> Dict[str, np.ndarray]:
    """(T, F) series -> {"x": (n, lag, F), "y": (n, 1)} with n = T - lag.

    With ``horizon`` > 1, the encoder-decoder samples of ``_encdec``
    (``first_record`` is the index of ``series[0]`` in its stream, for the
    known inputs of period ``period``)."""
    series = np.asarray(series, np.float32)
    if series.ndim == 1:
        series = series[:, None]
    if horizon > 1:
        with span("windows.encdec"):
            return _encdec(series, lag, horizon, target_col, period,
                           stream_id, first_record)
    T, F = series.shape
    n = T - lag
    if n <= 0:
        return {"x": np.zeros((0, lag, F), np.float32),
                "y": np.zeros((0, 1), np.float32)}
    idx = np.arange(lag)[None, :] + np.arange(n)[:, None]  # (n, lag)
    x = series[idx]  # (n, lag, F)
    y = series[lag:, target_col : target_col + 1]
    return {"x": x.astype(np.float32), "y": y.astype(np.float32)}


@dataclass(frozen=True)
class WindowPlan:
    """How a stream is cut into windows.  ``horizon`` > 1 asks for
    encoder-decoder samples, built from each window's own records, with
    known inputs of period ``period`` counted from ``first_record`` (the
    index of the series' first record in its stream) and the stream's id
    ``stream_id``."""

    n_windows: int
    records_per_window: int
    lag: int
    target_col: int = 0
    horizon: int = 1
    period: float = 0.0
    stream_id: int = 0
    first_record: int = 0


class WindowedStream:
    """Iterates (window_index, window_records, supervised_data).

    Each window's supervised pairs include ``lag`` records of left context
    from the previous window so no boundary samples are lost; an
    encoder-decoder window takes its samples from its own records only.
    """

    def __init__(self, series: np.ndarray, plan: WindowPlan):
        self.series = np.asarray(series, np.float32)
        self.plan = plan

    def __len__(self) -> int:
        return min(self.plan.n_windows,
                   len(self.series) // self.plan.records_per_window)

    def window_records(self, t: int) -> np.ndarray:
        w = self.plan.records_per_window
        return self.series[t * w : (t + 1) * w]

    def supervised(self, t: int) -> Dict[str, np.ndarray]:
        p = self.plan
        w = p.records_per_window
        if p.horizon > 1:
            return make_supervised(
                self.window_records(t), p.lag, p.target_col,
                horizon=p.horizon, period=p.period, stream_id=p.stream_id,
                first_record=p.first_record + t * w)
        start = max(t * w - p.lag, 0)
        chunk = self.series[start : (t + 1) * w]
        return make_supervised(chunk, p.lag, p.target_col)

    def __iter__(self) -> Iterator[Tuple[int, np.ndarray, Dict[str, np.ndarray]]]:
        for t in range(len(self)):
            yield t, self.window_records(t), self.supervised(t)
