"""Traffic generation from the seed: the turbine fleet's sensor windows and
the open-loop forecast-query arrivals.

The fleet series follow the program's ``streams.sources`` recipe (daily and
seasonal harmonics, cross-correlated AR(1) noise, a shared farm component,
and the paper's Eq. 6/7 gradual and abrupt drift plus a seasonal excursion),
vectorised over streams so a thousand-stream fleet is made in about a second.
Every stream is min-max scaled by its own history, as the program's fleet
launcher does.

The query arrivals keep the work fixed across seeds: the set of gaps and the
counts of each kind come from the traffic file alone (``shape_seed``); the
run's seed only orders them and draws the what-if perturbations.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
from scipy.signal import lfilter

N_CHANNELS = 5
BASE = np.array([45.0, 44.0, 55.0, 54.0, 12.0])
DAILY = np.array([2.0, 2.2, 3.0, 2.8, 5.0])
SEASONAL = np.array([1.2, 1.2, 1.6, 1.6, 3.0])
NOISE = np.array([0.8, 0.8, 1.2, 1.2, 1.5])
SCENARIOS = ("none", "gradual", "abrupt", "seasonal")


def _ar1(eps: np.ndarray, a: float) -> np.ndarray:
    """x[0] = 0, x[i] = a * x[i-1] + eps[i], along axis 1."""
    eps = eps.copy()
    eps[:, 0] = 0.0
    return lfilter([1.0], [1.0, -a], eps, axis=1)


def _turbines(rng: np.random.Generator, s: int, n: int) -> np.ndarray:
    """(s, n, 5) stationary turbine series, one per stream."""
    t = np.arange(n, dtype=np.float64)
    day = 24 * 60 / 10.0
    year = 365 * day
    harm = (BASE[None] + np.sin(2 * np.pi * t / day)[:, None] * DAILY[None]
            + np.sin(2 * np.pi * t / year + 0.5)[:, None] * SEASONAL[None])
    shared = _ar1(rng.normal(0, 0.3, (s, n)), 0.98)
    own = _ar1(rng.normal(0, 1.0, (s, n, N_CHANNELS)), 0.95)
    noise = (own + shared[:, :, None]) * NOISE * 0.5
    return harm[None] + noise


def _drift(rng: np.random.Generator, x: np.ndarray, kind: str, start: int,
           alpha: float, period: int) -> np.ndarray:
    """One stream's drift after ``start`` (Eq. 6 gradual, Eq. 7 abrupt, or
    the seasonal excursion), with its own observation noise."""
    n, f = x.shape
    if kind == "none":
        return x
    t = np.maximum(np.arange(n, dtype=np.float64) - start, 0.0)
    eps = rng.normal(0, 0.2, (n, f))
    if kind == "gradual":
        return x + alpha * t[:, None] + eps
    if kind == "abrupt":
        cuts = np.sort(rng.choice(np.arange(start + 1, n - 1), 4,
                                  replace=False))
        levels = rng.uniform(-1.5, 1.5, 5)
        lam = levels[np.searchsorted(cuts, np.arange(n), side="right")]
        return x + alpha * (t * lam)[:, None] + eps
    if kind == "seasonal":
        phases = rng.uniform(0.0, 2 * np.pi, f)
        wave = np.sin(2 * np.pi * t[:, None] / period + phases[None])
        wave *= (t > 0)[:, None]
        return x + x.std(axis=0)[None] * wave + eps
    raise ValueError(f"unknown drift {kind!r}")


def fleet(seed: int, n_streams: int, n_windows: int, traffic: Dict,
          cfg: Dict) -> Tuple[np.ndarray, np.ndarray, List[str]]:
    """The fleet's scaled series: ``(history (S, hist, 5), live (S, n, 5),
    scenario per stream)``, both float32 in [0, 1] by each stream's history.

    Scenarios are assigned round-robin from ``traffic["drift_mix"]`` and
    shuffled by the seed, so every seed has the same count of each."""
    hist = int(cfg["history_records"])
    lag = int(cfg["lag"])
    n = hist + int(cfg["records_per_window"]) * n_windows + lag
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xF1EE7]))
    farm = _turbines(rng, 1, n)[0]
    raw = _turbines(rng, n_streams, n)
    raw += float(traffic.get("shared_frac", 0.35)) * (
        farm - farm.mean(axis=0, keepdims=True))[None]
    mix = list(traffic["drift_mix"])
    kinds = [mix[i % len(mix)] for i in range(n_streams)]
    kinds = [kinds[i] for i in rng.permutation(n_streams)]
    period = int(traffic.get("seasonal_period_records", 12500))
    alpha = float(traffic.get("drift_alpha", 1.5e-3))
    for i, kind in enumerate(kinds):
        raw[i] = _drift(rng, raw[i], kind, hist, alpha, period)
    h = raw[:, :hist]
    lo, hi = h.min(axis=1, keepdims=True), h.max(axis=1, keepdims=True)
    scaled = ((raw - lo) / np.maximum(hi - lo, 1e-12)).astype(np.float32)
    return scaled[:, :hist], scaled[:, hist:], kinds


def supervised(series: np.ndarray, lag: int) -> Dict[str, np.ndarray]:
    """(T, F) -> {"x": (T - lag, lag, F), "y": (T - lag, 1)}: predict
    channel 0 from the ``lag`` records before it (paper Sec. 5.1)."""
    n = series.shape[0] - lag
    idx = np.arange(lag)[None, :] + np.arange(n)[:, None]
    return {"x": series[idx].astype(np.float32),
            "y": series[lag:, :1].astype(np.float32)}


def window(live: np.ndarray, w: int, rpw: int, lag: int
           ) -> Dict[str, np.ndarray]:
    """Window ``w`` of one stream as supervised pairs, with ``lag`` records
    of left context from the window before (none for window 0)."""
    start = max(w * rpw - lag, 0)
    return supervised(live[start:(w + 1) * rpw], lag)


def arrivals(seed: int, traffic: Dict, n_streams: int, seconds: float
             ) -> Dict[str, np.ndarray]:
    """Open-loop query arrivals over ``seconds``: due times, stream index,
    kind (0 point, 1 horizon, 2 what-if), horizon, and the what-if scale and
    offset.

    The count is ``rate * seconds``.  Gaps are exponential (Poisson
    arrivals); they are drawn once from the traffic's ``shape_seed`` and
    scaled to fill the window exactly, and the run's seed only permutes
    them.  Streams go round-robin from a seeded offset."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    gaps = shape.exponential(1.0, n)
    mix = np.asarray(traffic["kind_mix"], np.float64)
    counts = np.floor(mix / mix.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    kinds = np.repeat(np.arange(len(counts)), counts)
    max_h = int(traffic.get("max_horizon", 3))
    horizons = np.where(kinds == 1, 2 + np.arange(n) % (max_h - 1), 1)

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x0A11]))
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * (n - 0.5) / n
    order = rng.permutation(n)
    kinds, horizons = kinds[order], horizons[order]
    stream = (int(rng.integers(n_streams)) + np.arange(n)) % n_streams
    scale = np.where(kinds == 2, 1.0 + 0.1 * rng.standard_normal(n), 1.0)
    offset = np.where(kinds == 2, 0.05 * rng.standard_normal(n), 0.0)
    return {"due": due, "stream": stream.astype(np.int64),
            "kind": kinds.astype(np.int64), "horizon": horizons,
            "scale": scale.astype(np.float32),
            "offset": offset.astype(np.float32)}
