"""Adaptive hybrid stream analytics (paper Sec. 5): lambda-architecture
orchestration of batch, speed and hybrid layers over a windowed stream.

Per time window t (paper Fig. 4):

  inference phase: batch inference with the one-time pre-trained model M^b;
  speed inference with M^s_{t-1} (trained on the previous window); hybrid
  inference combines the two with static or dynamic (Algorithm 1) weights.

  training phase (async): speed training of M^s_t on window t's records.

The orchestrator is generic over ``Forecaster`` so any model-zoo member can
be the backbone; ``lstm_forecaster`` builds the paper's exact setup
(batch: 50 epochs x bs 512; speed: 100 epochs x bs 64; lr 1e-3), and
``forecaster_for`` / ``fleet_forecaster_for`` build the forecaster a
config's family names (the paper's LSTM, or the Temporal Fusion
Transformer).

The per-window work itself lives in ``repro.core.stages`` as discrete,
individually-invokable pipeline stages; ``HybridStreamAnalytics.run`` is a
thin wrapper over ``repro.runtime.executor.InProcessExecutor`` (the
synchronous modality), and the same stages run bus-scheduled under any
``Deployment`` via ``repro.runtime.executor.BusExecutor``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.windows import WindowedStream
from repro.models.model import get_model
from repro.training.train_loop import fit

Params = Any


@dataclass(frozen=True)
class Forecaster:
    """train(data, params, key) -> (params, wall_s); predict(params, x) -> y.

    ``engine`` (optional) exposes the backing trainer — for the compiled
    path, the ``CompiledForecaster`` whose ``retrace_count`` the hot-path
    benchmark and the compile-cache regression tests inspect."""

    train: Callable[[Dict[str, np.ndarray], Optional[Params], jax.Array],
                    Tuple[Params, float]]
    predict: Callable[[Params, np.ndarray], np.ndarray]
    engine: Any = None


def lstm_forecaster(cfg: ModelConfig, *, epochs: int, batch_size: int,
                    lr: float = 1e-3, warm_start: bool = False,
                    compiled: bool = True) -> Forecaster:
    """The paper's LSTM forecaster.  ``compiled=True`` (default) rides the
    compile-once hot path: one cached jitted ``lax.scan`` fit executable per
    shape bucket (``repro.training.compiled``), one dispatch per window.
    ``compiled=False`` keeps the legacy per-call ``fit`` (fresh trace+compile
    every window, one dispatch per minibatch) — the pre-optimization
    baseline the hot-path benchmark measures against."""
    model = get_model(cfg)
    from repro.models import lstm as lstm_mod

    if compiled:
        from repro.training.compiled import CompiledForecaster

        eng = CompiledForecaster(
            model, epochs=epochs, batch_size=batch_size, lr=lr,
            warm_start=warm_start,
            predict_fn=lambda p, x: lstm_mod.predict(cfg, p, x))
        return Forecaster(train=eng.train, predict=eng.predict, engine=eng)

    predict_jit = jax.jit(lambda p, x: lstm_mod.predict(cfg, p, x))

    def train(data, params, key):
        res = fit(model, data, epochs=epochs, batch_size=batch_size, lr=lr,
                  params=params if warm_start else None, key=key)
        return res.params, res.wall_time_s

    def predict(params, x):
        return np.asarray(predict_jit(params, x))

    return Forecaster(train=train, predict=predict)


def lstm_fleet_forecaster(cfg: ModelConfig, *, epochs: int, batch_size: int,
                          lr: float = 1e-3, devices=None):
    """The paper's LSTM speed layer lifted to a fleet of streams: a
    ``repro.training.compiled.FleetForecaster`` that trains every stream's
    speed model in one vmapped dispatch per window (and satisfies the
    single-stream ``Forecaster`` protocol by delegating to its wrapped
    ``CompiledForecaster``).  ``devices`` bounds the stream mesh (default:
    every local device)."""
    from repro.models import lstm as lstm_mod
    from repro.training.compiled import FleetForecaster

    model = get_model(cfg)
    return FleetForecaster(
        model, epochs=epochs, batch_size=batch_size, lr=lr,
        predict_fn=lambda p, x: lstm_mod.predict(cfg, p, x), devices=devices)


def _tft_parts(cfg: ModelConfig, lr: float):
    from repro.models import tft as tft_mod
    from repro.training.optimizer import adamw

    return (get_model(cfg), lambda p, x: tft_mod.predict(cfg, p, x),
            adamw(lr, clip_norm=cfg.max_grad_norm))


def forecaster_for(cfg: ModelConfig, *, epochs: int, batch_size: int,
                   lr: float = 1e-3) -> Forecaster:
    """The single-stream forecaster of ``cfg``'s family: the paper's LSTM
    (``lstm_forecaster``), or the Temporal Fusion Transformer (cold fits
    only) with AdamW clipped at ``cfg.max_grad_norm``."""
    if cfg.family == "lstm":
        return lstm_forecaster(cfg, epochs=epochs, batch_size=batch_size,
                               lr=lr)
    if cfg.family == "tft":
        eng = fleet_forecaster_for(cfg, epochs=epochs,
                                   batch_size=batch_size, lr=lr)
        return Forecaster(train=eng.train, predict=eng.predict, engine=eng)
    raise ValueError(f"no forecaster for family {cfg.family!r}")


def fleet_forecaster_for(cfg: ModelConfig, *, epochs: int, batch_size: int,
                         lr: float = 1e-3, devices=None):
    """The fleet forecaster of ``cfg``'s family (``lstm_fleet_forecaster``
    for the LSTM)."""
    if cfg.family == "lstm":
        return lstm_fleet_forecaster(cfg, epochs=epochs,
                                     batch_size=batch_size, lr=lr,
                                     devices=devices)
    if cfg.family == "tft":
        from repro.training.compiled import FleetForecaster

        model, predict_fn, opt = _tft_parts(cfg, lr)
        # on a v5e the single-stream executable trained TFT's batch model
        # to parameters that were not finite, where the fleet executable
        # over a bucket of one matched the reference bit for bit: every
        # TFT fit takes the fleet executable
        return FleetForecaster(model, epochs=epochs, batch_size=batch_size,
                               opt=opt, predict_fn=predict_fn,
                               devices=devices, vmap_lone=True)
    raise ValueError(f"no fleet forecaster for family {cfg.family!r}")


@dataclass
class WindowRecord:
    window: int
    rmse_batch: float
    rmse_speed: float
    rmse_hybrid: float
    w_speed: float
    w_batch: float
    t_speed_train: float = 0.0
    t_batch_infer: float = 0.0
    t_speed_infer: float = 0.0
    t_hybrid_infer: float = 0.0
    t_weight_solve: float = 0.0


@dataclass
class HybridRunResult:
    records: List[WindowRecord]
    mode: str

    def mean_rmse(self) -> Dict[str, float]:
        return {
            "batch": float(np.mean([r.rmse_batch for r in self.records])),
            "speed": float(np.mean([r.rmse_speed for r in self.records])),
            "hybrid": float(np.mean([r.rmse_hybrid for r in self.records])),
        }

    def best_fraction(self) -> Dict[str, float]:
        """Paper Tables 4-6: time percentage each inference is the best."""
        wins = {"batch": 0, "speed": 0, "hybrid": 0}
        for r in self.records:
            best = min(
                ("speed", r.rmse_speed),
                ("batch", r.rmse_batch),
                ("hybrid", r.rmse_hybrid),
                key=lambda kv: kv[1],
            )[0]
            wins[best] += 1
        n = max(len(self.records), 1)
        return {k: v / n for k, v in wins.items()}

    def mean_latency(self) -> Dict[str, float]:
        return {
            "speed_train": float(np.mean([r.t_speed_train for r in self.records])),
            "batch_infer": float(np.mean([r.t_batch_infer for r in self.records])),
            "speed_infer": float(np.mean([r.t_speed_infer for r in self.records])),
            "hybrid_infer": float(np.mean([r.t_hybrid_infer for r in self.records])),
            "weight_solve": float(np.mean([r.t_weight_solve for r in self.records])),
        }


class HybridStreamAnalytics:
    """The adaptive hybrid learner.

    mode: "dynamic" (Algorithm 1), ("static", w_speed), "speed", "batch".
    ``dwa_solver``: "scipy" (paper SLSQP) or "closed_form" (TPU-native).
    """

    def __init__(
        self,
        forecaster: Forecaster,
        mode: str | Tuple[str, float] = "dynamic",
        dwa_solver: str = "closed_form",
    ):
        self.forecaster = forecaster
        self.mode = mode
        self.dwa_solver = dwa_solver

    def stages(self):
        """The learner decomposed into bus-schedulable pipeline stages."""
        from repro.core.stages import PipelineStages

        return PipelineStages.build(self.forecaster, self.mode,
                                    self.dwa_solver)

    def run(
        self,
        stream: WindowedStream,
        batch_params: Params,
        key: jax.Array,
        start_window: int = 1,
    ) -> HybridRunResult:
        from repro.runtime.executor import InProcessExecutor

        return InProcessExecutor(self.stages(), start_window=start_window).run(
            stream, batch_params, key)


def pretrain_batch_model(
    forecaster: Forecaster, historical: Dict[str, np.ndarray], key: jax.Array
) -> Tuple[Params, float]:
    """One-time batch training on historical data (paper: 20k observations,
    50 epochs, batch 512)."""
    return forecaster.train(historical, None, key)
