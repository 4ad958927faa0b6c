"""The paper's primary contribution: adaptive hybrid stream analytics
(lambda-architecture batch/speed/hybrid layers + static/dynamic weighting)."""
from repro.core.hybrid import (  # noqa: F401
    Forecaster,
    HybridRunResult,
    HybridStreamAnalytics,
    WindowRecord,
    fleet_forecaster_for,
    forecaster_for,
    lstm_fleet_forecaster,
    lstm_forecaster,
    pretrain_batch_model,
)
from repro.core.stages import (  # noqa: F401
    FleetStages,
    FleetState,
    PipelineStages,
    StreamId,
    StreamState,
    resolve_fleet_params,
    split_chain,
)
from repro.core.weighting import (  # noqa: F401
    combine,
    dwa_closed_form,
    dwa_jax,
    dwa_scipy,
    rmse,
    static_weights,
)
from repro.core.windows import WindowedStream, WindowPlan, make_supervised  # noqa: F401
from repro.core import drift  # noqa: F401
