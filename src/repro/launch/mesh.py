"""Production mesh construction (TPU v5e class).

Defined as FUNCTIONS, not module-level constants, so importing this module
never touches jax device state (smoke tests must keep seeing 1 CPU device;
only the dry-run sets xla_force_host_platform_device_count=512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model zoo shards through GSPMD sharding constraints
    # (``distributed.sharding.shard``), which may not name Explicit axes —
    # the kind ``jax.make_mesh`` makes by default
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
