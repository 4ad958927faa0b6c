"""Shared host-device plumbing for the fleet-scale benchmarks.

XLA fixes its device count at backend initialization, so exposing the
host's cores as devices (``--xla_force_host_platform_device_count``) must
happen *before the first jax import anywhere in the process* — and can
never be changed afterwards.  Every benchmark that shards a stream axis
used to carry its own copy of this dance; they all route through here now:

* :func:`ensure_host_devices` — the in-process shim: append the device-count
  flag to ``XLA_FLAGS`` unless one is already inherited (so an outer harness
  can still pin it).  Call it from ``main()`` before any jax-importing work.
* :func:`subprocess_env` — the sweep cell: an environ copy with the count
  pinned to exactly ``n`` (*replacing* any inherited flag) and the backend
  pinned to the CPU.  Weak-scaling sweeps need a fresh process per device
  count, and the child must not inherit the parent's mesh size.  Such a
  cell is a host-device rehearsal: on a TPU machine the parent holds the
  chip, and a child that asked for it would fail or hang.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

_FLAG = "--xla_force_host_platform_device_count"


def ensure_host_devices(n: Optional[int] = None) -> int:
    """Expose ``n`` host devices to XLA (default: the machine's core count)
    by appending to ``XLA_FLAGS`` — an inherited device-count flag wins.
    Must run before the first jax import; returns the count requested (the
    inherited one when present)."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_FLAG + r"=(\d+)", flags)
    if m:
        return int(m.group(1))
    n = n or os.cpu_count() or 1
    os.environ["XLA_FLAGS"] = (flags + " " if flags else "") + f"{_FLAG}={n}"
    return n


def subprocess_env(n: int) -> Dict[str, str]:
    """An ``os.environ`` copy for a CPU-only child with exactly ``n`` host
    devices: any inherited ``--xla_force_host_platform_device_count`` is
    stripped first, so a sweep's child processes get the cell's mesh size,
    not the parent's, and ``JAX_PLATFORMS=cpu`` keeps them off any
    accelerator the parent holds."""
    env = dict(os.environ)
    flags = re.sub(_FLAG + r"=\d+", "", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (flags + " " if flags else "") + f"{_FLAG}={n}"
    env["JAX_PLATFORMS"] = "cpu"
    return env
