"""Seconds per window that the fit and predict executables do not cover:
the executor's host work (bus, scheduling, staging, sync fan-out, weight
solves) and whatever else the device runs, from the trace."""


def read(rd):
    tr = rd["trace"]
    if not tr:
        return None
    m = tr["modules_s"]
    covered = m.get("jit_fleet_fit", 0.0) + m.get("jit_fleet_predict", 0.0)
    if not covered:
        return None
    return (tr["window_s"] - covered) / rd["n_windows"]
