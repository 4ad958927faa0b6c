"""Device seconds of the fleet-fit executable (``jit_fleet_fit``) per
window, from the trace."""


def read(rd):
    tr = rd["trace"]
    t = tr and tr["modules_s"].get("jit_fleet_fit")
    return t / rd["n_windows"] if t else None
