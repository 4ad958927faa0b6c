"""Device seconds of the fleet-predict executable (``jit_fleet_predict``:
the evaluation predicts and the batch and speed inference) per window,
from the trace."""


def read(rd):
    tr = rd["trace"]
    t = tr and tr["modules_s"].get("jit_fleet_predict")
    return t / rd["n_windows"] if t else None
