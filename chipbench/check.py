"""The comparisons that decide ``correct``: the program's outputs against
the plain reference's, reduced to a few numbers, each held to its limit in
``limits/<cell>.json``.

Gaps that compound through hundreds of optimizer steps (a trained model's
final loss, its weights, its forecasts) are each taken twice over the
streams and checked windows: as the median, and as the 90th percentile.
One stream whose fit settles in another minimum moves a mean or a maximum
a long way, and neither of these; a fault on a tenth of the streams or
more (one chip's streams of four, say) moves the 90th percentile.  The
fit's first steps do not compound yet, so their gap is the worst over
every stream.  The hybrid weights are held through the hybrid forecast
that they weigh: a weight alone is ill-conditioned where the speed and
batch forecasts agree, and there moves the forecast by nothing.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

FIRST_STEPS = 3
TAIL = 0.9


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-12)


def _leaves(tree) -> Dict[str, np.ndarray]:
    return {f"{k}/{kk}": np.asarray(v, np.float64)
            for k, sub in tree.items() for kk, v in sub.items()}


def tree_gap(p, r) -> float:
    """The worst leaf's ``||p - r||`` over the larger of that leaf's and
    the median leaf's reference norm."""
    pl, rl = _leaves(p), _leaves(r)
    norms = {k: float(np.linalg.norm(v)) for k, v in rl.items()}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(pl[k] - rl[k])) / max(norms[k], med,
                                                          1e-12)
               for k in rl)


def compare_windows(prog: Dict, ref: Dict, checked: List[int]
                    ) -> Dict[str, float]:
    rows = ref["rows"]
    first, final, trees = [], [], []
    for v, rl in ref["fit"].items():
        pl = prog["fit"].get(v)
        for j, i in enumerate(rows):
            if pl is None or np.isnan(pl[i]).any():
                first.append(np.inf)
                final.append(np.inf)
                continue
            first.append(float(_rel(pl[i, :FIRST_STEPS],
                                    rl[j, :FIRST_STEPS]).max()))
            last = 4 if rl.shape[1] >= 4 else 1
            final.append(float(_rel(pl[i, -last:].mean(),
                                    rl[j, -last:].mean())))
            pt = prog["synced"].get((i, v))
            trees.append(np.inf if pt is None
                         else tree_gap(pt, ref["synced"][(i, v)]))
    stale = 0
    per = {"speed": [], "batch": [], "hybrid": []}
    for w in checked:
        for i in rows:
            if prog["model_window"].get((i, w)) != w - 1:
                stale += 1
            for k in per:
                p = prog["infer"].get((k, i, w))
                r = ref["infer"][(k, i, w)]
                if p is None or np.shape(p) != np.shape(r):
                    per[k].append(np.inf)
                    continue
                per[k].append(float(np.mean(np.abs(
                    np.asarray(p, np.float64) - np.asarray(r, np.float64)))))
    out = {
        "windows_missing": float(prog["missing"] + stale),
        "fit_first_steps_gap": float(max(first)) if first else float("inf"),
    }
    for name, xs in (("fit_final_loss_gap", final),
                     ("synced_tree_gap", trees),
                     ("batch_forecast_gap", per["batch"]),
                     ("speed_forecast_gap", per["speed"]),
                     ("hybrid_forecast_gap", per["hybrid"])):
        out.update(_spread(name, xs))
    return out


def _spread(name: str, xs: List[float]) -> Dict[str, float]:
    """``name`` (the median over ``xs``) and ``name_p90`` (its 90th
    percentile, an element of ``xs``, so a gap that never came reads
    inf and never NaN)."""
    if not xs:
        return {name: float("inf"), name + "_p90": float("inf")}
    return {name: float(np.median(xs)),
            name + "_p90": float(np.quantile(xs, TAIL, method="higher"))}


def compare_answers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Queries cells: every query due in the window must be answered, and
    the sampled answers must agree with the reference's (median and 90th
    percentile over the streams of each stream's mean gap, every horizon
    step counted)."""
    by_stream: Dict[int, List[float]] = {}
    for uid, r in ref["answers"].items():
        p = prog["answers"].get(uid)
        s = ref["stream"][uid]
        if p is None or len(p) != len(r):
            gap = np.inf
        else:
            gap = float(np.mean(np.abs(np.asarray(p, np.float64)
                                       - np.asarray(r, np.float64))))
        by_stream.setdefault(s, []).append(gap)
    means = [float(np.mean(v)) for v in by_stream.values()]
    return {"queries_unanswered": float(prog["unanswered"]),
            **_spread("answer_gap", means)}
