"""The comparison that decides ``correct`` for a training cell.

Readings of one side (the program, or a stand-in for it) and of the plain
reference, over the same first steps from the same seed:

* ``loss``: the loss of each checked step;
* ``grad``: per leaf, the norm of the first step's gradient as the
  optimizer gets it (clipped), worked out on the program's side from its
  first moment after one step, ``m / (1 - beta1)``;
* ``hess``: per leaf, the norm of Sophia's curvature state after the first
  step, which is a refresh step (Sophia cells only);
* ``update``: per leaf, the norm of the parameters' change over the
  checked steps;
* ``update_cos``: per leaf, the cosine between the two sides' changes over
  the checked steps (computed by whichever side was given the other's).

Numbers compared, each against a limit of the cell's workload file:

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the steps;
* ``grad_gap``, ``hess_gap``, ``update_gap``: over leaves, the largest
  ``| |x| - |ref| |`` (a gap of norms, not the norm of the difference),
  divided by the larger of the reference's norm of that leaf and the median
  leaf's.  ``update_gap`` leaves out the leaves whose reference gradient is
  under a thousandth of the median leaf's: they move by round-off alone;
* ``update_dir_gap``: over the same leaves, the largest ``1 - cos`` of the
  two sides' changes.  Under the warm-up's small learning rate Sophia clips
  nearly every coordinate to ``lr``, so a change's norm hardly depends on
  what the update computed; its direction (the sign of each coordinate)
  does.
"""
from __future__ import annotations

import math
import statistics

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's carries no signal in its update (e.g. a bias under a softmax)
DEAD_LEAF = 1e-3


def _leaf_gap(side: dict, ref: dict, keep=None):
    names = [k for k in ref if keep is None or keep(k)]
    med = statistics.median(ref[k] for k in names)
    worst, where = 0.0, None
    for k in names:
        x = side.get(k, float("nan"))
        gap = abs(x - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def gaps(side: dict, ref: dict) -> dict:
    """{number: (value, worst leaf or step)} for the readings both have."""
    out = {}
    worst, where = 0.0, None
    for i, (a, b) in enumerate(zip(side["loss"], ref["loss"])):
        gap = abs(a - b) / abs(b)
        if not math.isfinite(gap):
            worst, where = float("inf"), f"step {i}"
            break
        if gap > worst:
            worst, where = gap, f"step {i}"
    out["loss_gap"] = (worst, where)
    out["grad_gap"] = _leaf_gap(side["grad"], ref["grad"])
    if ref.get("hess"):
        out["hess_gap"] = _leaf_gap(side["hess"], ref["hess"])
    med = statistics.median(ref["grad"].values())

    def live(k):
        return ref["grad"][k] >= DEAD_LEAF * med

    out["update_gap"] = _leaf_gap(side["update"], ref["update"], keep=live)
    cos = ref.get("update_cos") or side.get("update_cos")
    if cos is not None:
        worst, where = 0.0, None
        for k in filter(live, ref["update"]):
            gap = 1.0 - cos.get(k, float("nan"))
            if not math.isfinite(gap):
                worst, where = float("inf"), k
                break
            if gap > worst:
                worst, where = gap, k
        out["update_dir_gap"] = (worst, where)
    return out


def judge(side: dict, ref: dict, limits: dict):
    """(correct, checks): every number at or under its limit, and finite.
    ``checks`` maps each number to its value, limit and worst place."""
    checks = {}
    ok = True
    for name, (value, where) in gaps(side, ref).items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit, "at": where}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, checks
