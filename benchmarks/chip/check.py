"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first ``checked_steps`` steps of the same job
from the same weights and batches:

* ``loss``: each step's loss;
* ``grad_sq``: per entry (a leaf, or one layer of a stacked leaf) the
  squared norm of the first gradient as the optimizer gets it (after
  clipping);
* ``update_sq``: per entry the squared norm of the parameters' change
  over those steps.

The numbers compared:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: over the entries, the largest gap between the two sides'
  gradient norms, as a share of the reference's norm of that entry or of
  the median entry, whichever is larger (some gradients are all but zero);
* ``update_gap``: the same for the change of the parameters, over the
  entries whose reference gradient is at least ``MOVED`` of the median
  entry's.  The others (a key bias under softmax) move under Adam by
  round-off alone.
"""

from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
MOVED = 1e-3


def _worst(prog_sq, ref_sq, names, keep=None):
    p, r = np.sqrt(np.asarray(prog_sq)), np.sqrt(np.asarray(ref_sq))
    keep = np.ones(r.shape, bool) if keep is None else keep
    floor = max(np.median(r[keep]), 1e-30)
    gap = np.where(keep, np.abs(p - r) / np.maximum(r, floor), 0.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def readings(prog: dict, ref: dict, names) -> tuple[dict, dict]:
    """({number: value}, {number: the entry or step it came from})."""
    lp, lr = prog["loss"], ref["loss"]
    steps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
             for a, b in zip(lp, lr)]
    worst = int(np.argmax(steps))
    g, g_at = _worst(prog["grad_sq"], ref["grad_sq"], names)
    r = np.sqrt(np.asarray(ref["grad_sq"]))
    moved = r >= MOVED * np.median(r)
    u, u_at = _worst(prog["update_sq"], ref["update_sq"], names, moved)
    return ({"loss_gap": steps[worst], "grad_gap": g, "update_gap": u},
            {"loss_gap": f"step {worst}", "grad_gap": g_at,
             "update_gap": u_at})


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(every number within its limit, {number: {value, limit}})."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(math.isfinite(values[k]) and values[k] <= limits[k]
             for k in NUMBERS)
    return ok, checks
