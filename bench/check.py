"""The comparison that decides ``correct``.

What set-up's calls returned, window by window, against the plain
reference over the same requests from the same initial state:

* ``reward_gap``: the largest relative gap of a window's fractional reward
  (sum of f over its requests) — the gradient step and the projection or
  threshold solve of every earlier window;
* ``hit_count_gap``: the gap of a window's count of Poisson hits, summed
  over the windows — the hit accounting against the permanent random
  numbers.  Flips in opposite directions within one window cancel, so it
  is a lower bound on the requests whose hit decision differs;
* ``occupancy_gap``: a quantile over the windows of the gap of a window's
  occupancy, in items, at the configuration's ``occupancy_quantile``.  The
  dense engine states an exact occupancy and is held by the largest gap
  (quantile 1).  The tree engine counts occupancy by the bucket of its
  threshold, so a threshold a rounding away from a bucket edge moves one
  window's count by a whole bucket; its median (quantile 0.5) sees past
  such a window and not past a state that has drifted;
* ``window_compiles``: compiles inside the measured window, which must be
  none.

Each number passes when it is at most its limit; the limits are in the
configuration's file, with the readings they were set from in PERF.md.
"""

from __future__ import annotations

import math

import numpy as np


def compare(got: dict, want: dict, cfg: dict) -> dict:
    ref = np.asarray(want["reward"], np.float64)
    occ = np.abs(np.asarray(got["occupancy"]) - want["occupancy"])
    return {
        "reward_gap": float(np.max(np.abs(np.asarray(got["reward"]) - ref)
                                   / np.maximum(np.abs(ref), 1e-30))),
        "hit_count_gap": float(np.sum(np.abs(np.asarray(got["hits"]) - want["hits"]))),
        "occupancy_gap": float(np.quantile(occ, float(cfg["occupancy_quantile"]))),
    }


def judge(numbers: dict, limits: dict) -> dict:
    """{"correct": bool, "checks": {name: {"value", "limit"}}}; a number
    with no limit, or a NaN, fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        passed = limit is not None and not math.isnan(value) and value <= limit
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return {"correct": ok, "checks": checks}
