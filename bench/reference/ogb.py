"""Dense OGB (``policy: ogb``): the gradient step and the exact projection
onto the capped simplex, in plain numpy.

Per window of B requests, at the pre-update state f (the order of online
convex optimization): the reward is sum_r f[r]; a request hits when
f[r] >= p[r]; the occupancy is #{i : f_i >= p_i}.  Then
y = f + eta * counts and f' = clip(y - tau, 0, 1) with tau the exact root
of sum_i clip(y_i - tau, 0, 1) = C.  That root is bracketed by [0, eta*B]
(f is feasible) and found by Newton steps on the piecewise-linear mass,
safeguarded by bisection, until the mass is C to the last bits of the
dtype or the bracket cannot shrink.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from bench.reference import learning_rate, permanent_numbers

#: Newton/bisection steps per projection; float64 converges in a handful,
#: a lower precision that cannot reach the root stops here
MAX_STEPS = 60


def project(y: np.ndarray, cap, lo, hi, t0):
    """(f, tau): the projection of ``y`` onto {f in [0,1]^N, sum f = cap}."""
    dt = y.dtype.type
    cap, lo, hi = dt(cap), dt(lo), dt(hi)
    t = min(max(dt(t0), lo), hi)
    tol = float(cap) * float(ml_dtypes.finfo(y.dtype).eps) * 4
    for _ in range(MAX_STEPS):
        z = y - t
        mass = float(np.clip(z, dt(0), dt(1)).sum())
        if abs(mass - float(cap)) <= tol:
            break
        if mass > float(cap):
            lo = t
        else:
            hi = t
        interior = np.count_nonzero((z > 0) & (z < 1))
        mid = dt((float(lo) + float(hi)) / 2)
        t_new = mid
        if interior:
            newton = dt(float(t) + (mass - float(cap)) / interior)
            if lo < newton < hi:
                t_new = newton
        if t_new == t or not lo < hi:
            break
        t = t_new
    return np.clip(y - t, dt(0), dt(1)), t


def replay(windows: np.ndarray, cfg: dict, policy_seed: int,
           dtype=np.float64) -> dict:
    n, c = int(cfg["catalog_size"]), int(cfg["capacity"])
    eta = dtype(learning_rate(cfg))
    p = permanent_numbers(policy_seed, n).astype(dtype)
    f = np.full(n, dtype(c) / dtype(n), dtype)
    m, b = windows.shape
    out = {k: np.zeros(m, np.float64) for k in ("reward", "hits", "occupancy")}
    tau = dtype(0)
    for w in range(m):
        ids = windows[w]
        fi = f[ids]
        out["reward"][w] = float(fi.sum())
        out["hits"][w] = np.count_nonzero(fi >= p[ids])
        out["occupancy"][w] = np.count_nonzero(f >= p)
        y = f.copy()
        np.add.at(y, ids, eta)
        f, tau = project(y, c, 0.0, float(eta) * b, tau)
    return out
