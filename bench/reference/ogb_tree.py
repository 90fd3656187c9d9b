"""Lazy bucketized OGB (``policy: ogb_tree``) by its own stated semantics,
in plain numpy with dense histograms in place of the program's trees.

The state is the unprojected accumulation y with f = clip(y - rho, 0, 1)
implicit, a V-bucket grid of width w over [-1, V*w - 1), and three
histograms over that grid: the count and the sum of y per bucket, and the
count of y - p per bucket.  Per window of B requests:

* at the pre-update state: reward = sum_r f[r]; a request hits when
  f[r] >= p[r]; the occupancy counts the items whose y - p lies in a bucket
  above rho's (the d-histogram's suffix);
* the lazy clip: each requested item is first clipped to [rho, 1 + rho],
  then gains eta per request; each distinct requested item moves from the
  buckets of its old value to those of its new one;
* the threshold solve: 30 bisections of [rho, rho + max(eta*B, 4w)] on the
  histogram mass, where a bucket wholly inside the linear region counts
  sum - t*count, one wholly above t+1 counts its items, and the two
  boundary buckets count their mean clipped;
* the re-anchor: when 1 + rho' + eta*B reaches the top of the grid less one
  bucket, y <- clip(y - rho', 0, 1), rho <- 0, and the histograms are
  rebuilt.

The grid follows the configuration: span = 1 + 2*8*max(1, eta*4096),
w = (span + 1) / 65536, so the grid spans about 16 windows of the largest
threshold growth between re-anchors.
"""

from __future__ import annotations

import numpy as np

from bench.reference import learning_rate, permanent_numbers

#: value-grid buckets, grid headroom factor and the batch the grid is sized
#: for, as the configuration's engine states them
BUCKETS = 65536
GAIN = 8.0
BATCH_HINT = 4096
#: bisection steps of the threshold solve
ITERS = 30


def _bucket(x, w, v):
    return np.clip(np.floor((x + 1.0) / w), 0, v - 1).astype(np.int64)


def replay(windows: np.ndarray, cfg: dict, policy_seed: int,
           dtype=np.float64) -> dict:
    n, c = int(cfg["catalog_size"]), int(cfg["capacity"])
    v = BUCKETS
    eta = dtype(learning_rate(cfg))
    p = permanent_numbers(policy_seed, n).astype(dtype)
    span = 1.0 + 2.0 * GAIN * max(1.0, float(eta) * BATCH_HINT)
    w = dtype((span + 1.0) / v)
    cap = dtype(c)
    y = np.full(n, dtype(c) / dtype(n), dtype)
    rho = dtype(0)

    def rebuild(y):
        by = _bucket(y, w, v)
        cnt = np.bincount(by, minlength=v).astype(dtype)
        sm = np.zeros(v, dtype)
        np.add.at(sm, by, y)
        dcnt = np.bincount(_bucket(y - p, w, v), minlength=v).astype(dtype)
        return cnt, sm, dcnt

    cnt, sm, dcnt = rebuild(y)
    m, b = windows.shape
    out = {k: np.zeros(m, np.float64) for k in ("reward", "hits", "occupancy")}
    for k in range(m):
        ids = windows[k]
        fi = np.clip(y[ids] - rho, 0, 1)
        out["reward"][k] = float(fi.sum())
        out["hits"][k] = np.count_nonzero(fi >= p[ids])
        out["occupancy"][k] = float(dcnt.sum() - dcnt[: _bucket(rho, w, v) + 1].sum())

        # the lazy clip and the gradient step
        first = np.unique(ids, return_index=True)[1]
        items = ids[first]
        yold = y[items].copy()
        y[ids] = np.clip(y[ids], rho, 1 + rho)
        np.add.at(y, ids, eta)
        ynew = y[items]
        bo, bn = _bucket(yold, w, v), _bucket(ynew, w, v)
        np.add.at(cnt, bo, dtype(-1))
        np.add.at(cnt, bn, dtype(1))
        np.add.at(sm, bo, -yold)
        np.add.at(sm, bn, ynew)
        np.add.at(dcnt, _bucket(yold - p[items], w, v), dtype(-1))
        np.add.at(dcnt, _bucket(ynew - p[items], w, v), dtype(1))

        # the threshold solve over the histogram
        ccum, scum = np.cumsum(cnt), np.cumsum(sm)
        total = ccum[-1]

        def mass(t):
            k0, k1 = _bucket(t, w, v), _bucket(t + 1, w, v)
            above = total - ccum[k1]
            mid = (scum[k1] - sm[k1] - scum[k0]) - t * (ccum[k1] - cnt[k1] - ccum[k0])
            bnd = 0
            for kk in ((k0, k1) if k1 > k0 else (k0,)):
                if cnt[kk] > 0:
                    bnd += cnt[kk] * np.clip(sm[kk] / cnt[kk] - t, 0, 1)
            return above + mid + bnd

        lo, hi = rho, rho + max(eta * dtype(b), dtype(4) * w)
        for _ in range(ITERS):
            mid_t = (lo + hi) / dtype(2)
            if mass(mid_t) >= cap:
                lo = mid_t
            else:
                hi = mid_t
        rho = lo

        # the re-anchor, before the next window could outgrow the grid
        if 1 + rho + eta * dtype(b) >= w * dtype(v) - 1 - w:
            y = np.clip(y - rho, 0, 1).astype(dtype)
            rho = dtype(0)
            cnt, sm, dcnt = rebuild(y)
    return out
