"""The one traffic generator: every mix file under ``bench/traffic/`` is read
by these functions, and nothing else makes requests.

Ids follow zipf(alpha) over a catalog of ``n`` items, drawn by inverse CDF
(the arithmetic of ``numpy.random.Generator.choice(n, p=w)`` that
``repro.cachesim.traces.zipf`` uses, copied so the yardstick does not move
with the program).  Arrival gaps of an open loop are the ``count``
equal-probability quantiles of an exponential distribution at the mix's
rate, in an order drawn from the seed: every seed offers the same gaps and
the same total time, so seeds differ in the order of the load and not in
its amount.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

#: the keys every mix file gives, by mode; a mix may not add others
MODE_KEYS = {
    "replay": {"mode", "alpha", "ring_segments", "setup_segments"},
    "stream": {"mode", "alpha", "ring_segments", "setup_segments", "chunk",
               "prefetch", "opt_window"},
    "serve": {"mode", "alpha", "ring_decisions", "setup_decisions",
              "rate_per_s", "drain_s"},
}


def load_mix(path: Path) -> dict:
    """A mix file, checked against the keys its mode needs."""
    mix = json.loads(Path(path).read_text())
    mode = mix.get("mode")
    if mode not in MODE_KEYS:
        raise ValueError(f"{path}: mode {mode!r} is not one of {sorted(MODE_KEYS)}")
    if set(mix) != MODE_KEYS[mode]:
        raise ValueError(
            f"{path}: a {mode} mix has exactly the keys {sorted(MODE_KEYS[mode])}, "
            f"got {sorted(mix)}"
        )
    return mix


def seeds(seed: int) -> tuple:
    """(traffic generator, policy seed) from the run's ``--seed``: any whole
    number, however large, maps to an independent stream and to a policy
    seed that fits a 32-bit ``jax.random.key``."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    ss = np.random.SeedSequence(seed)
    traffic_ss, policy_ss = ss.spawn(2)
    policy_seed = int(policy_ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)
    return np.random.default_rng(traffic_ss), policy_seed


def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    """Normalized cumulative zipf(alpha) weights over item ranks 0..n-1."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), alpha)
    cdf = np.cumsum(w / w.sum())
    cdf /= cdf[-1]
    return cdf


def zipf_ids(cdf: np.ndarray, count: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` ids drawn by inverse CDF, as int32."""
    return cdf.searchsorted(rng.random(count), side="right").astype(np.int32)


def exp_gaps(count: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``count`` inter-arrival gaps (seconds) of a Poisson process at
    ``rate``: the exponential's quantiles at (i + 0.5) / count, shuffled."""
    q = (np.arange(count, dtype=np.float64) + 0.5) / count
    gaps = -np.log1p(-q) / float(rate)
    rng.shuffle(gaps)
    return gaps
