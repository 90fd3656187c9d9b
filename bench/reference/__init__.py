"""Plain references of the policies the benchmark runs, one module per
policy kind (``bench/reference/<policy>.py``), each exposing

    replay(windows, cfg, policy_seed, dtype=np.float64) -> dict

over ``windows``, an ``(M, B)`` array of request ids, from the initial
state the configuration ``cfg`` states.  The dict holds per-window
``reward``, ``hits`` and ``occupancy``, the observables the program's
``repro.run`` returns.  The references import nothing of the program and
take nothing it made: the learning rate and the permanent random numbers
are derived here from the configuration and the seed.
"""

from __future__ import annotations

import math

import numpy as np


def learning_rate(cfg: dict) -> float:
    """Theorem 3.1 of arXiv:2405.01263 at a batch of one, over the
    configuration's planned horizon: sqrt(C (1 - C/N) / T)."""
    n, c, t = cfg["catalog_size"], cfg["capacity"], cfg["horizon"]
    return math.sqrt(c * (1.0 - c / n) / t)


def permanent_numbers(policy_seed: int, n: int) -> np.ndarray:
    """The coordinated Poisson sample's permanent random numbers p_i: the
    first half of a split of ``jax.random.key(policy_seed)``, uniform
    float32 over the catalog.  Threefry bits are the same on every
    backend, so this is the table the configuration states."""
    import jax

    k_p, _ = jax.random.split(jax.random.key(policy_seed))
    return np.asarray(jax.random.uniform(k_p, (n,), jax.numpy.float32))
