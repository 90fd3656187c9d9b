"""A fleet of dense OGB caches (``policy: ogb``, mode ``fleet``): each
checked tenant replayed alone by ``ogb.py`` beside this file, from its own
initial state, under the fleet mode's rules for a tenant's seed and for
the tenants it checks (``bench/modes/fleet.py``).

``windows`` holds the checked tenants' windows tenant-major, in the order
of the configuration's ``checked_tenants``; tenant ``e`` is seeded
``(policy_seed + e) mod 2**31``.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from bench.harness import load_module

_ogb = load_module(Path(__file__).with_name("ogb.py"), "bench.reference.ogb")


def checked_tenants(cfg: dict) -> list:
    """The configuration's ``checked_tenants``, or the fleet mode's default
    on one device: the first and the last tenant."""
    return cfg.get("checked_tenants", list(dict.fromkeys([0, int(cfg["tenants"]) - 1])))


def replay(windows: np.ndarray, cfg: dict, policy_seed: int, dtype=np.float64) -> dict:
    tenants = checked_tenants(cfg)
    outs = [_ogb.replay(w, cfg, (policy_seed + e) % 2**31, dtype)
            for e, w in zip(tenants, np.split(windows, len(tenants)))]
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
