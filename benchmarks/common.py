"""Shared helpers for the paper-figure benchmarks.

Every benchmark prints a compact CSV (name,us_per_call,derived) plus a
human-readable table, and writes JSON to benchmarks/results/.  Default sizes
run in minutes on one CPU core; set REPRO_BENCH_SCALE=full for paper-scale
runs (millions of requests / items).
"""

from __future__ import annotations

import json
import os

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
SCALE = os.environ.get("REPRO_BENCH_SCALE", "quick")


def scale(quick, full):
    return full if SCALE == "full" else quick


def save_json(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    return path


def csv_row(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def make_policies(N, C, T, B=1, eta=None, zeta=None, seed=0, kinds=None):
    """The paper's host-side comparison set, tuned per theory unless
    overridden.  Every constructor goes through the one shared registry
    (:data:`repro.core.policies.POLICY_REGISTRY`) so the kind-string set
    cannot drift from ``make_policy`` / ``simulator.compare``.
    """
    from repro.core.policies import make_policy

    per_kind_kw = {
        "ogb": dict(eta=eta, horizon=None if eta else T, batch_size=B, seed=seed),
        "ogb_cl": dict(eta=eta, horizon=None if eta else T, batch_size=B, seed=seed),
        "omd_cl": dict(eta=eta, horizon=None if eta else T, batch_size=B, seed=seed),
        "ftpl": dict(zeta=zeta, horizon=None if zeta else T, seed=seed),
    }
    out = {}
    if kinds is None:
        kinds = ("ogb", "ftpl", "lru", "lfu", "arc")
    for kind in kinds:
        p = make_policy(kind, N, C, **per_kind_kw.get(kind, {}))
        out[getattr(p, "name", kind)] = p
    return out


def check_finite(payload, _path="results") -> None:
    """Fail a benchmark loudly on NaN/inf/empty/missing results (CI guard)."""
    if isinstance(payload, dict):
        if not payload:
            raise AssertionError(f"{_path}: empty result dict")
        for k, v in payload.items():
            check_finite(v, f"{_path}.{k}")
    elif isinstance(payload, (list, tuple)):
        if not payload:
            raise AssertionError(f"{_path}: empty result list")
        for i, v in enumerate(payload):
            check_finite(v, f"{_path}[{i}]")
    elif isinstance(payload, np.ndarray):
        if payload.size == 0:
            raise AssertionError(f"{_path}: empty result array")
        if np.issubdtype(payload.dtype, np.number) and not np.all(
            np.isfinite(payload)
        ):
            raise AssertionError(f"{_path}: non-finite values {payload!r}")
    elif isinstance(payload, (bool, str)):
        pass  # labels / flags are fine
    elif isinstance(payload, (int, float, np.floating, np.integer)):
        if not np.isfinite(payload):
            raise AssertionError(f"{_path}: non-finite value {payload!r}")
    else:
        # None (the canonical missing-result value) and anything exotic:
        # a guard that shrugs at these would write the bad JSON anyway
        raise AssertionError(
            f"{_path}: unexpected result type {type(payload).__name__}"
        )
