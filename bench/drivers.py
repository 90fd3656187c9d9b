"""What every traffic mode shares: the window's counts and the base of
its driver.

A mode is a file, ``bench/modes/<mode>.py``, found by the ``mode`` a mix
file names (``harness.load_mode``).  It holds ``KEYS``, the exact keys of
its mix files, and ``Driver``, a subclass of :class:`Driver` built as
``Driver(cfg, mix, seed, devices)`` with the cell's devices.  Each driver
builds its traffic from the seed, then

* ``setup()`` drives the program through its first segments (or
  decisions) with the same calls and the same feed as the window: that
  compiles or loads every shape the window uses, and what those calls
  return is what the plain reference is compared with after the window;
* ``window(seconds)`` runs the measured window and returns its counts;
* ``release()`` drops the program's state, so the reference runs on a
  device that holds nothing of it.

Host spans around each call into the program are written with
``jax.profiler.TraceAnnotation`` under ``bench.*`` names; the trace
reduction attributes device idle time to them.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from bench import traffic
from repro import policy_def, run


@dataclass
class WindowStats:
    """What one measured window did."""

    seconds: float  # first timed request to the last result on the host
    requests: int  # requests whose decisions completed in the window
    windows: int  # B-request windows replayed
    attempted: int  # requests (replay, stream) or decisions (serve) offered
    failed: int  # offered and never completed
    latencies_ms: Optional[np.ndarray] = None  # serve: one per decision
    extras: dict = field(default_factory=dict)


class Driver:
    """Shared state: configuration, mix, seeds, the cell's devices, the
    policy and its eta (None for a policy that has no learning rate)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list):
        self.cfg, self.mix, self.devices = cfg, mix, list(devices)
        self.rng, self.policy_seed = traffic.seeds(seed)
        self.n = int(cfg["catalog_size"])
        self.c = int(cfg["capacity"])
        self.b = int(cfg["window"])
        self.pd = policy_def(cfg["policy"])
        # the program's own rule for the learning rate, over the planned
        # horizon (run() alone would tune it to the first call's length)
        self.eta = None if self.pd.default_eta is None else float(
            self.pd.default_eta(self.n, self.c, int(cfg["horizon"]), self.b))
        self.cdf = traffic.zipf_cdf(self.n, float(mix["alpha"]))
        self.carry = None
        self.checked_ids: list = []
        self.checked_out = {"reward": [], "hits": [], "occupancy": []}

    def _keep(self, ids: np.ndarray, res) -> None:
        self.checked_ids.append(np.asarray(ids).reshape(-1, self.b))
        self.checked_out["reward"].append(np.asarray(res.reward, np.float64))
        self.checked_out["hits"].append(np.asarray(res.hits, np.float64))
        self.checked_out["occupancy"].append(np.asarray(res.occupancy, np.float64))

    def checked(self) -> tuple:
        """(ids (M, B), {observable: (M,)}) of what set-up produced."""
        out = {k: np.concatenate(v) for k, v in self.checked_out.items()}
        return np.concatenate(self.checked_ids), out

    def _call(self, ids: np.ndarray):
        """One ``repro.run`` over ``ids``, resuming the carry after the first."""
        if self.carry is None:
            res = run(self.pd, ids, self.n, self.c, window=self.b, eta=self.eta,
                      seed=self.policy_seed, track_opt=False)
        else:
            res = run(self.pd, ids, capacity=self.c, carry=self.carry,
                      window=self.b, track_opt=False)
        self.carry = res.carry
        return res

    def release(self) -> None:
        self.carry = None
