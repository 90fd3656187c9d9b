"""Mode ``fleet``: closed back-to-back replay of a fleet of independent
caches through ``repro.run_fleet(carry=...)``, one call per segment.

The configuration's ``tenants`` caches each hold a catalog of
``catalog_size`` items at ``capacity``, and each replays its own
zipf(``alpha``) stream: tenant ``e``'s ids are drawn with
``traffic.zipf_ids`` from the ``e``-th generator spawned from the run's
traffic generator.  A call hands every tenant ``segment`` requests; the
fleet's tenant axis is sharded over a 1-D ``data`` mesh of the cell's
devices, one contiguous slice of tenants to each.

The rules a reference follows to replay a tenant alone, without the
program:

* tenant ``e``'s policy seed is ``(policy_seed + e) mod 2**31``, with
  ``policy_seed`` the run's (``traffic.seeds``);
* every tenant has the learning rate a single cache of the configuration
  has (none for a policy without one);
* ``checked()`` returns the set-up windows of the tenants the
  configuration's ``checked_tenants`` lists, in that order, tenant-major:
  ids ``(len(checked_tenants) * M, B)`` with ``M`` set-up windows per
  tenant, each observable flattened the same way.  Without the key, the
  first and the last tenant of each device's slice are checked
  (:func:`default_checked`), so every device's shard is compared.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from bench import drivers, traffic
from repro import run_fleet

#: the keys of a fleet mix
KEYS = {"mode", "alpha", "ring_segments", "setup_segments"}


def default_checked(tenants: int, n_devices: int) -> list:
    """The first and the last tenant of each device's contiguous slice."""
    edges = [tenants * d // n_devices for d in range(n_devices + 1)]
    ends = [t for lo, hi in zip(edges, edges[1:]) if hi > lo for t in (lo, hi - 1)]
    return list(dict.fromkeys(ends))


class Fleet(drivers.Driver):
    """Closed back-to-back replay of the fleet: one ``run_fleet`` call per
    segment of a ring of pre-generated segments, cycled while the window
    lasts.  A call counts ``tenants * segment`` requests, and
    ``WindowStats.windows`` counts tenant-windows."""

    def __init__(self, cfg, mix, seed, devices):
        super().__init__(cfg, mix, seed, devices)
        self.tenants = int(cfg["tenants"])
        self.seg = int(cfg["segment"])
        k = int(mix["ring_segments"])
        # ring[s] is segment s of every tenant, (tenants, segment)
        self.ring = np.stack(
            [traffic.zipf_ids(self.cdf, k * self.seg, g).reshape(k, self.seg)
             for g in self.rng.spawn(self.tenants)], axis=1)
        self.next = 0
        self.seeds = [(self.policy_seed + e) % 2**31 for e in range(self.tenants)]
        self.mesh = Mesh(np.asarray(self.devices), ("data",))
        self.check = [int(t) for t in cfg.get(
            "checked_tenants", default_checked(self.tenants, len(self.devices)))]
        if not self.check or not all(0 <= t < self.tenants for t in self.check):
            raise ValueError(f"checked_tenants {self.check} must name tenants of "
                             f"0..{self.tenants - 1}")

    def _segment(self) -> np.ndarray:
        ids = self.ring[self.next % len(self.ring)]
        self.next += 1
        return ids

    def _call(self, ids: np.ndarray):
        """One ``run_fleet`` over ``ids`` (tenants, segment), resuming the
        stacked carry after the first."""
        if self.carry is None:
            res = run_fleet(self.pd, ids, self.n, [self.c] * self.tenants, window=self.b,
                            seeds=self.seeds, etas=[self.eta] * self.tenants,
                            track_opt=False, mesh=self.mesh)
        else:
            res = run_fleet(self.pd, ids, window=self.b, carry=self.carry, track_opt=False,
                            mesh=self.mesh)
        self.carry = res.carry
        return res

    def setup(self) -> None:
        for _ in range(int(self.mix["setup_segments"])):
            ids = self._segment()
            res = self._call(ids)
            self.checked_ids.append(ids[self.check])
            for k, kept in self.checked_out.items():
                kept.append(np.asarray(getattr(res, k), np.float64)[self.check])

    def checked(self) -> tuple:
        """(ids (K * M, B), {observable: (K * M,)}) of the K checked tenants'
        M set-up windows each, tenant-major."""
        ids = np.concatenate(self.checked_ids, axis=1).reshape(-1, self.b)
        out = {k: np.concatenate(v, axis=1).reshape(-1) for k, v in self.checked_out.items()}
        return ids, out

    def window(self, seconds: float) -> drivers.WindowStats:
        requests = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with TraceAnnotation("bench.next_segment"):
                ids = self._segment()
            with TraceAnnotation("bench.run_call"):
                res = self._call(ids)
            with TraceAnnotation("bench.readback"):
                requests += res.n_tenants * int(res.T)
            if time.perf_counter() >= deadline:
                break
        dt = time.perf_counter() - t0
        return drivers.WindowStats(dt, requests, requests // self.b, requests, 0)


Driver = Fleet
