"""Mode ``serve``: open-loop serving, one window of ids per decision, at
the mix's fixed Poisson rate."""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import drivers, traffic

#: the keys of a serve mix
KEYS = {"mode", "alpha", "ring_decisions", "setup_decisions", "rate_per_s", "drain_s"}


class Serve(drivers.Driver):
    """Open loop: decisions arrive as a Poisson process at the mix's fixed
    rate, each one window of ids served by one ``repro.run(carry=...)``
    call, in arrival order.  A decision's latency runs from its due time to
    its result on the host, so queueing behind a slow decision counts."""

    def __init__(self, cfg, mix, seed, devices):
        super().__init__(cfg, mix, seed, devices)
        k = int(mix["ring_decisions"])
        self.ring = traffic.zipf_ids(self.cdf, k * self.b, self.rng).reshape(k, self.b)
        self.rate = float(mix["rate_per_s"])
        self.next = 0

    def _decision(self) -> np.ndarray:
        ids = self.ring[self.next % len(self.ring)]
        self.next += 1
        return ids

    def setup(self) -> None:
        for _ in range(int(self.mix["setup_decisions"])):
            ids = self._decision()
            self._keep(ids, self._call(ids))

    def window(self, seconds: float) -> drivers.WindowStats:
        count = max(1, int(round(self.rate * seconds)))
        due = np.cumsum(traffic.exp_gaps(count, self.rate, self.rng))
        lat = np.full(count, np.inf)
        overshoot = []
        t0 = time.perf_counter()
        give_up = t0 + due[-1] + float(self.mix["drain_s"])
        served = 0
        for i in range(count):
            at = t0 + due[i]
            now = time.perf_counter()
            if now >= give_up:
                break
            if now < at:
                with TraceAnnotation("bench.wait_arrival"):
                    if at - now > 1e-3:
                        time.sleep(at - now - 5e-4)
                    while time.perf_counter() < at:
                        pass
                overshoot.append(time.perf_counter() - at)
            with TraceAnnotation("bench.next_segment"):
                ids = self._decision()
            with TraceAnnotation("bench.run_call"):
                self._call(ids)
            with TraceAnnotation("bench.readback"):
                lat[i] = (time.perf_counter() - at) * 1e3
            served += 1
        dt = time.perf_counter() - t0
        over = np.asarray(overshoot) * 1e3
        return drivers.WindowStats(
            dt, served * self.b, served, count, count - served, latencies_ms=lat,
            extras={"wake_late_p99_ms": float(np.percentile(over, 99)) if over.size else 0.0,
                    "wake_late_max_ms": float(over.max()) if over.size else 0.0,
                    "offered_per_s": self.rate},
        )


Driver = Serve
