"""Mode ``replay``: closed back-to-back replay of one cache through
``repro.run(carry=...)``, one call per segment of ``segment`` requests."""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import drivers, traffic

#: the keys of a replay mix
KEYS = {"mode", "alpha", "ring_segments", "setup_segments"}


class Replay(drivers.Driver):
    """Closed back-to-back replay: one ``repro.run(carry=...)`` per segment
    of a ring of pre-generated segments, cycled while the window lasts."""

    def __init__(self, cfg, mix, seed, devices):
        super().__init__(cfg, mix, seed, devices)
        self.seg = int(cfg["segment"])
        k = int(mix["ring_segments"])
        self.ring = traffic.zipf_ids(self.cdf, k * self.seg, self.rng).reshape(k, self.seg)
        self.next = 0

    def _segment(self) -> np.ndarray:
        ids = self.ring[self.next % len(self.ring)]
        self.next += 1
        return ids

    def setup(self) -> None:
        for _ in range(int(self.mix["setup_segments"])):
            ids = self._segment()
            self._keep(ids, self._call(ids))

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
                requests += int(res.T)
            if time.perf_counter() >= deadline:
                break
        dt = time.perf_counter() - t0
        return drivers.WindowStats(dt, requests, requests // self.b, requests, 0)


Driver = Replay
