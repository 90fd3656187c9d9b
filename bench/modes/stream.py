"""Mode ``stream``: ``repro.run_stream`` over the ring in fixed-size
chunks, with its async ingest, prefetch and dynamic-OPT pass."""

from __future__ import annotations

import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import drivers, traffic
from repro import run_stream

#: the keys of a stream mix
KEYS = {"mode", "alpha", "ring_segments", "setup_segments", "chunk", "prefetch",
        "opt_window"}


class Stream(drivers.Driver):
    """``repro.run_stream`` over the ring in fixed-size chunks, with its
    async ingest and its dynamic-OPT pass.  The chunk source stops at the
    first segment boundary after the deadline, so no tail shape compiles."""

    def __init__(self, cfg, mix, seed, devices):
        super().__init__(cfg, mix, seed, devices)
        self.seg = int(cfg["segment"])
        k = int(mix["ring_segments"])
        self.ring = traffic.zipf_ids(self.cdf, k * self.seg, self.rng)
        self.pos = 0
        self.chunk = int(mix["chunk"])

    def _take(self, m: int) -> np.ndarray:
        j = self.pos % len(self.ring)
        self.pos += m
        if j + m <= len(self.ring):
            return self.ring[j:j + m]
        return np.concatenate([self.ring[j:], self.ring[:j + m - len(self.ring)]])

    def _chunks(self, total: Optional[int], deadline: Optional[float]):
        emitted = 0
        while True:
            with TraceAnnotation("bench.stream_pull"):
                into = emitted % self.seg
                late = deadline is not None and time.perf_counter() >= deadline
                if (total is not None and emitted >= total) or (late and into == 0):
                    return
                m = self.chunk
                if total is not None:
                    m = min(m, total - emitted)
                if late:
                    m = min(m, self.seg - into)
                ids = self._take(m)
            emitted += m
            yield ids

    def _stream(self, chunks):
        kw = dict(window=self.b, segment_len=self.seg, prefetch=int(self.mix["prefetch"]),
                  opt_window=int(self.mix["opt_window"]))
        if self.carry is None:
            res = run_stream(self.pd, chunks, self.n, self.c, eta=self.eta,
                             horizon=int(self.cfg["horizon"]), seed=self.policy_seed, **kw)
        else:
            res = run_stream(self.pd, chunks, capacity=self.c, carry=self.carry, **kw)
        self.carry = res.carry
        return res

    def setup(self) -> None:
        total = int(self.mix["setup_segments"]) * self.seg
        ids = self.ring[self.pos:self.pos + total]
        self._keep(ids, self._stream(self._chunks(total, None)))

    def window(self, seconds: float) -> drivers.WindowStats:
        t0 = time.perf_counter()
        res = self._stream(self._chunks(None, t0 + seconds))
        dt = time.perf_counter() - t0
        return drivers.WindowStats(
            dt, int(res.T), int(res.T) // self.b, int(res.T), 0,
            extras={"stream_host_s": res.host_seconds, "stream_wall_s": res.wall_seconds,
                    "stream_ingest_s": res.ingest_seconds, "segments": res.n_segments},
        )


Driver = Stream
