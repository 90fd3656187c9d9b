"""The three ways a traffic mix drives the program, one class per ``mode``.

Each driver builds its traffic from the seed, then

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

import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import traffic
from repro import policy_def, run, run_stream


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
    """Shared state: configuration, mix, seeds, the policy and its eta."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.rng, self.policy_seed = traffic.seeds(seed)
        self.n = int(cfg["catalog_size"])
        self.c = int(cfg["capacity"])
        self.b = int(cfg["window"])
        self.pd = policy_def(cfg["policy"])
        # the program's own rule for the learning rate, over the planned
        # horizon (run() alone would tune it to the first call's length)
        self.eta = float(self.pd.default_eta(self.n, self.c, int(cfg["horizon"]), self.b))
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


class Replay(Driver):
    """Closed back-to-back replay: one ``repro.run(carry=...)`` per segment
    of a ring of pre-generated segments, cycled while the window lasts."""

    def __init__(self, cfg, mix, seed):
        super().__init__(cfg, mix, seed)
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

    def window(self, seconds: float) -> WindowStats:
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
        return WindowStats(dt, requests, requests // self.b, requests, 0)


class Stream(Driver):
    """``repro.run_stream`` over the ring in fixed-size chunks, with its
    async ingest and its dynamic-OPT pass.  The chunk source stops at the
    first segment boundary after the deadline, so no tail shape compiles."""

    def __init__(self, cfg, mix, seed):
        super().__init__(cfg, mix, seed)
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

    def window(self, seconds: float) -> WindowStats:
        t0 = time.perf_counter()
        res = self._stream(self._chunks(None, t0 + seconds))
        dt = time.perf_counter() - t0
        return WindowStats(
            dt, int(res.T), int(res.T) // self.b, int(res.T), 0,
            extras={"stream_host_s": res.host_seconds, "stream_wall_s": res.wall_seconds,
                    "stream_ingest_s": res.ingest_seconds, "segments": res.n_segments},
        )


class Serve(Driver):
    """Open loop: decisions arrive as a Poisson process at the mix's fixed
    rate, each one window of ids served by one ``repro.run(carry=...)``
    call, in arrival order.  A decision's latency runs from its due time to
    its result on the host, so queueing behind a slow decision counts."""

    def __init__(self, cfg, mix, seed):
        super().__init__(cfg, mix, seed)
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

    def window(self, seconds: float) -> WindowStats:
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
        return WindowStats(
            dt, served * self.b, served, count, count - served, latencies_ms=lat,
            extras={"wake_late_p99_ms": float(np.percentile(over, 99)) if over.size else 0.0,
                    "wake_late_max_ms": float(over.max()) if over.size else 0.0,
                    "offered_per_s": self.rate},
        )


DRIVERS = {"replay": Replay, "stream": Stream, "serve": Serve}
