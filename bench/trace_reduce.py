"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The window runs from the first to the last host span whose name starts
  with ``bench.`` (the benchmark's own spans around each call into the
  program).
* Device busy time is the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:`` plane), inside
  the window, averaged over the devices that ran any.  A trace of the CPU
  backend has no device plane; its operations are the host events that
  name an HLO op.
* Per span name: how many, their seconds, and the device busy time inside
  them; a span's host time is its seconds less that busy time.
* The device operations that took most time, and the window's idle time
  split by the host span it fell in (``(no bench span)`` where none).
"""

from __future__ import annotations

import bisect
from pathlib import Path

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
NO_SPAN = "(no bench span)"
TOP = 10


def _merge(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class _Busy:
    """Merged busy intervals with prefix sums: busy time inside [a, b)."""

    def __init__(self, merged: list):
        self.iv = merged
        self.starts = [s for s, _ in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def within(self, a: float, b: float) -> float:
        if b <= a or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        j = bisect.bisect_left(self.starts, b)
        total = self.cum[j] - self.cum[i]
        s, e = self.iv[i]
        total -= max(0.0, min(e, a) - s)  # the part of the first before a
        if j > i:
            s, e = self.iv[j - 1]
            total -= max(0.0, e - max(b, s))  # the part of the last after b
        return max(total, 0.0)


def reduce_planes(planes) -> dict:
    """The reduction over planes that look like ``ProfileData.planes``."""
    planes = list(planes)
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            if ops:
                devices.append(ops)
        else:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    if not spans:
        raise ValueError("the trace holds no host span named bench.*")
    if not devices:
        # the CPU backend runs its operations on host threads (the tests'
        # traces; the benchmark itself refuses to run without a chip)
        devices = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for plane in planes if plane.name == "/host:CPU"
                    for line in plane.lines if line.name.startswith("tf_XLA")
                    for e in line.events if not e.name.startswith(("end: ", "Thread"))]]
    if not devices[0]:
        raise ValueError(f"the trace holds no device plane with an {OPS_LINE!r} line")
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    busies, op_time = [], {}
    for ops in devices:
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops if e > w0 and s < w1]
        busies.append(_Busy(_merge(clipped)))
        for name, s, e in ops:
            if e > w0 and s < w1:
                op_time[name] = op_time.get(name, 0.0) + (min(e, w1) - max(s, w0))
    k = len(busies)
    busy_ns = sum(b.cum[-1] for b in busies) / k

    per_span, idle = {}, {}
    for name, s, e in spans:
        d = per_span.setdefault(name, {"count": 0, "seconds": 0.0, "busy_s": 0.0})
        d["count"] += 1
        d["seconds"] += (e - s) * 1e-9
        inside = sum(b.within(s, e) for b in busies) / k
        d["busy_s"] += inside * 1e-9
        idle[name] = idle.get(name, 0.0) + ((e - s) - inside) * 1e-9
    covered = _Busy(_merge([(s, e) for _, s, e in spans]))
    uncovered_ns = (w1 - w0) - covered.cum[-1]
    busy_uncovered = sum(b.cum[-1] - sum(b.within(s, e) for s, e in covered.iv)
                         for b in busies) / k
    idle[NO_SPAN] = (uncovered_ns - busy_uncovered) * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((n, v) for n, v in idle.items() if v > 0), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "devices": k,
        "spans": per_span,
        "top_ops": [[n, v * 1e-9 / k] for n, v in top_ops],
        "idle_by_span": [[n, v] for n, v in gaps],
    }


def reduce_file(path) -> dict:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)


def reduce_dir(trace_dir) -> dict:
    """The reduction of the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return reduce_file(found[0])
