"""Least work of a kernel, from its shapes, and the chip's peaks.

The peaks come from ``bench/peaks.json``, keyed by the ``device_kind`` JAX
reports, with their source; a device that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dense_step_bytes(catalog_size: int, window: int) -> int:
    """Least HBM bytes of one dense OGB step over a window: the float32
    state read and written once (8 N) and the window's int32 ids (4 B).
    The permanent random numbers, the projection's extra sweeps and the
    gather of requested entries are left out, so the time these bytes take
    at peak bandwidth is a lower bound of the step's time."""
    return 8 * int(catalog_size) + 4 * int(window)
