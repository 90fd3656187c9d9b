"""Share of the traced window in which no operation ran on the device, in
the open-loop serving cell (waiting for arrivals included): the same
reading as ``device_idle_share.replay``, moving the serving cell's latency."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("device_idle_share.replay.py"),
                   "bench_metric_device_idle_share.replay").read
