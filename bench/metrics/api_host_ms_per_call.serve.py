"""Host time per ``repro.run`` call in the serving cell: the same reading
as ``api_host_ms_per_call.replay``, moving the serving cell's latency."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("api_host_ms_per_call.replay.py"),
                   "bench_metric_api_host_ms_per_call.replay").read
