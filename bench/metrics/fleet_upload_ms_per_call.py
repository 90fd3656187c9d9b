"""Host time per ``run_fleet`` call spent in its ``repro.fleet.upload``
spans: the host stack of every tenant's ids, their copy to the device, and
the ``device_put`` that spreads them over the tenant sharding."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    spans = (program_trace.for_ctx(ctx, ROOT) or {}).get("program_spans", {})
    if "repro.fleet" not in spans or "repro.fleet.upload" not in spans:
        return None
    return spans["repro.fleet.upload"]["seconds"] / spans["repro.fleet"]["count"] * 1e3
