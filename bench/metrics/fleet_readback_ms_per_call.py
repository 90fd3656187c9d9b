"""Host time per ``run_fleet`` call spent in its ``repro.fleet.readback``
span: the copies of every tenant's per-window results back to the host,
after the wait for the device."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    spans = (program_trace.for_ctx(ctx, ROOT) or {}).get("program_spans", {})
    if "repro.fleet" not in spans or "repro.fleet.readback" not in spans:
        return None
    return spans["repro.fleet.readback"]["seconds"] / spans["repro.fleet"]["count"] * 1e3
