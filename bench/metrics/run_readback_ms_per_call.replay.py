"""Host time per ``repro.run`` call spent in its ``repro.run.readback``
span: the copies of the per-window results back to the host, after the
wait for the device."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    spans = (program_trace.for_ctx(ctx, ROOT) or {}).get("program_spans", {})
    if "repro.run" not in spans or "repro.run.readback" not in spans:
        return None
    return spans["repro.run.readback"]["seconds"] / spans["repro.run"]["count"] * 1e3
