"""Host time per ``repro.run`` call spent in its ``repro.run.upload``
span: the reshape of the segment into windows and its copy to the
device."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    spans = (program_trace.for_ctx(ctx, ROOT) or {}).get("program_spans", {})
    if "repro.run" not in spans or "repro.run.upload" not in spans:
        return None
    return spans["repro.run.upload"]["seconds"] / spans["repro.run"]["count"] * 1e3
