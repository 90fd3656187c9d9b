"""Share of ``run_stream``'s time in the traced window that its main
thread spends waiting for the ingest thread's next segment (its
``repro.stream.queue_wait`` spans over its ``repro.stream`` span)."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    spans = (program_trace.for_ctx(ctx, ROOT) or {}).get("program_spans", {})
    stream, wait = spans.get("repro.stream"), spans.get("repro.stream.queue_wait")
    if not stream or not wait or stream["seconds"] <= 0:
        return None
    return 100.0 * wait["seconds"] / stream["seconds"]
