"""Device time per window of the dense OGB's projection onto the capped
simplex: the leaf operations under the ``ogb/project`` scope."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    red, s = program_trace.for_ctx(ctx, ROOT), ctx["stats"]
    if red is None or s.windows <= 0 or "ogb/project" not in red["scopes"]:
        return None
    return red["scopes"]["ogb/project"] / s.windows * 1e6
