"""Device time per window of the lazy OGB's threshold solve: the leaf
operations under the ``ogb_tree/solve`` scope (the bucket total and the
bisection over the histogram)."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    red, s = program_trace.for_ctx(ctx, ROOT), ctx["stats"]
    if red is None or s.windows <= 0 or "ogb_tree/solve" not in red["scopes"]:
        return None
    return red["scopes"]["ogb_tree/solve"] / s.windows * 1e6
