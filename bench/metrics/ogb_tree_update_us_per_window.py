"""Device time per window of the lazy OGB's bucket-tree updates: the leaf
operations under the ``ogb_tree/update`` scope (moving each touched item
between buckets in the count, sum and sample trees)."""

from pathlib import Path

from bench import program_trace

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    red, s = program_trace.for_ctx(ctx, ROOT), ctx["stats"]
    if red is None or s.windows <= 0 or "ogb_tree/update" not in red["scopes"]:
        return None
    return red["scopes"]["ogb_tree/update"] / s.windows * 1e6
