"""Share of ``run_stream``'s wall time its main thread spends on host work
(the dynamic-OPT pass and folding results), as ``StreamResult`` reports."""


def read(ctx):
    ex = ctx["stats"].extras
    if ex["stream_wall_s"] <= 0:
        return None
    return 100.0 * ex["stream_host_s"] / ex["stream_wall_s"]
