"""Host time per ``repro.run`` call: the benchmark's ``bench.run_call``
span less the device busy time inside it, averaged over the calls."""


def read(ctx):
    span = ctx["trace"]["spans"].get("bench.run_call")
    if not span or span["count"] == 0:
        return None
    return (span["seconds"] - span["busy_s"]) / span["count"] * 1e3
