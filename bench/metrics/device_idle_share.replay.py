"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    red = ctx["trace"]
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
