"""Device busy time in the traced window per window of B requests replayed
in it: the engine's time per step, whatever kernels it runs."""


def read(ctx):
    red, s = ctx["trace"], ctx["stats"]
    if s.windows <= 0 or red["busy_s"] <= 0:
        return None
    return red["busy_s"] / s.windows * 1e6
