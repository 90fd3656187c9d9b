"""Requests whose decisions completed in the window, over the window's
seconds on the host clock (first timed request to the last result back)."""


def read(ctx):
    s = ctx["stats"]
    if s.seconds <= 0:
        return None
    return s.requests / s.seconds
