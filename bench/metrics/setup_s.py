"""Process start to the first timed request: imports, device start-up,
traffic generation, the program's state, compiles or cache loads, and the
set-up calls."""


def read(ctx):
    return ctx["setup_s"]
