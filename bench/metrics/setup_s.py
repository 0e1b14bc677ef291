"""Process start to the window's start: data, weights of the cell's
state, compilation or cache loads, and warm-up (host clock)."""


def value(ctx):
    return ctx["setup_s"]
