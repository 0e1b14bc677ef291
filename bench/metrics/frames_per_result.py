"""Frames sampled per result over the window (the paper's cost measure,
as a count; ``SearchStats.frames_sampled`` over the results)."""


def value(ctx):
    c = ctx["counters"]
    return c["frames_sampled"] / c["results"] if c.get("results") else None
