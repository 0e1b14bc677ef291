"""Queries that stopped at their result limit or frame budget in the
window, over the window's seconds (host clock, batch cells)."""


def value(ctx):
    c = ctx["counters"]
    return c["queries_done"] / c["window_s"] if c["window_s"] > 0 else None
