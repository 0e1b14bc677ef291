"""Results found per finished query (a per-layer metric added as a file)."""


def value(ctx):
    c = ctx["counters"]
    return c["results"] / c["queries_done"] if c["queries_done"] else None
