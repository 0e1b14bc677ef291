"""Detection-cache hits over lookups (hits + fresh detector calls) in the
window, from ``SearchStats``."""


def value(ctx):
    c = ctx["counters"]
    total = c.get("cache_hits", 0) + c.get("detector_invocations", 0)
    return 100.0 * c["cache_hits"] / total if total else None
