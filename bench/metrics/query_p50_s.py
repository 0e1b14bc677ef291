"""Median, over every tenant submitted in the window, of its finish stamp
minus its scheduled arrival (host clock, service cells)."""
from bench.stats import percentile


def value(ctx):
    return percentile(ctx["counters"].get("latency_s", []), 50)
