"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window (profiler trace)."""
from bench.trace import idle_percent


def value(ctx):
    return idle_percent(ctx["trace"])
