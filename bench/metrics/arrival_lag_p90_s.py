"""90th percentile of how late the load generator submitted a tenant
against its schedule (host clock): a starved generator shows here."""
from bench.stats import percentile


def value(ctx):
    return percentile(ctx["counters"].get("arrival_lag_s", []), 90)
