"""90th percentile of a tenant's admission stamp (the service's
``admitted_s``) minus its scheduled arrival."""
from bench.stats import percentile


def value(ctx):
    return percentile(ctx["counters"].get("admission_wait_s", []), 90)
