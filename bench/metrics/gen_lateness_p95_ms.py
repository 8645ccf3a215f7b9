"""95th percentile of how late the load generator submitted each request
of the window: submit time less due time, on the harness clock (ms)."""
from bench.stats import quantile


def read(rec):
    q = quantile(rec.stats.lateness, 0.95)
    return None if q is None else q * 1e3
