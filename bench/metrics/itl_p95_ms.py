"""95th percentile of every gap between consecutive output tokens of a
request, over all gaps in the window (ms)."""
from bench.stats import quantile


def read(rec):
    q = quantile(rec.stats.gaps, 0.95)
    return None if q is None else q * 1e3
