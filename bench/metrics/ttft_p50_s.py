"""Median time from when a request was due to its first token, over every
request whose first token falls in the window (s)."""
from bench.stats import quantile


def read(rec):
    return quantile(rec.stats.ttft, 0.5)
