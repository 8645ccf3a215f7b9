"""Output tokens delivered in the window, over the window (tokens/s)."""

from bench.stats import rate


def read(rec):
    return rate(rec.stats.tokens, rec.window_s)
