"""Rates and order statistics, taken over every sample of a window."""
from __future__ import annotations

from typing import Optional, Sequence

__all__ = ["quantile", "rate"]


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q`` quantile of all ``values``, linearly interpolated between
    order statistics (numpy's default method); None for no samples."""
    if not values:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    """Work per second over a whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds

