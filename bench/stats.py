"""Percentile and rate arithmetic of the end-to-end metrics."""

from __future__ import annotations


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile of a non-empty sample: the element at rank
    ``round(q * (n - 1))`` of the sorted sample (the serving layer's
    convention)."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    xs = sorted(xs)
    return xs[min(len(xs) - 1, max(0, round(q * (len(xs) - 1))))]


def rate(count: float, seconds: float) -> float:
    """Work per second over a window: all of the work, all of the time."""
    if seconds <= 0:
        raise ValueError(f"a rate needs a window longer than 0 s, got {seconds}")
    return count / seconds

