"""Medians and quartiles; never a best-of."""

from __future__ import annotations

from typing import List, Optional, Sequence


def quantile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics; None when empty."""
    if not xs:
        return None
    s = sorted(xs)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(xs: Sequence[float]) -> Optional[float]:
    return quantile(xs, 0.5)


def spread(xs: List[float]) -> Optional[float]:
    """Distance between the quartiles over the median, as the driver takes it."""
    m = median(xs)
    if not m:
        return None
    return (quantile(xs, 0.75) - quantile(xs, 0.25)) / abs(m)
