"""Order statistics over every sample of a run."""

from __future__ import annotations

from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of all ``values``, by linear
    interpolation between order statistics (``numpy.percentile``'s
    default).  None for no values."""
    if not values:
        return None
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
