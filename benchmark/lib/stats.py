"""Small arithmetic the metrics share: percentiles and rates between writes."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between order
    statistics; None on no samples."""
    if len(values) == 0:
        return None
    s = sorted(float(v) for v in values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def rate_between_writes(writes: Sequence[tuple[float, float]], t_open: float,
                        t_close: float) -> Optional[float]:
    """Work per second between the first and the last sink write inside
    ``[t_open, t_close]``.

    ``writes`` are ``(time_s, work)`` pairs in time order. The work of every
    write after the first, over the time between the first and the last
    write: a window edge that falls between two writes costs nothing, and
    the first write's work (done before the window opened) is not counted.
    None when fewer than two writes fall inside the window.
    """
    inside = [(t, w) for t, w in writes if t_open <= t <= t_close]
    if len(inside) < 2:
        return None
    span = inside[-1][0] - inside[0][0]
    if span <= 0:
        return None
    return sum(w for _, w in inside[1:]) / span
