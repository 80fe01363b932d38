"""Window arithmetic: percentiles and rates over a whole measured window.

Tails are taken over every sample (every request due in the window, every gap
between two tokens), never as medians of chunks; rates divide all the work
done in the window by all of its time.
"""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """Linear-interpolated percentile (``q`` in [0, 100]); NaN when empty.

    A sample of ``math.inf`` (a request never served) counts as missing every
    limit: it sorts above every finite sample.
    """
    samples = sorted(samples)
    if not samples:
        return float("nan")
    pos = (len(samples) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(samples) - 1)
    a, b = samples[lo], samples[hi]
    if pos == lo or a == b:
        return float(a)
    return float(a + (b - a) * (pos - lo))


def rate(work: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return work / seconds


def interval_union(intervals) -> float:
    """Total length covered by a set of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
