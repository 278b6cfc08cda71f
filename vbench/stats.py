"""Arithmetic on the stamps of a run: windows and percentiles.

The percentile is the linear interpolation between the two nearest
ranks (numpy's default, as the serving stack's own ``stream_summary``
uses), carried over to samples that hold infinities: a request that
failed, or was not answered in time, counts as infinitely late, and a
percentile that reaches into those ranks is infinite.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> float:
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def in_window(t: float, window: Tuple[float, float]) -> bool:
    """Half-open: a stamp at the window's close belongs to the next one."""
    return window[0] <= t < window[1]


def count_in_window(stamps: Sequence[float],
                    window: Tuple[float, float]) -> int:
    return sum(1 for t in stamps if t is not None and in_window(t, window))


def late_latencies_ms(due: Sequence[float], done: Sequence[float],
                      deadline: float) -> list:
    """From due time to answer, in ms, for requests due together; an
    answer that never came (None) or came after ``deadline`` is
    infinitely late."""
    out = []
    for d, t in zip(due, done):
        if t is None or t > deadline:
            out.append(math.inf)
        else:
            out.append((t - d) * 1e3)
    return out
