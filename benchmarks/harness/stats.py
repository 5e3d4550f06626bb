"""Percentile, spread and rate arithmetic — standard library only (the load
generator's process imports this and must never import jax or numpy)."""
from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics — numpy's default. None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)` —
    the number the benchmark's bounds are set from (five times the
    widest)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def token_gaps_ms(token_times: Sequence[float], w0: float,
                  w1: float) -> list:
    """Gaps between consecutive tokens of ONE request, in ms, for every
    gap whose later token arrived inside the window [w0, w1]."""
    return [(b - a) * 1e3 for a, b in zip(token_times, token_times[1:])
            if w0 <= b <= w1]


def tokens_in_window(token_times: Sequence[float], w0: float,
                     w1: float) -> int:
    return sum(1 for t in token_times if w0 <= t <= w1)
