"""The serving cells' end-to-end metrics, from the load generator's
samples: one function per metric of BENCHMARK.json's `end_to_end`, found
by the metric's name. Each takes (samples, w0, w1) — the requests as the
client saw them and the window on the shared monotonic clock — and gives
the value, or None where the window holds nothing to read it from.
"""
from __future__ import annotations

from typing import Optional

from . import stats


def serve_tokens_per_s(samples, w0: float, w1: float) -> Optional[float]:
    """Output tokens that reached the clients inside the window, over the
    window."""
    n = sum(stats.tokens_in_window(r["tokens"], w0, w1) for r in samples)
    return stats.rate(n, w1 - w0) if n else None


def itl_ms_p95(samples, w0: float, w1: float) -> Optional[float]:
    """95th percentile of the gaps between consecutive streamed tokens of
    one request, over all requests of the window."""
    gaps = [g for r in samples
            for g in stats.token_gaps_ms(r["tokens"], w0, w1)]
    return stats.percentile(gaps, 95)


def ttft_ms(samples, w0: float, w1: float) -> list:
    """Due time to first streamed token, for every request due inside
    the window. One that had no token yet when the window closed counts
    with the wait it had by then (a lower bound; none at a sustained
    rate)."""
    return [((r["tokens"][0] if r["tokens"] else w1) - r["due"]) * 1e3
            for r in samples if w0 <= r["due"] <= w1]


def ttft_ms_p90(samples, w0: float, w1: float) -> Optional[float]:
    return stats.percentile(ttft_ms(samples, w0, w1), 90)


METRICS = {f.__name__: f for f in (serve_tokens_per_s, itl_ms_p95,
                                   ttft_ms_p90)}
