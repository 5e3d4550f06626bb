"""The traffic generator's schedule and the arithmetic of the serving
metrics: same seed -> same requests and due times; every seed gets the
same set of sizes and gaps; latency is timed from the due time."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks")]

from harness import end_to_end, stats  # noqa: E402
from harness.traffic import Mix, quantiles  # noqa: E402

OPEN = {"kind": "serve", "loop": "open", "rate_per_s": 4.0, "pool": 64,
        "prompt_tokens": {"dist": "uniform", "min": 512, "max": 960},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 32},
        "sampling": {"temperature": 0.8, "top_p": 0.95}}
CLOSED = dict(OPEN, loop="closed", clients=16)
BIG_SEED = 3_000_000_019            # more than 32 signed bits hold


def test_same_seed_same_schedule():
    a, b = Mix(OPEN, BIG_SEED, 50304), Mix(OPEN, BIG_SEED, 50304)
    assert [a.due(i) for i in range(200)] == [b.due(i) for i in range(200)]
    assert [a.payload(i) for i in range(5)] == \
        [b.payload(i) for i in range(5)]


def test_other_seed_same_set_other_order():
    a, b = Mix(OPEN, 1, 50304), Mix(OPEN, BIG_SEED, 50304)
    la = [a.lengths(i) for i in range(64)]
    lb = [b.lengths(i) for i in range(64)]
    assert la != lb
    assert sorted(p for p, _ in la) == sorted(p for p, _ in lb)
    assert sorted(o for _, o in la) == sorted(o for _, o in lb)
    # a pass through the pool lasts pool / rate seconds under every seed
    assert a.due(63) == pytest.approx(16.0) and \
        b.due(63) == pytest.approx(16.0)
    assert a.due(127) == pytest.approx(32.0)
    assert a.payload(0)["input_ids"] != b.payload(0)["input_ids"]


def test_payload_is_what_the_file_says():
    m = Mix(OPEN, BIG_SEED, 50304)
    for i in range(70):
        p = m.payload(i)
        assert 512 <= len(p["input_ids"]) <= 960
        assert 8 <= p["max_new_tokens"] <= 32
        assert all(0 <= t < 50304 for t in p["input_ids"])
        assert p["stream"] is True and p["temperature"] == 0.8 \
            and p["top_p"] == 0.95 and 0 <= p["seed"] < 2 ** 31
    assert len({m.payload(i)["seed"] for i in range(70)}) == 70


def test_closed_loop_needs_no_rate_and_open_loop_does():
    assert Mix(CLOSED, 1, 1024).lengths(0)
    with pytest.raises(ValueError):
        Mix(dict(OPEN, rate_per_s=0), 1, 1024)
    # the sweep overrides the file's rate
    assert Mix(OPEN, 1, 1024, rate_per_s=8.0).due(63) == pytest.approx(8.0)


@pytest.mark.parametrize("dist,lo,hi", [("uniform", 16, 128),
                                        ("loguniform", 32, 512)])
def test_quantiles_cover_the_range_evenly(dist, lo, hi):
    q = quantiles({"dist": dist, "min": lo, "max": hi}, 64)
    assert q == sorted(q) and lo < q[0] and q[-1] < hi and len(q) == 64
    mid = (lo + hi) / 2 if dist == "uniform" else (lo * hi) ** 0.5
    assert q[31] < mid < q[32]
    with pytest.raises(ValueError):
        quantiles({"dist": "zipf", "min": lo, "max": hi}, 4)


def test_bursts_keep_the_mean_rate_and_leave_the_off_phase_empty():
    m = Mix(dict(OPEN, burst={"period_s": 4.0, "on_share": 0.25}), 7, 1024)
    dues = [m.due(i) for i in range(128)]
    assert dues == sorted(dues)
    assert all(d % 4.0 <= 1.0 + 1e-9 for d in dues)
    # two passes through the pool still take 2 * 64 / 4 seconds
    assert dues[-1] == pytest.approx(32.0)


def test_shared_prefix_groups():
    m = Mix(dict(OPEN, shared_prefix={"groups": 2, "tokens": 256}), 7, 1024)
    heads = {tuple(m.payload(i)["input_ids"][:256]) for i in range(40)}
    assert len(heads) == 2
    tails = {tuple(m.payload(i)["input_ids"][256:300]) for i in range(40)}
    assert len(tails) == 40


# ---------------------------------------------------------------- metrics
def _req(due, tokens, asked=None, **kw):
    return dict({"due": due, "sent": due, "tokens": tokens,
                 "asked": asked or len(tokens), "cut": False, "done": True,
                 "error": None, "status": 200}, **kw)


SAMPLES = [
    _req(10.0, [10.5, 10.6, 10.7, 10.9]),     # TTFT 500 ms from due
    _req(11.0, [11.1, 11.4]),                 # TTFT 100 ms
    _req(9.0, [9.9, 10.1]),                   # due before the window
    _req(19.5, []),                           # no token when it closed
]


def test_ttft_is_timed_from_the_due_time_not_the_send():
    late = _req(10.0, [10.5], sent=10.3)      # generator ran 300 ms late
    assert end_to_end.ttft_ms([late], 10.0, 20.0) == [pytest.approx(500.0)]


def test_ttft_population_is_every_request_due_in_the_window():
    got = sorted(end_to_end.ttft_ms(SAMPLES, 10.0, 20.0))
    assert got == [pytest.approx(100.0), pytest.approx(500.0),
                   pytest.approx(500.0)]      # the last: waited 0.5 s so far
    assert end_to_end.ttft_ms_p90(SAMPLES, 10.0, 20.0) == \
        pytest.approx(500.0)


def test_tokens_per_s_counts_tokens_inside_the_window_only():
    # 4 + 2 + 1 (the 10.1 of the early request) over 10 s
    assert end_to_end.serve_tokens_per_s(SAMPLES, 10.0, 20.0) == \
        pytest.approx(0.7)
    assert end_to_end.serve_tokens_per_s(SAMPLES, 30.0, 40.0) is None


def test_itl_takes_gaps_whose_later_token_is_in_the_window():
    gaps = sorted(g for r in SAMPLES
                  for g in stats.token_gaps_ms(r["tokens"], 10.0, 20.0))
    assert gaps == [pytest.approx(x) for x in (100, 100, 200, 200, 300)]
    assert end_to_end.itl_ms_p95(SAMPLES, 10.0, 20.0) == \
        pytest.approx(280.0)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6),
                                    (95, 4.8), (100, 5.0)])
def test_percentile_interpolates_like_numpy(q, want):
    import numpy as np

    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, q) == pytest.approx(want)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_edges():
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_spread_is_the_contracts():
    import statistics

    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))
    with pytest.raises(ValueError):
        stats.rate(5, 0)
