"""Metric arithmetic on hand-made records."""

import pytest

from perfbench.harness import stats


def rec(sent, deliveries, done=None, stop="max_tokens", error=None,
        budget=None, due=None, prompt_len=10):
    n = sum(k for _, k in deliveries)
    return {"index": 0, "session": 0, "due": due, "sent": sent,
            "deliveries": [list(d) for d in deliveries], "done": done,
            "stop": stop, "error": error,
            "budget": n if budget is None else budget,
            "prompt_len": prompt_len}


RECORDS = [
    # in flight at the window's start: only in-window deliveries count
    rec(8.0, [(9.0, 1), (9.8, 16), (10.6, 16), (11.4, 16)], done=11.5),
    # inside the window
    rec(11.0, [(12.0, 1), (13.6, 16), (15.2, 8)], done=15.3),
    # cut by the window's end: stop is None, not an early stop
    rec(18.0, [(19.0, 1), (19.8, 16), (20.6, 16)], done=20.7, stop=None,
        budget=64),
    # failed: counts no tokens, and the timeout as its TTFT
    rec(12.0, [(12.5, 1)], done=13.0, error="StageExecutionError: SlotFull",
        stop=None, budget=32),
    # ended before its budget by the repeat stop
    rec(14.0, [(14.4, 1), (15.0, 4)], done=15.1, stop="repeat", budget=40),
    # over before the window: not attempted
    rec(1.0, [(2.0, 1)], done=2.1),
]
W0, W1 = 10.0, 20.0


def test_tokens_per_s_counts_in_window_deliveries_only():
    # 16+16 | 1+16+8 | 1+16 | (failed: 0) | 1+4
    assert stats.delivered_tokens(RECORDS, W0, W1) == 32 + 25 + 17 + 5
    assert stats.tokens_per_s(RECORDS, W0, W1) == pytest.approx(7.9)


def test_gap_samples_are_per_token_and_skip_first_deliveries():
    gaps = sorted(stats.gap_samples_ms(RECORDS, W0, W1))
    want = sorted([800 / 16, 800 / 16, 1600 / 16, 1600 / 8, 800 / 16,
                   600 / 4])
    assert gaps == pytest.approx(want)
    assert stats.percentile(gaps, 95) == pytest.approx(200.0)
    assert stats.percentile(gaps, 50) == pytest.approx(50.0)


@pytest.mark.parametrize("records, w0, w1, want", [
    # every in-window gap by its tokens: 6200 ms waited for 76 tokens
    (RECORDS, W0, W1, 6200 / 76),
    # a 16-token burst and a 4-token one weigh 4 : 1, not 1 : 1
    ([rec(0, [(1.0, 1), (1.2, 16), (1.3, 4)])], 0, 2, 300 / 20),
    # a gap counts where its delivery lands, whole, as in gap_samples_ms
    ([rec(0, [(0.5, 1), (1.5, 16), (2.5, 16)])], 1, 2, 1000 / 16),
    # first deliveries and failed requests give no gap: nothing to read
    ([rec(0, [(1.0, 1)]), rec(0, [(1.0, 1), (1.5, 16)], error="E: x")],
     0, 2, None),
], ids=["records", "token-weighted", "window-edge", "nothing"])
def test_gap_mean_is_time_waited_over_tokens_delivered(records, w0, w1, want):
    got = stats.gap_mean_ms(records, w0, w1)
    assert got == (pytest.approx(want) if want is not None else None)
    assert stats.summarize(records, w0, w1, 120)["gap_mean_ms"] == got
    gaps = stats.gap_samples_ms(records, w0, w1)
    if gaps:        # a mean of the same samples: between their extremes
        assert min(gaps) - 1e-9 <= got <= max(gaps) + 1e-9


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_ttft_over_requests_sent_in_window_failed_counts_timeout():
    got = sorted(stats.ttft_samples_ms(RECORDS, W0, W1, timeout_s=120))
    assert got == pytest.approx(sorted([1000.0, 1000.0, 120000.0, 400.0]))
    late = [rec(5.5, [(6.0, 1)], due=5.0)]
    assert stats.ttft_samples_ms(late, 5, 7, 120) == pytest.approx([1000.0])
    assert stats.lateness_ms(late) == pytest.approx([500.0])


def test_request_counts():
    c = stats.request_counts(RECORDS, W0, W1)
    assert c == {"attempted": 5, "failed": 1,
                 "causes": {"StageExecutionError": 1}, "finished": 3,
                 "stopped_early": 1}


def test_summarize_and_rows_in_use():
    s = stats.summarize(RECORDS, W0, W1, 120)
    assert s["gap_samples"] == 6 and s["ttft_samples"] == 4
    assert s["gap_p95_ms"] == pytest.approx(200.0)
    assert s["gap_p75_ms"] == pytest.approx(150.0)
    assert s["gap_p50_ms"] == pytest.approx(50.0)
    assert s["tokens_per_s"] == pytest.approx(7.9)
    rows = stats.ctx_rows_in_use([rec(0, [(1, 1), (2, 16)], prompt_len=100)],
                                 0, 3)
    assert rows == pytest.approx((101 + 117) / 2)


def test_spread_is_the_contracts_and_drops_the_farthest_run():
    runs = [100.0, 101.0, 102.0, 103.0, 104.0, 130.0]
    assert stats.spread(runs) == pytest.approx(
        (110.5 - 100.75) / 102.5)                  # statistics.quantiles
    assert stats.spread_dropping_farthest(runs) == pytest.approx(3 / 102)
    tight = [10.0, 10.1, 10.2]
    assert stats.spread_dropping_farthest(tight) <= stats.spread(tight)
