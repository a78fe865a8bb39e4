"""TTFT, TPOT, percentiles and rates on a synthetic client log."""

import pytest

from perfbench import clientlog


def rec(key, sched, send, events, status="ok", prompt_len=10, cls="chat"):
    tokens = [7] * sum(n for _, n in events)
    return {"key": key, "cls": cls, "prompt_len": prompt_len,
            "max_new": len(tokens), "sched_t": sched, "send_t": send,
            "first_t": events[0][0] if events else None,
            "last_t": events[-1][0] if events else None,
            "events": events, "tokens": tokens, "status": status}


LOG = [
    rec(0, 4.0, 4.001, [[4.5, 1], [5.0, 8]]),            # due before window
    rec(1, 5.0, 5.002, [[5.4, 1], [6.4, 8], [7.4, 8]]),  # ttft .4 tpot .125
    rec(2, 6.0, 6.010, [[7.0, 1]]),                      # ttft 1.0, no tpot
    rec(3, 9.0, 9.000, [[9.5, 1], [11.5, 4]]),           # ends after window
    rec(4, 9.5, 9.500, [], status="http_500"),
    rec(5, 10.0, 10.0, [[10.1, 1], [10.2, 1]]),          # due after window
]


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for p in (0, 50, 90, 95, 100):
        assert clientlog.percentile(xs, p) == pytest.approx(
            float(np.percentile(xs, p)))
    assert clientlog.percentile([], 90) is None


def test_ttft_is_from_the_scheduled_time_of_requests_due_in_the_window():
    ttft = clientlog.ttfts_ms(LOG, 5.0, 10.0)
    assert ttft == pytest.approx([400.0, 1000.0, 500.0])


def test_tpot_is_the_mean_gap_and_skips_single_tokens():
    tpot = clientlog.tpots_ms(LOG, 5.0, 10.0)
    assert tpot == pytest.approx([2000.0 / 16, 2000.0 / 4])


def test_gen_lag():
    assert clientlog.gen_lag_s(LOG[2]) == pytest.approx(0.010)


def test_tokens_completed_in_the_window():
    # generated tokens by arrival: request 0: 8 at 5.0; 1: 17; 2: 1; 3: 1
    # (4 arrive after the window); 5: none. Prompts (10 tokens each) spread
    # over send..first: 0: 4.001..4.5 lies before the window; 1, 2 and 3
    # lie inside whole; 5: 10.0..10.1 lies after it.
    assert clientlog.completed_tokens(LOG, 5.0, 10.0) == \
        pytest.approx(8 + 17 + 1 + 1 + 30)
    # half of request 2's prompt interval (6.01..7.0) lies in 6.505..7.0
    assert clientlog.completed_tokens([LOG[2]], 6.505, 6.9) == \
        pytest.approx(10 * (6.9 - 6.505) / 0.99)


def test_tail_quantile_is_a_weighted_mean_of_order_statistics():
    xs = [float(i) for i in range(1, 41)]
    hd = clientlog.tail_quantile(xs, 90)
    assert abs(hd - clientlog.percentile(xs, 90)) < 0.6
    # one neighbour of the p90 moves: the estimate moves by less
    moved = list(xs)
    moved[36] += 1.0
    assert 0 < clientlog.tail_quantile(moved, 90) - hd < 0.25
    assert clientlog.percentile(moved, 90) - clientlog.percentile(xs, 90) \
        > 0.09
    assert clientlog.tail_quantile([], 90) is None
    assert clientlog.tail_quantile([3.0], 90) == pytest.approx(3.0)


def test_slo_share_counts_failures_as_misses():
    # due in window: 1 (ok: 400 ms, 125 ms), 2 (ttft 1000), 3 (tpot 500),
    # 4 (failed)
    assert clientlog.slo_share(LOG, 5.0, 10.0, 600.0, 150.0) == 0.25
    assert clientlog.slo_share(LOG, 5.0, 10.0, 1500.0, 600.0) == 0.75
    assert clientlog.slo_share(LOG, 5.0, 10.0, 1500.0, 600.0,
                               unfinished=1) == 0.6


def test_mean_gap_weighs_requests_by_their_tokens():
    # requests 1 and 3: 2.0 s over 16 gaps and 2.0 s over 4 gaps
    assert clientlog.mean_gap_ms(LOG, 5.0, 10.0) == pytest.approx(200.0)
    assert clientlog.mean_gap_ms([LOG[2]], 5.0, 10.0) is None


def test_steadiness_counts_completions_and_who_waits():
    s = clientlog.steadiness(LOG, 5.0, 10.0)
    # completed in the window: 0, 1 and 2; the failed request waits on
    assert (s["due"], s["completed"]) == (4, 3)
    assert s["in_flight"] == [1, 0, 3]
    assert s["waiting"] == [1, 0, 2]
