import pytest

from benchmarks import e2e_metrics


def _rec(due, first, n, gap=0.01, status="ok"):
    return {"due": due, "status": status,
            "t_tok": [first + k * gap for k in range(n)]}


def test_arithmetic_on_hand_made_records():
    recs = [_rec(0.0, 0.1, 11), _rec(1.0, 1.3, 21), _rec(2.0, 2.2, 1)]
    m = e2e_metrics.summarize(recs, 10.0, 1, miss_s=22.0)
    assert m["attempted"] == 3 and m["failed"] == 0
    assert m["ttft_ms_p50"] == pytest.approx(200.0)
    assert m["ttft_ms_mean"] == pytest.approx(200.0)
    assert m["tpot_ms"] == pytest.approx(10.0)
    assert m["tok_s_per_chip"] == pytest.approx(33 / 10.0)


def test_a_stalled_stream_moves_tpot():
    base = [_rec(0.0, 0.1, 11), _rec(1.0, 1.3, 21)]
    stalled = [_rec(0.0, 0.1, 11), _rec(1.0, 1.3, 21)]
    stalled[1]["t_tok"] = [t + (0.5 if k >= 10 else 0.0)
                           for k, t in enumerate(stalled[1]["t_tok"])]
    a = e2e_metrics.summarize(base, 10.0, 1, 22.0)["tpot_ms"]
    b = e2e_metrics.summarize(stalled, 10.0, 1, 22.0)["tpot_ms"]
    assert b == pytest.approx(a + 500.0 / 30)


def test_a_cancelled_request_counts_in_failed_and_misses():
    recs = [_rec(0.0, 0.1, 5), _rec(0.5, 0.0, 0, status="timeout"),
            _rec(1.0, 1.2, 3, status="timeout")]
    m = e2e_metrics.summarize(recs, 10.0, 1, miss_s=22.0)
    assert m["attempted"] == 3 and m["failed"] == 2
    # the one that never showed a token waited its whole deadline
    assert sorted(e2e_metrics.ttft_ms(recs, 22.0))[-1] == pytest.approx(22000.0)
    # tokens that reached a client count even where the stream failed
    assert m["tok_s_per_chip"] == pytest.approx(8 / 10.0)
    # the mean time to first token carries the miss
    assert m["ttft_ms_mean"] == pytest.approx((100.0 + 22000.0 + 200.0) / 3)
    assert m["tpot_ms"] == pytest.approx(10.0)


def test_tokens_after_the_window_do_not_count():
    recs = [_rec(9.9, 9.955, 20, gap=0.01)]
    m = e2e_metrics.summarize(recs, 10.0, 1, 22.0)
    # five arrive inside; the sixth, due at 10.005 after 9.995, is half inside
    assert m["tok_s_per_chip"] == pytest.approx(5.5 / 10.0)


def test_stream_time_is_taken_inside_the_window():
    # 5 ms a token inside the window, 50 ms in the drain behind it
    t = [9.0 + 0.005 * k for k in range(200)] + [10.0 + 0.05 * k for k in range(1, 20)]
    m = e2e_metrics.summarize([{"due": 8.9, "status": "ok", "t_tok": t}], 10.0, 1, 22.0)
    assert m["tpot_ms"] == pytest.approx(5.0)
    # one request more, due at the window's very end and served in the drain,
    # moves the judged numbers by its share of the mean and no further
    late = {"due": 9.99, "status": "ok", "t_tok": [10.4 + 0.05 * k for k in range(30)]}
    m2 = e2e_metrics.summarize([{"due": 8.9, "status": "ok", "t_tok": t}, late],
                               10.0, 1, 22.0)
    assert m2["tpot_ms"] == pytest.approx(5.0)
    assert m2["tok_s_per_chip"] == pytest.approx(m["tok_s_per_chip"], rel=2e-3)
    assert m2["ttft_ms_mean"] == pytest.approx((100.0 + 410.0) / 2)


def _bursty(due, first, bursts, period, k=8):
    t = [first + b * period for b in range(bursts) for _ in range(k)]
    return {"due": due, "status": "ok", "t_tok": t}


def test_a_burst_across_the_windows_end_counts_by_its_share():
    # bursts of 8 at 1.0, 2.0, ...: the one at 10.0 is inside, the one at
    # 11.0 not at all in a window of 10 s, and a quarter in one of 10.25 s
    rec = _bursty(0.0, 1.0, 12, 1.0)
    assert e2e_metrics.tokens_inside(rec, 10.0) == pytest.approx(80.0)
    assert e2e_metrics.tokens_inside(rec, 10.25) == pytest.approx(82.0)
    # a stream that stalls before the window's end earns nothing for it
    stalled = {"due": 0.0, "status": "timeout", "t_tok": [1.0] * 8}
    assert e2e_metrics.tokens_inside(stalled, 10.0) == pytest.approx(8.0)
    # frames of one token sent back to back are one burst
    ragged = dict(rec, t_tok=[t + 1e-4 * (i % 8) for i, t in enumerate(rec["t_tok"])])
    assert e2e_metrics.tokens_inside(ragged, 10.25) == pytest.approx(82.0, rel=1e-3)


def test_throughput_moves_with_the_speed_and_not_in_jumps():
    def rate(period):
        recs = [_bursty(0.0, 0.3 + 0.2 * s, 80, period) for s in range(4)]
        return e2e_metrics.summarize(recs, 50.0, 1, 22.0)["tok_s_per_chip"]

    base = rate(0.8)
    for faster in (0.002, 0.005, 0.01, 0.02):
        gain = rate(0.8 * (1 - faster)) / base - 1
        assert gain == pytest.approx(faster, rel=0.2), (faster, gain)
