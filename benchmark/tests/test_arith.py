"""Metric arithmetic on synthetic records that straddle the window edges.
Neither rate divides a count of whole steps or requests by --seconds;
`serve_rate`, beside `serve_tok_s`, does not divide whole prompts either."""

import pytest

from benchlib import arith, prom


def test_train_tok_s_times_whole_steps_not_the_window():
    step = 1.5568                         # PR 22's step time, seconds
    ends = [10.0 + i * step for i in range(40)]
    for t0, secs in ((10.3, 20.0), (11.0, 20.0), (10.3, 20.5), (12.9, 45.0)):
        got = arith.train_tok_s(ends, 8192, t0, t0 + secs)
        assert got == pytest.approx(8192 / step, rel=1e-9)
    # What PR 22 did: steps / window, two values some 8 % apart.
    naive = {round(sum(t0 <= e <= t0 + 20 for e in ends) * 8192 / 20.0)
             for t0 in (10.1, 10.3, 11.0, 11.4)}
    assert len(naive) == 2 and max(naive) / min(naive) > 1.07


def test_train_tok_s_leaves_out_steps_cut_by_an_edge():
    ends = [0.0, 1.0, 2.0, 4.0, 5.0]      # one slow step, 2 -> 4
    n, span = arith.whole_steps(ends, 0.5, 4.5)
    assert (n, span) == (2, 3.0)          # steps 1->2 and 2->4 only
    assert arith.train_tok_s(ends, 100, 0.5, 4.5) == pytest.approx(200 / 3.0)
    with pytest.raises(ValueError):
        arith.train_tok_s(ends, 100, 2.1, 3.9)


def rec(due, times, prompt=100, ok=True):
    return {"due_t": due, "token_times": times, "prompt_tokens": prompt,
            "ok": ok}


def test_serve_tok_s_credits_tokens_at_their_arrival():
    records = [
        rec(0.0, [0.5, 0.9, 1.1, 1.1, 1.1], prompt=1000),  # first token early
        rec(0.8, [1.2, 1.5, 1.5, 2.5], prompt=400),        # straddles the end
        rec(1.4, [1.7, 1.8], prompt=300),                  # all inside
        rec(1.9, [2.1, 2.2], prompt=700),                  # all after
    ]
    # The instants inside [1, 2) are 1.2 and 1.7; counted in (1.2, 1.7] are
    # 2 tokens of the second, and prompt 300 + 1 token of the third.
    rate = arith.serve_rate(records, 1.0, 2.0)
    assert rate["tok_s"] == pytest.approx((2 + 300 + 1) / 0.5)
    assert (rate["instants"], rate["tokens"]) == (2, 303)
    assert rate["span_s"] == pytest.approx(0.5)
    assert rate["largest_credit_share"] == pytest.approx(300 / 303)
    # A wider window counts (0.5, 2.1]: every prompt but the first.
    assert arith.serve_rate(records, 0.0, 3.0)["tok_s"] == pytest.approx(
        (400 + 300 + 700 + 4 + 3 + 2 + 1) / 1.6)
    # The metric, by the window: 3 tokens of the first, prompt 400 + 3
    # tokens of the second, prompt 300 + 2 of the third, over 1 s.
    assert arith.serve_tok_s(records, 1.0, 2.0) == pytest.approx(
        3 + 400 + 3 + 300 + 2)
    # Whole finished requests / window would have read (1005 + 302) or so.
    assert arith.serve_tok_s(records, 0.0, 3.0) == pytest.approx(
        (1000 + 400 + 300 + 700 + 5 + 4 + 2 + 2) / 3.0)


def long_prompts(n=40, every=1.5568, prompt=10_000, out=32):
    """One slot's worth of a long-document server: a first token every
    `every` seconds, then `out` - 1 more tokens in chunks of 8."""
    return [rec(10.0 + i * every - 1.0,
                [10.0 + i * every + 0.1 * ((k + 7) // 8) for k in range(out)],
                prompt=prompt) for i in range(n)]


def test_serve_rate_counts_whole_prompts_between_events_not_the_window():
    every, records = 1.5568, long_prompts()
    for t0, secs in ((10.3, 20.0), (11.0, 20.0), (10.3, 20.5), (12.9, 45.0)):
        rate = arith.serve_rate(records, t0, t0 + secs)
        assert rate["tok_s"] == pytest.approx(10_032 / every, rel=1e-9)
        assert rate["instants"] in (13, 14, 29)
        assert rate["largest_credit_share"] == pytest.approx(
            10_000 / 10_032 / (rate["instants"] - 1))
    # Tokens over the window's length: it holds 12 or 13 such prompts,
    # never 12.85, so the reading takes two values some 8 % apart.
    naive = {round(arith.serve_tok_s(records, t0, t0 + 20.0), -1)
             for t0 in (10.1, 10.3, 11.0, 11.4)}
    assert len(naive) == 2 and max(naive) / min(naive) > 1.07


def test_first_tokens_of_one_dispatch_are_one_instant():
    # Two requests prefilled in one dispatch: first tokens 0.8 ms apart.
    pair = [rec(0.0, [1.0000, 1.3], prompt=500),
            rec(0.0, [1.0008, 1.3], prompt=700)]
    later = [rec(0.5, [2.0, 2.2], prompt=900), rec(0.6, [2.0004], prompt=100),
             rec(1.5, [3.0], prompt=300)]
    assert arith.first_token_instants(pair + later, 0.0, 4.0) == [
        1.0008, 2.0004, 3.0]
    rate = arith.serve_rate(pair + later, 0.0, 4.0)
    # Neither prompt of the first dispatch counts, both of the second do.
    assert rate["tokens"] == (2 + 900 + 2 + 100 + 1) + (300 + 1)
    assert rate["tok_s"] == pytest.approx(rate["tokens"] / (3.0 - 1.0008))
    assert rate["largest_credit_share"] == pytest.approx(1000 / 1306)
    # A dispatch whose first tokens straddle an edge is in or out whole,
    # by the last of them.
    assert arith.first_token_instants(pair + later, 1.0004, 2.0002) == [
        1.0008]
    assert arith.first_token_instants(pair + later, 0.0, 1.0004) == []
    assert arith.SAME_DISPATCH_S == pytest.approx(0.010)


def test_one_instant_inside_the_window_is_an_error():
    records = long_prompts(n=3)
    with pytest.raises(ValueError, match="fewer than two"):
        arith.serve_rate(records, 10.5, 12.0)      # holds one first token
    with pytest.raises(ValueError):
        arith.serve_rate([rec(0.0, [])], 0.0, 10.0)
    assert arith.serve_rate(records, 10.5, 13.2)["instants"] == 2
    # The metric by the window needs no event: it reads the one prompt.
    assert arith.serve_tok_s(records, 10.5, 12.0) == pytest.approx(
        (10_000 + 32) / 1.5)


def test_ttft_from_due_and_failures_count_as_missing():
    records = [rec(1.0, [1.1]), rec(2.0, [2.3]), rec(3.0, [3.2])]
    assert arith.ttft_ms(records, 50) == pytest.approx(200.0)
    records.append(rec(4.0, [], ok=False))
    records.append(rec(5.0, [5.1], ok=False))
    assert arith.ttft_ms(records, 95) == float("inf")
    assert arith.ttft_ms(records, 50) == pytest.approx(300.0)


def test_tpot_is_per_request_not_per_gap():
    chunked = rec(0.0, [1.0] * 8 + [1.12] * 8 + [1.24] * 8)   # chunks of 8
    assert arith.tpot_ms([chunked], 90) == pytest.approx(240.0 / 23)
    single = rec(0.0, [1.0])
    assert arith.tpot_ms([chunked, single], 50) == pytest.approx(240.0 / 23)


def test_spread_is_the_contracts():
    vals = [100, 101, 102, 103, 104, 110]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert arith.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_prom_delta_of_sums_and_counts():
    a = prom.parse('x_sum{v="1"} 1.0\nx_sum{v="2"} 2.0\nx_count{v="1"} 4\n'
                   'x_count{v="2"} 6\nx_bucket{le="1"} 9\n# HELP x\n')
    b = prom.parse('x_sum{v="1"} 2.0\nx_sum{v="2"} 5.0\nx_count{v="1"} 6\n'
                   'x_count{v="2"} 10\n')
    d = prom.delta(b, a)
    assert "x_bucket" not in a
    assert prom.mean_ms(d, "x") == pytest.approx(4.0 / 6 * 1e3)
    assert prom.mean_ms(d, "x", per=8) == pytest.approx(4.0 / 48 * 1e3)
    assert prom.mean_ms(prom.delta(a, a), "x") is None
