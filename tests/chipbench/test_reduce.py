"""The arithmetic from request records to end-to-end numbers, on
hand-worked records."""

import pytest

from chipbench import reduce


def rec(due, arrive, ttft_ms, latency_ms, n_out, n_prompt=10, error=None):
    return {"due": due, "sent": due, "arrive": arrive, "ttft_ms": ttft_ms,
            "latency_ms": latency_ms, "n_out": n_out, "n_prompt": n_prompt,
            "error": error}


def test_first_token_is_arrival_less_the_engines_decode_time():
    r = rec(1.0, 3.0, 500.0, 1500.0, 11)
    assert reduce.first_token_at(r) == pytest.approx(2.0)


def test_tokens_in_window_counts_the_part_inside():
    # 11 tokens evenly from t=2.0 to t=3.0: at 2.0, 2.1, ... 3.0
    r = rec(1.0, 3.0, 500.0, 1500.0, 11)
    assert reduce.tokens_in_window([r], 0.0, 10.0) == 11
    assert reduce.tokens_in_window([r], 2.45, 10.0) == 6      # 2.5 .. 3.0
    assert reduce.tokens_in_window([r], 0.0, 2.45) == 5       # 2.0 .. 2.4
    assert reduce.tokens_in_window([r], 2.15, 2.45) == 3      # 2.2 2.3 2.4
    assert reduce.serve_tok_s([r], 2.0, 3.0) == pytest.approx(10.0)


def test_edges_split_a_request_without_loss():
    r = rec(0.0, 7.3, 100.0, 5100.0, 257)
    whole = reduce.tokens_in_window([r], 0.0, 100.0)
    parts = sum(reduce.tokens_in_window([r], a, a + 1.0) for a in range(100))
    assert whole == parts == 257


def test_ttft_is_timed_from_due_and_failures_count_as_worst():
    ok = rec(10.0, 12.0, 300.0, 1300.0, 5)           # first token at 11.0
    bad = rec(11.0, 11.5, None, None, 0, error="boom")
    never = rec(12.0, None, None, None, 0)
    early = rec(5.0, 6.0, 100.0, 200.0, 2)            # due before the window
    t = reduce.ttft_ms_all([ok, bad, never, early], 10.0, 20.0)
    assert sorted(t) == pytest.approx([1000.0, 68000.0, 69000.0])


def test_tpot_is_decode_time_over_tokens_less_one():
    ok = rec(10.0, 12.0, 300.0, 1300.0, 5)
    assert reduce.tpot_ms_all([ok], 10.0, 20.0) == pytest.approx([250.0])


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert reduce.percentile(xs, 95) == 95
    assert reduce.percentile(xs, 50) == 50
    assert reduce.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        reduce.percentile([], 95)


def test_decode_tokens_and_their_context():
    # tokens 1..10 are decode steps' (token 0 is the prefill's);
    # token i attends over n_prompt + i keys
    r = rec(1.0, 3.0, 500.0, 1500.0, 11, n_prompt=100)
    n, ctx = reduce.decode_tokens_in([r], 0.0, 10.0)
    assert n == 10 and ctx == sum(100 + i for i in range(1, 11))
    n, ctx = reduce.decode_tokens_in([r], 2.45, 2.75)       # tokens 5, 6, 7
    assert n == 3 and ctx == 105 + 106 + 107
    assert reduce.prefills_in([r], 1.5, 2.5) == [100]
    assert reduce.prefills_in([r], 2.5, 3.5) == []
