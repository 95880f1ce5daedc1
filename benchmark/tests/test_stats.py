import pytest

from harness import metrics as mt
from harness.load import Record
from harness.manifest import load_layer_metric
from harness.stats import mean_gap_s, percentile


def test_percentile_interpolates_between_closest_ranks():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile([4, 1, 3, 2], 50) == 2.5
    assert percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert percentile([7], 95) == 7
    with pytest.raises(ValueError):
        percentile([], 50)


def test_mean_gap_is_over_tokens_minus_one():
    assert mean_gap_s(10.0, 10.7, 8) == pytest.approx(0.1)
    assert mean_gap_s(1.0, 1.0, 1) is None


def rec(i, due, first, last, tokens, max_tokens=None, finish="length"):
    return Record(i, "window", 100, max_tokens or tokens, due=due, sent=due + 0.001,
                  first=first, last=last, done=last, tokens=tokens, finish=finish)


def test_end_to_end_metrics_on_hand_made_records():
    records = [
        rec(0, 0.0, 0.100, 1.100, 11),   # ttft 100 ms, gap 100 ms
        rec(1, 1.0, 1.300, 1.500, 5),    # ttft 300 ms, gap 50 ms
        rec(2, 2.0, 2.200, 2.200, 1),    # ttft 200 ms, one token: no gap
        rec(3, 3.0, 3.400, 3.600, 3, max_tokens=9),  # short: failed, no gap
    ]
    records[0].tokens_in_window, records[1].tokens_in_window = 7, 5
    ctx = {"records": records, "all_records": records, "seconds": 10.0, "chips": 2,
           "setup": {"setup_s": 42.0}}
    assert mt.ttft_p50_ms(ctx) == pytest.approx(250.0)
    # The tail of first token is judged in no cell since PR 34: its readers are per layer.
    for name in ("request.ttft_p95_ms", "request.ttft_p95_ms.steady"):
        assert load_layer_metric(name).read(ctx) == pytest.approx(385.0)
    assert mt.gap_p95_ms(ctx) == pytest.approx(97.5)
    # Tokens that arrived inside the window, whatever request they belong to.
    assert mt.out_tokens_per_s_chip(ctx) == pytest.approx((7 + 5) / 10.0 / 2)
    assert mt.setup_s(ctx) == 42.0
    assert mt.ttft_p50_ms({**ctx, "records": []}) is None
    late = mt.lateness_histogram(records)
    assert late["n"] == 4 and late["<=1ms"] == 4
