"""Median time to first token in chat-steady: there the median swings with
the phase of the decode chunks in flight (5-8 % between runs of one seed,
PR 24) and can carry no bound. The cell judged the 95th percentile end to end
until the check of PR 34 read that too wide for any bound as well
(`request.ttft_p95_ms.steady`); the cell's judged tail is `gap_p95_ms`."""
from harness.layer_common import ttft_percentile

read = ttft_percentile(50)

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "host_clock", "gap_p95_ms"
