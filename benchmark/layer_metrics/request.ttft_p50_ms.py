"""Median time to first token in a cell that judges the 95th percentile end
to end: there the median swings with the phase of the decode chunks in
flight (5-8 % between runs of one seed, PR 24) and can carry no bound."""
from harness.layer_common import ttft_percentile

read = ttft_percentile(50)

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "host_clock", "ttft_p95_ms"
