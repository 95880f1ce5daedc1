"""95th percentile of time to first token in a cell that judges the median end
to end: with long prompts the tail is a few requests that queued behind
other prefills (5 % between runs, PR 24) and can carry no bound."""
from harness.layer_common import ttft_percentile

read = ttft_percentile(95)

LAYER, UNIT, BETTER = "provider boundary", "ms", "lower"
SOURCE, MOVES = "host_clock", "ttft_p50_ms"
