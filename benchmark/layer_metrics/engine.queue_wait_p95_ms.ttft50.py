"""Wait for the scheduler in an open loop, submit -> claim
(`LatencyBreakdown.queue_s`, 95th percentile), in cells that judge the median first token."""
from harness.layer_common import queue_wait_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p50_ms"
