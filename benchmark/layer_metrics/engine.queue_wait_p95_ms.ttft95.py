"""Wait for the scheduler in an open loop, submit -> claim
(`LatencyBreakdown.queue_s`, 95th percentile), in the cell whose first-token tail is the 95th percentile
(`request.ttft_p95_ms.steady`; judged end to end until the check of PR 34, so the cell's `gap_p95_ms` is what it names)."""
from harness.layer_common import queue_wait_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "engine scheduler", "ms", "lower"
SOURCE, MOVES = "program_span", "gap_p95_ms"
