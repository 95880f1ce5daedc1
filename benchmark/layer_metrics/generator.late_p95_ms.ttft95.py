"""How late the generator sent (sent - due, 95th percentile), in the cell whose first-token tail is the
95th percentile (`request.ttft_p95_ms.steady`; judged end to end until the check of PR 34, so the cell's `gap_p95_ms` is what it names)."""
from harness.layer_common import late_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "benchmark generator", "ms", "lower"
SOURCE, MOVES = "host_clock", "gap_p95_ms"
