"""How late the generator sent (sent - due, 95th percentile), in cells that judge the median first token."""
from harness.layer_common import late_p95_ms as read  # noqa: F401

LAYER, UNIT, BETTER = "benchmark generator", "ms", "lower"
SOURCE, MOVES = "host_clock", "ttft_p50_ms"
