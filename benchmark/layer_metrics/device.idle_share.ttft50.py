"""1 - union of device op intervals over the traced window (open loop that judges the median first token)."""
from harness.layer_common import idle_share as read  # noqa: F401

LAYER, UNIT, BETTER = "device", "%", "lower"
SOURCE, MOVES = "device_trace", "ttft_p50_ms"
