"""1 - union of device op intervals over the traced window (closed loop)."""
from harness.layer_common import idle_share as read  # noqa: F401

LAYER, UNIT, BETTER = "device", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_chip"
